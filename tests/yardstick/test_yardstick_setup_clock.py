"""The six readers of set-up's clock (PR 52) on hand-made records of
`ray_tpu.util.compile_cache`, each value worked out beside it; what they
return over a program without the clock (the parent of PR 52); the rule
that cuts the records to set-up, on a ring with one record after the
first request; and the CPU rehearsal of a serving cell and of the
training cell printing all six, the sum of the four parts inside
`setup_s` and `programs.setup` equal to the record file's count."""
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import rehearse  # noqa: E402
from benchmarks.harness import readers, setup_clock  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.util import compile_cache  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = ["import_s.setup", "programs.setup", "trace_lower_s.setup",
       "cache_fetch_s.setup", "backend_compile_s.setup",
       "slowest_program_s.setup"]
T0 = 2_000_000.0  # the first measured request


def _program(name, t0, trace, lower, backend, fetch=None, **more):
    rec = {"name": name, "t0": t0, "t1": t0 + trace + lower + backend,
           "trace_s": trace, "lower_s": lower, "backend_s": backend,
           "hit": fetch is not None, "thread": "MainThread", **more}
    if fetch is not None:
        rec["fetch_s"] = fetch
    return rec


# set-up: three hits, a miss, a program made inside `_tick`'s trace
# (its trace and lowering lie in `_tick`'s), two traces that met no
# hand-over; then ONE program the window compiled, under request r1
RING = [
    _program("init", T0 - 20.0, 0.5, 0.25, 1.0, fetch=0.75),
    _program("rope_table", T0 - 15.5, 0.125, 0.125, 0.25, fetch=0.25,
             parent="_tick"),
    _program("_tick", T0 - 16.0, 2.0, 1.0, 1.5, fetch=1.25, inner=[
        {"name": "_layer", "parent": "_tick", "n": 8, "trace_s": 1.5},
        {"name": "add", "parent": "_layer", "n": 30, "trace_s": 0.25}]),
    _program("_prefill_paged", T0 - 8.0, 1.0, 0.5, 4.0),
    _program("_prefill_paged", T0 + 0.5, 0.75, 0.25, 3.0, thread="cb-engine"),
    {"name": "unattributed", "n": 2, "t0": T0 - 18.0, "t1": T0 - 17.0,
     "trace_s": 0.5, "lower_s": 0.25},
]
IMPORTS = [("ray_tpu", 100.0, 100.5), ("ray_tpu.models", 100.25, 104.0),
           ("ray_tpu.other", 110.0, 110.25)]
WANT = {
    "import_s.setup": 4.25,             # 100 to 104, and a quarter
    "programs.setup": 4.0,              # the four before T0
    # init 0.75, _tick 3.0, _prefill_paged 1.5, unattributed 0.75; not
    # rope_table's, which lie inside _tick's trace
    "trace_lower_s.setup": 6.0,
    "cache_fetch_s.setup": 2.25,        # 0.75 + 0.25 + 1.25
    "backend_compile_s.setup": 4.0,     # the one miss of set-up
    "slowest_program_s.setup": 5.5,     # _prefill_paged: 1 + 0.5 + 4
}


def _summary(i, ts, total_ms=500.0):
    return {"kind": "trace", "request_id": f"r{i}", "ts": ts,
            "total_ms": total_ms, "outcome": "ok", "attempts": 1,
            "replayed": False, "preempts": 0, "phases": [], "phase_ms": {}}


@pytest.fixture()
def store():
    reqtrace._reset_store_for_tests()
    st = reqtrace.store()
    yield st
    reqtrace._reset_store_for_tests()


@pytest.fixture()
def clock(monkeypatch, store):
    """A program whose clock holds RING and IMPORTS, and a serving cell
    that measured three requests from T0 on after a warm-up one."""
    monkeypatch.setattr(compile_cache, "compile_cache_programs",
                        lambda since=0.0: [dict(r) for r in RING])
    monkeypatch.setattr(compile_cache, "import_spans",
                        lambda: list(IMPORTS))
    for i, ts in enumerate((T0 - 6.0, T0 + 0.25, T0, T0 + 2.0)):
        store.record(_summary(i, ts, total_ms=1000.0 if i == 1 else 100.0))
    return {"phases": [{}, {}, {}], "cell": {"seconds": 3.0}}


def test_the_entries_are_the_issues_table():
    entries = BENCH["per_layer"][-6:]
    assert [m["name"] for m in entries] == NEW
    for m in entries:
        assert (m["layer"], m["moves"], m["better"]) == (
            "runtime", "setup_s", "lower")
        assert "workloads" not in m  # every cell reports `setup_s`
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        counted = m["name"] == "programs.setup"
        assert m["unit"] == ("count" if counted else "s")
        assert m["source"] == ("program_counter" if counted
                               else "program_span")
    # as the one reader of set-up before them
    old = next(m for m in BENCH["per_layer"]
               if m["name"] == "compile_cache_misses.setup")
    assert "workloads" not in old and old["layer"] == "runtime"


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_made_records(name, clock):
    assert sorted(WANT) == sorted(NEW)
    assert readers.load_reader(name)(clock) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_a_training_cell_keeps_every_record(name, clock):
    """No request was measured: the record of T0 + 0.5 is set-up's too."""
    obs = {"train": {"step_s": [0.25]}, "cell": {}}
    want = dict(WANT, **{"programs.setup": 5.0, "trace_lower_s.setup": 7.0,
                         "backend_compile_s.setup": 7.0})
    assert readers.load_reader(name)(obs) == pytest.approx(want[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_over_a_program_without_the_clock(
        name, clock, monkeypatch):
    """The parent of PR 52: `compile_cache` has the counts alone."""
    monkeypatch.delattr(compile_cache, "compile_cache_programs")
    monkeypatch.delattr(compile_cache, "import_spans")
    assert readers.load_reader(name)(clock) is None
    assert readers.load_reader(name)({"train": {}, "cell": {}}) is None


def test_the_cut_rule(clock):
    setup, after = setup_clock.split(clock)
    assert [r["name"] for r in after] == ["_prefill_paged"]
    assert after[0]["t0"] == T0 + 0.5
    assert len(setup) == 5 and setup[-1]["name"] == "unattributed"
    # a warm-up request's start is not the window's: the three NEWEST
    # summaries are the measured ones, whatever order they finished in
    assert setup_clock.first_request_ts(clock) == T0
    assert setup_clock.first_request_ts({"phases": []}) == math.inf
    # on plain data: `t0` strictly before the first request
    a, b = setup_clock.cut([{"t0": 1.0}, {"t0": 2.0}, {"t0": 3.0}], 2.0)
    assert (a, b) == ([{"t0": 1.0}], [{"t0": 2.0}, {"t0": 3.0}])
    assert setup_clock.cut([{"t0": 9e9}], math.inf) == ([{"t0": 9e9}], [])


def test_the_union_of_spans():
    assert setup_clock.union_s([]) == 0.0
    assert setup_clock.union_s([(3.0, 4.0), (0.0, 1.0)]) == 2.0
    # one inside another, and two that overlap
    assert setup_clock.union_s([(0.0, 4.0), (1.0, 2.0)]) == 4.0
    assert setup_clock.union_s([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]) == 4.0


def test_a_compile_after_set_up_is_named_with_its_requests(clock, capsys):
    assert readers.load_reader("programs.setup")(clock) == 4.0
    line = capsys.readouterr().err
    assert "programs.setup: AFTER set-up _prefill_paged 4.000 (trace " \
        "0.750, lower 0.250, backend 3.000: compiled)" in line
    assert f"at t0 {T0 + 0.5:.3f} on cb-engine" in line
    # r1 ran from T0 + 0.25 for a second, r2 ended at T0 + 0.1, r3 began
    # at T0 + 2.0, inside the record's 4 s; the warm-up's r0 is not asked
    assert line.rstrip().endswith("under requests r1, r3")


def test_the_slowest_and_the_inner_traces_are_logged(clock, capsys):
    readers.load_reader("slowest_program_s.setup")(clock)
    readers.load_reader("trace_lower_s.setup")(clock)
    readers.load_reader("import_s.setup")(clock)
    err = capsys.readouterr().err
    first = err.index("slowest_program_s.setup: _prefill_paged 5.500 "
                      "(trace 1.000, lower 0.500, backend 4.000: compiled)")
    assert first < err.index("_tick 4.500 (trace 2.000, lower 1.000, "
                             "backend 1.500: fetch 1.250)") \
        < err.index("init 1.750") < err.index("rope_table 0.500")
    assert "trace_lower_s.setup: trace 4.000 s, lowering 2.000 s; the " \
        "largest traced inside another: _layer in _tick 1.500 s x8, add " \
        "in _layer 0.250 s x30" in err
    assert "import_s.setup: ray_tpu 0.500 s, ray_tpu.models 3.750 s" in err


@pytest.mark.parametrize("workload", [rehearse.cells("serve")[0],
                                      rehearse.cells("train")[0]])
def test_rehearsal_prints_all_six(workload, tmp_path):
    seed = "2000000052"  # no other test's: the record file is this run's
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         workload, "--seed", seed, "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    m = {}
    for name in NEW:
        assert result["metrics"][name]["unit"] == units[name]
        m[name] = result["metrics"][name]["value"]
        assert math.isfinite(m[name]) and m[name] >= 0.0, name
    with open(os.path.join(ROOT, "chiprun_out",
                           f"bench_{workload}_s{seed}_t1.json")) as f:
        record = json.load(f)
    # the check of the cut rule: nothing compiled after set-up
    assert m["programs.setup"] == record["cache_at_setup"]["compiles"] > 0
    assert "programs.setup: AFTER set-up" not in proc.stderr
    # an empty cache directory: every program of set-up was compiled
    misses = result["metrics"]["compile_cache_misses.setup"]["value"]
    assert misses > 0 and m["backend_compile_s.setup"] > 0.0
    assert m["backend_compile_s.setup"] == pytest.approx(
        record["cache_at_setup"]["miss_compile_s"], abs=1e-3)
    assert m["cache_fetch_s.setup"] == pytest.approx(
        record["cache_at_setup"]["fetch_s"], abs=1e-3)
    assert m["trace_lower_s.setup"] == pytest.approx(
        record["cache_at_setup"]["trace_s"]
        + record["cache_at_setup"]["lower_s"], abs=1e-3)
    assert min(m["import_s.setup"], m["trace_lower_s.setup"]) > 0.0
    assert m["slowest_program_s.setup"] <= m["trace_lower_s.setup"] \
        + m["cache_fetch_s.setup"] + m["backend_compile_s.setup"]
    # the parts lie inside what they are parts of
    assert m["import_s.setup"] + m["trace_lower_s.setup"] \
        + m["cache_fetch_s.setup"] + m["backend_compile_s.setup"] \
        <= record["setup_s"]
    for name in ("import_s", "trace_lower_s", "slowest_program_s"):
        assert f"{name}.setup: " in proc.stderr
