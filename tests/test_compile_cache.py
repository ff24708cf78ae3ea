"""The clock of set-up in `ray_tpu.util.compile_cache` (PR 52): what one
process leaves in its records after a few compiles on the CPU, read from a
child process (the listeners and JAX's cache are a process's own, and a
test worker's must stay as they are), and the listeners driven by hand
where no JAX is needed: the ring's bound, a trace that meets no
hand-over, threads that compile at once."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.util import compile_cache as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the child does: one plain jit, an inner jit traced inside an outer
# one, an eval_shape, a `.lower()` alone
CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp
import ray_tpu.models
from ray_tpu.util import compile_cache as cc

cc.enable_compile_cache()
x = jnp.ones(4)

@jax.jit
def only(x):
    return jnp.cos(x) * 3

@jax.jit
def inner(x):
    return jnp.sin(x) * 2

@jax.jit
def outer(x):
    return inner(x) + inner(x * 2) + 1

only(x)
mark = time.time()
outer(x)
jax.eval_shape(lambda x: inner(x) + 3, jnp.ones(5))
jax.jit(lambda x: x * 7).lower(jnp.ones(3))
from ray_tpu.serve.disagg import runtime_record
print(json.dumps({"programs": cc.compile_cache_programs(),
                  "since": cc.compile_cache_programs(since=mark),
                  "counts": cc.compile_cache_counts(),
                  "runtime": runtime_record(),
                  "imports": cc.import_spans(), "mark": mark}))
"""


def _child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The child twice against one cache directory: cold, then with a
    fresh in-memory state and the entries the first wrote."""
    cache_dir = tmp_path_factory.mktemp("xla_cache")
    return _child(cache_dir), _child(cache_dir)


def _named(out, name):
    return [r for r in out["programs"] if r["name"] == name]


def test_a_jitted_function_leaves_one_record(runs):
    (rec,) = _named(runs[0], "only")
    assert rec["hit"] is False and "fetch_s" not in rec
    assert min(rec["trace_s"], rec["lower_s"], rec["backend_s"]) > 0.0
    assert rec["t0"] < rec["t1"] and rec["thread"] == "MainThread"
    assert "parent" not in rec
    # the spans follow one another: their sum fits between the stamps
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] \
        <= rec["t1"] - rec["t0"] + 1e-6


def test_the_same_function_is_a_hit_in_a_fresh_process(runs):
    (rec,) = _named(runs[1], "only")
    assert rec["hit"] is True
    assert 0.0 < rec["fetch_s"] <= rec["backend_s"]
    assert rec["trace_s"] > 0.0 and rec["lower_s"] > 0.0
    counts = runs[1]["counts"]
    assert counts["misses"] == 0 and counts["miss_compile_s"] == 0.0
    assert counts["fetch_s"] == pytest.approx(sum(
        r["fetch_s"] for r in runs[1]["programs"] if r.get("hit")), abs=1e-5)


def test_an_inner_jit_carries_its_parent_and_counts_once(runs):
    out = runs[0]
    (outer,) = _named(out, "outer")
    assert not _named(out, "inner")  # handed over inside the outer's
    inner = [e for e in outer["inner"] if e["name"] == "inner"]
    assert [(e["parent"], e["n"]) for e in inner] == [("outer", 2)]
    assert outer["trace_s"] >= inner[0]["trace_s"] > 0.0
    # what `inner` traced lies in it, and so in the outer's once more
    assert {e["parent"] for e in outer["inner"]} == {"outer", "inner"}
    # the total is the union's: every outermost span once
    outermost = sum(r["trace_s"] for r in out["programs"]
                    if "parent" not in r)
    assert out["counts"]["trace_s"] == pytest.approx(outermost, abs=1e-4)
    assert out["counts"]["trace_s"] < outermost + inner[0]["trace_s"]


def test_a_trace_that_meets_no_hand_over_is_unattributed(runs):
    out = runs[0]
    last = out["programs"][-1]
    assert last["name"] == "unattributed" and "backend_s" not in last
    # eval_shape's lambda and the `.lower()` alone (still waiting)
    assert last["n"] == 2
    assert last["trace_s"] > 0.0 and last["lower_s"] > 0.0
    assert last["t0"] <= last["t1"]
    assert [r for r in out["programs"][:-1] if "backend_s" not in r] == []


def test_programs_since_a_time(runs):
    out = runs[0]
    names = [r["name"] for r in out["since"]]
    assert "only" not in names and "outer" in names
    assert names[-1] == "unattributed"
    assert all(r["t0"] >= out["mark"] for r in out["since"][:-1])


def test_the_four_old_keys_keep_their_meanings(runs):
    for out in runs:
        counts, records = out["counts"], [
            r for r in out["programs"] if "backend_s" in r]
        assert counts["compiles"] == len(records)
        assert counts["hits"] == sum(r["hit"] for r in records)
        assert counts["hits"] + counts["misses"] == counts["compiles"]
        assert counts["compile_s"] == pytest.approx(
            sum(r["backend_s"] for r in records), abs=2e-3)
        assert counts["compile_s"] == pytest.approx(
            counts["miss_compile_s"] + sum(
                r["backend_s"] for r in records if r["hit"]), abs=2e-3)
        assert all(isinstance(v, (int, float)) for v in counts.values())
    assert runs[0]["counts"]["hits"] <= 2  # a program met twice, at most
    assert runs[1]["counts"]["misses"] == 0


def test_a_replicas_stats_carry_the_totals_and_the_slowest(runs):
    for out in runs:
        runtime = out["runtime"]
        assert set(runtime) == {"compile_cache", "slowest_program",
                                "peak_bytes_in_use"}
        assert runtime["compile_cache"] == out["counts"]
        worst = max((r for r in out["programs"] if "backend_s" in r),
                    key=lambda r: r["trace_s"] + r["lower_s"]
                    + r["backend_s"])
        assert runtime["slowest_program"] == {
            "name": worst["name"], "seconds": round(
                worst["trace_s"] + worst["lower_s"] + worst["backend_s"], 3)}


def test_import_spans_hold_both_packages(runs):
    spans = {p: (t0, t1) for p, t0, t1 in runs[0]["imports"]}
    assert set(spans) == {"ray_tpu", "ray_tpu.models"}
    assert all(t1 >= t0 for t0, t1 in spans.values())
    # the models come after the package they are in
    assert spans["ray_tpu.models"][0] >= spans["ray_tpu"][1]


def test_the_module_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu.util.compile_cache; "
         "print('jax' in sys.modules)"],
        env=dict(os.environ, PYTHONPATH=ROOT), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip() == "False", proc.stderr[-2000:]


# ------------------------------------------------ the listeners, by hand

@pytest.fixture()
def clean(monkeypatch):
    """The module's state emptied for a test, and put back after it."""
    import collections

    monkeypatch.setattr(cc, "_counts", dict.fromkeys(cc._counts, 0))
    monkeypatch.setattr(cc, "_ring", collections.deque(maxlen=cc.RING))
    monkeypatch.setattr(cc, "_unattributed", {})
    monkeypatch.setattr(cc, "_threads", {})


def _span(event, name, start, seconds):
    cc._on_start(event, start, fun_name=name)
    cc._on_span(event, start, start + seconds, fun_name=name)


def _program(name, t, hit=False):
    """A program's three spans, as JAX reports them, from `t` on."""
    _span(cc._TRACE, name, t, 0.25)
    _span(cc._LOWER, f"jit({name})", t + 0.25, 0.5)
    cc._on_start(cc._BACKEND, t + 0.75, fun_name=f"jit({name})")
    if hit:
        cc._on_event("/jax/compilation_cache/cache_hits")
        cc._on_duration(cc._FETCH, 0.75)
    else:
        cc._on_event("/jax/compilation_cache/cache_misses")
    cc._on_span(cc._BACKEND, t + 0.75, t + 1.75, fun_name=f"jit({name})")


def test_the_ring_is_bounded(clean):
    for i in range(cc.RING + 10):
        _program(f"f{i}", 100.0 + 2 * i, hit=bool(i % 2))
    records = cc.compile_cache_programs()
    assert len(records) == cc.RING
    assert records[0]["name"] == "f10" and records[-1]["name"] == \
        f"f{cc.RING + 9}"
    assert records[-1] == {
        "name": f"f{cc.RING + 9}", "t0": 100.0 + 2 * (cc.RING + 9),
        "t1": 101.75 + 2 * (cc.RING + 9), "trace_s": 0.25, "lower_s": 0.5,
        "backend_s": 1.0, "hit": True, "fetch_s": 0.75,
        "thread": "MainThread"}
    # the totals are the process's, not the ring's
    n = cc.RING + 10
    assert cc.compile_cache_counts() == {
        "hits": n // 2, "misses": n // 2, "compiles": n,
        "compile_s": float(n), "trace_s": 0.25 * n, "lower_s": 0.5 * n,
        "fetch_s": 0.75 * (n // 2), "miss_compile_s": float(n // 2)}
    assert not cc._threads  # nothing is left waiting on this thread


def test_a_program_made_inside_a_trace_carries_its_parent(clean):
    """A value the tracing of `f` needed at once: `g` is traced, lowered
    and handed over while `f`'s trace is open."""
    cc._on_start(cc._TRACE, 10.0, fun_name="f")
    _program("g", 10.5)                      # 10.5 to 12.25, backend 1.0
    _span(cc._TRACE, "add", 12.5, 0.25)      # traced inside, no hand-over
    cc._on_span(cc._TRACE, 10.0, 13.0, fun_name="f")
    _span(cc._LOWER, "jit(f)", 13.0, 0.5)
    _span(cc._BACKEND, "jit(f)", 13.5, 2.0)
    g, f = cc.compile_cache_programs()
    assert (g["name"], g["parent"], g["backend_s"]) == ("g", "f", 1.0)
    assert "parent" not in f
    # f's trace is its span less the hand-over inside it
    assert (f["trace_s"], f["lower_s"], f["backend_s"]) == (2.0, 0.5, 2.0)
    assert f["inner"] == [{"name": "add", "parent": "f", "n": 1,
                           "trace_s": 0.25}]
    counts = cc.compile_cache_counts()
    assert (counts["trace_s"], counts["lower_s"], counts["compile_s"]) \
        == (2.0, 0.5, 3.0)


def test_traces_without_a_hand_over_are_one_record(clean):
    _span(cc._TRACE, "shape_only", 5.0, 0.5)      # an eval_shape
    _program("f", 6.0)
    _span(cc._TRACE, "lowered", 8.0, 0.25)        # a `.lower()` alone,
    _span(cc._LOWER, "jit(lowered)", 8.25, 0.25)  # still waiting
    f, rest = cc.compile_cache_programs()
    assert f["name"] == "f" and f["trace_s"] == 0.25
    assert rest == {"name": "unattributed", "n": 2, "t0": 5.0, "t1": 8.5,
                    "trace_s": 0.75, "lower_s": 0.25}
    assert cc.compile_cache_counts()["trace_s"] == 1.0
    # its hand-over comes after all: the record is the program's
    _span(cc._BACKEND, "jit(lowered)", 9.0, 1.0)
    f, lowered, rest = cc.compile_cache_programs()
    assert (lowered["name"], lowered["t0"], lowered["trace_s"],
            lowered["lower_s"]) == ("lowered", 8.0, 0.25, 0.25)
    assert (rest["n"], rest["trace_s"], rest["lower_s"]) == (1, 0.5, 0.0)


def test_threads_that_compile_at_once_lose_nothing(clean):
    """More threads than cores, a short switch interval: every program
    lands in the ring once and every second in the totals."""
    n_threads, each = 16, 40
    errors = []

    def work(k):
        try:
            for i in range(each):
                _program(f"t{k}_{i}", 1000.0 * k + 2 * i, hit=bool(i % 2))
        except Exception as e:  # noqa: BLE001 - shown by the assert below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,), name=f"w{k}")
                   for k in range(n_threads)]
        deadline = time.monotonic() + 60.0
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    n = n_threads * each
    records = cc.compile_cache_programs()
    assert len(records) == n
    assert len({r["name"] for r in records}) == n
    assert all(r["thread"] == "w" + r["name"][1:].split("_")[0]
               and (r["trace_s"], r["lower_s"], r["backend_s"])
               == (0.25, 0.5, 1.0) for r in records)
    counts = cc.compile_cache_counts()
    assert (counts["compiles"], counts["hits"], counts["misses"]) \
        == (n, n // 2, n // 2)
    assert counts["trace_s"] == 0.25 * n and counts["fetch_s"] == 0.375 * n
