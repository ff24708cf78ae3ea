"""The nine readers of the engine's gap ledger (PR 35) on a hand-made loop
ring, each value worked out beside it; what they return over a program
whose ring has no `gap_ms` (the parent of PR 35); and the CPU rehearsal of
one cell judged on the gap between tokens and of the two judged on tokens
per second printing every new metric of the cell."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import rehearse  # noqa: E402
from benchmarks.harness import gap_ledger, readers  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = ("engine_gap_ms_", "gap_", "chip_empty_", "ttft_collision_share.")
NEW_METRICS = [m for m in BENCH["per_layer"] if m["name"].startswith(NEW)]
ITL = ["mistral-chat", "gpt2-chat", "nemotron-3-super-reason",
       "kimi-linear-generate"]
T0 = 2_000_000.0


def _admission(waited=None):
    a = {"rid": 0, "prompt_tokens": 64, "suffix_tokens": 64,
         "reused_tokens": 0, "lookup_ms": 0.1, "prefill_ms": 5.0,
         "commit_ms": 1.0, "commit_dispatches": 2, "commit_blocks": 1,
         "splice_ms": 0.2}
    if waited is not None:
        a["prefills_waited"] = waited
    return a


def _record(ts, live, entries=(), **gap):
    """A pass of the ring; `gap`: the ledger's fields, where the pass
    landed a tick (`ms` absent: no stream felt the gap)."""
    rec = {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 8,
           "pending": 0, "admit_ms": 0.0, "admissions": list(entries),
           "dispatch_ms": 0.5, "readback_ms": 4.0, "emit_ms": 0.25,
           "total_ms": 5.0, "inflight": 1, "discarded": 0}
    if gap:
        by = gap.get("by", {})
        rec.update(gap_streams=gap["streams"],
                   gap_admissions=gap.get("admissions", 0),
                   gap_blocked_ms=gap.get("blocked", 0.0),
                   gap_empty_ms=sum(by.values()), gap_empty_by=by)
        if "ms" in gap:
            rec["gap_ms"] = gap["ms"]
    return rec


# the ring: a warm-up gap before the requests; inside the two seconds of
# offered load a first landing that ends no gap, five gaps (two of them
# held an admission) and a pass that admitted and landed nothing; a gap
# of the drain after them
RING = [
    _record(T0 - 5.0, 1, [_admission(5)], streams=1, ms=900.0, admissions=1,
            by={"splice": 300.0}),
    _record(T0 + 0.1, 1, [_admission(0)], streams=0, admissions=1,
            by={"first_token": 0.5, "splice": 0.5}),
    _record(T0 + 0.2, 1, streams=1, ms=10.0, blocked=9.0),
    _record(T0 + 0.3, 3, [_admission(0)], streams=1, ms=50.0, admissions=1,
            blocked=40.0,
            by={"first_token": 1.0, "splice": 2.0, "tick_dispatch": 1.0}),
    _record(T0 + 0.4, 3, streams=3, ms=12.0, blocked=11.0),
    _record(T0 + 0.5, 0, [_admission(1)]),
    _record(T0 + 0.6, 4, [_admission(2)], streams=2, ms=40.0, admissions=1,
            blocked=30.0, by={"emit": 0.5, "lookup": 0.5, "splice": 1.0}),
    _record(T0 + 1.9, 4, streams=4, ms=20.0, blocked=19.0),
    _record(T0 + 3.0, 1, [_admission(7)], streams=1, ms=700.0, admissions=1,
            by={"bookkeeping": 100.0}),
]
# the stream-gaps inside: 10 | 50 | 12 12 12 | 40 40 | 20 20 20 20
ALL = [10.0, 50.0, 12.0, 12.0, 12.0, 40.0, 40.0, 20.0, 20.0, 20.0, 20.0]
WANT = {
    "engine_gap_ms_p95.itl": float(np.percentile(ALL, 95)),    # 45.0
    "gap_admission_share.itl": 100.0 * 3 / 11,     # 50 and twice 40
    "gap_admission_ms_mean.itl": 130.0 / 3,
    "gap_steady_ms_p95.itl": 20.0,                 # of 10, 3 x 12, 4 x 20
    "chip_empty_ms_per_admission.itl": 3.0,        # (4 + 2) / 2 gaps
    "chip_empty_share.itl": 100.0 * 6.0 / 132.0,   # each gap once
    "chip_empty_share.tput": 100.0 * 6.0 / 132.0,
    # the four admissions inside waited behind 0, 0, 1 and 2 prefills
    "ttft_collision_share.ttft": 50.0,
    "ttft_collision_share.tput": 50.0,
}


def _summary(i, ts):
    return {"kind": "trace", "request_id": f"r{i}", "ts": ts,
            "total_ms": 500.0, "outcome": "ok", "attempts": 1,
            "replayed": False, "preempts": 0, "phases": [], "phase_ms": {}}


@pytest.fixture()
def store():
    reqtrace._reset_store_for_tests()
    st = reqtrace.store()
    yield st
    reqtrace._reset_store_for_tests()


def _obs(store, ring):
    """Two measured requests, the first from T0, after a warm-up one;
    the cell offered load for two seconds."""
    for rec in ring:
        store.record_loop(rec)
    for i, ts in enumerate((T0 - 6.0, T0, T0 + 1.0)):
        store.record(_summary(i, ts))
    return {"phases": [{}, {}], "cell": {"seconds": 2.0}}


def test_the_entries_are_the_issues_table():
    assert sorted(WANT) == sorted(m["name"] for m in NEW_METRICS)
    assert len(WANT) == 9
    # appended, in one layer, all to be lowered
    assert BENCH["per_layer"][-9:] == NEW_METRICS
    assert {m["layer"] for m in NEW_METRICS} == {"engine and cache"}
    assert {m["better"] for m in NEW_METRICS} == {"lower"}
    by = {m["name"]: m for m in NEW_METRICS}
    for name, m in by.items():
        if name.endswith(".itl"):
            assert (m["moves"], m["workloads"]) == ("itl_p95_ms", ITL)
        assert m["unit"] == ("%" if "share" in name else "ms")
    assert by["chip_empty_share.tput"]["workloads"] == [
        "mistral-summarize", "deepseek-v2-longdoc"]
    assert (by["ttft_collision_share.ttft"]["moves"],
            by["ttft_collision_share.ttft"]["workloads"]) == (
        "ttft_p75_ms", ["mistral-summarize"])
    assert by["ttft_collision_share.tput"]["workloads"] == [
        "deepseek-v2-longdoc"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_ring(name, store):
    assert readers.load_reader(name)(_obs(store, RING)) \
        == pytest.approx(WANT[name])


def test_a_stream_gap_is_a_gap_once_a_stream_that_felt_it(store):
    obs = _obs(store, RING)
    felt = gap_ledger.gaps(obs)
    assert [r["ts"] for r in felt] == [T0 + 0.2, T0 + 0.3, T0 + 0.4,
                                       T0 + 0.6, T0 + 1.9]
    assert gap_ledger.stream_gaps(felt).tolist() == ALL
    assert gap_ledger.streams(felt) == 11
    assert [r["gap_ms"] for r in
            gap_ledger.gaps(obs, gap_ledger.held_admission)] == [50.0, 40.0]
    assert [r["gap_ms"] for r in gap_ledger.gaps(obs, gap_ledger.steady)] \
        == [10.0, 12.0, 20.0]
    # a landing that ended no gap is in no count, whatever it carries
    assert all("gap_ms" in r for r in felt)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_returns_none_over_a_ring_without_the_ledger(name, store):
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": {"seconds": 2.0}}) is None
    # the parent of PR 35: the ring and its admissions, but no `gap_ms`
    # on a record and no `prefills_waited` on an admission
    parent = [_record(r["ts"], r["live"],
                      [_admission() for _ in r["admissions"]])
              for r in RING]
    assert read(_obs(store, parent)) is None


def test_the_starved_seconds_are_logged_by_step(store, capsys):
    value = readers.load_reader("chip_empty_share.itl")(_obs(store, RING))
    assert value == pytest.approx(100.0 * 6.0 / 132.0)
    line = capsys.readouterr().err
    assert "chip_empty_share.itl: chip starved 0.0060 s of 0.132 s" in line
    # the largest first: splice 3 ms, then the three of a millisecond
    # and the two of half of one
    assert line.index("splice 0.0030") < line.index("first_token 0.0010") \
        < line.index("lookup 0.0005")
    assert "tick_dispatch 0.0010" in line and "emit 0.0005" in line


@pytest.mark.parametrize("workload", ["gpt2-chat", "mistral-summarize",
                                      "deepseek-v2-longdoc"])
def test_rehearsal_prints_every_new_metric(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         workload, "--seed", "2000000033", "--seconds", "3", "--trace",
         "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    want = {m["name"]: m["unit"] for m in NEW_METRICS
            if workload in m["workloads"]}
    assert len(want) == (6 if workload in ITL else 2)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name, unit in want.items():
        assert result["metrics"][name]["unit"] == unit
        assert math.isfinite(m[name]) and m[name] >= 0.0, name
        if unit == "%":
            assert m[name] <= 100.0
    if workload in ITL:
        # some stream decoded through an admission, and every gap has a
        # length (which of the two tails is the longer is the chip's to
        # say: here a toy model's ticks take what the CPU gives them)
        assert m["gap_admission_share.itl"] > 0.0
        assert min(m["engine_gap_ms_p95.itl"], m["gap_steady_ms_p95.itl"],
                   m["gap_admission_ms_mean.itl"]) > 0.0
    assert f"chip_empty_share.{'itl' if workload in ITL else 'tput'}: " \
        "chip starved" in proc.stderr
