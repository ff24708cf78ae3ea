"""The twelve readers of the engine's own clock (PR 24) on a hand-made
loop ring and `obs`, the rule that cuts the ring to the seconds in which
a cell offered its load, what the readers return over a program that has neither ring
nor parts, and the CPU rehearsal of the three serving cells printing
every new metric of the cell."""
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loop_records, readers  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = ("engine_queue_ms_", "engine_prefill_ms_", "admit_stall_ms_",
       "loop_", "tick_live_slots_", "pool_commit_")
NEW_METRICS = [m for m in BENCH["per_layer"] if m["name"].startswith(NEW)]
T0 = 1_000_000.0


def _admission(commit_ms, dispatches):
    return {"rid": 0, "prompt_tokens": 64, "suffix_tokens": 64,
            "reused_tokens": 0, "lookup_ms": 0.1, "prefill_ms": 5.0,
            "commit_ms": commit_ms, "commit_dispatches": dispatches,
            "commit_blocks": dispatches // 2, "splice_ms": 0.2}


def _record(ts, live, admit_ms=0.0, admissions=(), dispatch=0.5,
            readback=40.0, emit=0.25, extra=0.25):
    return {"engine_id": "cb-test", "ts": ts, "live": live,
            "max_batch": 8, "pending": 0, "admit_ms": admit_ms,
            "admissions": list(admissions), "dispatch_ms": dispatch,
            "readback_ms": readback, "emit_ms": emit,
            "total_ms": admit_ms + dispatch + readback + emit + extra}


def _summary_record(i, ts, total_ms, phase_ms):
    return {"kind": "trace", "request_id": f"r{i}", "ts": ts,
            "total_ms": total_ms, "outcome": "ok", "attempts": 1,
            "replayed": False, "preempts": 0, "phases": [],
            "phase_ms": phase_ms}


# the ring: a warm-up pass before the requests, five passes inside the
# two seconds of offered load (one begins with nothing decoding), one
# pass of the drain after them
RING = [
    _record(T0 - 5.0, live=1, admit_ms=900.0,
            admissions=[_admission(500.0, 999)]),
    _record(T0 + 0.1, live=0, admit_ms=30.0,
            admissions=[_admission(8.0, 8)]),
    _record(T0 + 0.2, live=1),
    _record(T0 + 0.3, live=2, admit_ms=20.0,
            admissions=[_admission(4.0, 4)], dispatch=1.5, emit=0.75),
    _record(T0 + 0.4, live=3),
    _record(T0 + 1.9, live=2),
    _record(T0 + 3.0, live=1, admit_ms=700.0,
            admissions=[_admission(300.0, 777)]),
]
PHASES = [
    {"decode_first_token": 50.0, "decode_steady": 400.0,
     "decode_first_token.engine_queue": 10.0,
     "decode_first_token.engine_prefill": 38.0},
    {"decode_first_token": 90.0, "decode_steady": 900.0,
     "decode_first_token.engine_queue": 30.0,
     "decode_first_token.engine_prefill": 58.0},
]
# over the four passes inside that began with a slot decoding
WANT = {
    "engine_queue_ms_p95.lat": 29.0,
    "engine_queue_ms_p50.ttft": 20.0,
    "engine_prefill_ms_p95.lat": 57.0,
    "engine_prefill_ms_p50.ttft": 48.0,
    "admit_stall_ms_p95.itl": 17.0,       # of 0, 0, 0, 20
    "loop_host_ms_mean.itl": 1.375,       # (1 + 2.5 + 1 + 1) / 4
    "loop_dispatch_ms_mean.itl": 0.75,    # (0.5 + 1.5 + 0.5 + 0.5) / 4
    "loop_emit_ms_mean.itl": 0.375,
    "tick_live_slots_mean.itl": 2.0,      # (1 + 2 + 3 + 2) / 4
    "tick_live_slots_mean.tput": 2.0,
    "pool_commit_ms_mean.tput": 6.0,      # the two admissions inside
    "pool_commit_dispatches_mean.tput": 6.0,
}


@pytest.fixture()
def store():
    reqtrace._reset_store_for_tests()
    st = reqtrace.store()
    yield st
    reqtrace._reset_store_for_tests()


@pytest.fixture()
def obs(store):
    """Two measured requests, the first from T0, after a warm-up one;
    the cell offered load for two seconds."""
    for rec in RING:
        store.record_loop(rec)
    store.record(_summary_record(0, T0 - 6.0, 1500.0, {"prefill": 1.0}))
    store.record(_summary_record(1, T0, 450.0, PHASES[0]))
    store.record(_summary_record(2, T0 + 1.0, 1000.0, PHASES[1]))
    return {"phases": list(PHASES), "cell": {"seconds": 2.0}}


def test_every_new_metric_has_its_case():
    assert sorted(WANT) == sorted(m["name"] for m in NEW_METRICS)
    assert len(WANT) == 12
    assert {m["layer"] for m in NEW_METRICS} == {"engine and cache"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_ring(name, obs):
    assert readers.load_reader(name)(obs) == pytest.approx(WANT[name])


def test_the_window_rule_keeps_the_seconds_of_offered_load(obs):
    kept = loop_records.window(obs)
    assert [r["ts"] for r in kept] == [T0 + 0.1, T0 + 0.2, T0 + 0.3,
                                       T0 + 0.4, T0 + 1.9]
    assert [r["ts"] for r in loop_records.decoding(obs)] == [
        T0 + 0.2, T0 + 0.3, T0 + 0.4, T0 + 1.9]
    assert [a["commit_dispatches"]
            for a in loop_records.admissions(obs)] == [8, 4]
    # both edges are kept; the second request ends at T0 + 2.0 and the
    # drain after the offered load is cut whatever still decodes in it
    edge = [{"ts": T0 - 1e-3}, {"ts": T0}, {"ts": T0 + 1.5},
            {"ts": T0 + 1.5 + 1e-3}]
    rows = [{"ts": T0 + 1.0, "total_ms": 1000.0},
            {"ts": T0, "total_ms": 450.0}]
    assert loop_records.cut(edge, rows, 1.5) == edge[1:3]
    # summaries without a start (the parent's) keep nothing
    assert loop_records.cut(edge, [{"total_ms": 5.0}], 1.5) == []


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_returns_none_where_there_is_nothing(name, store):
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": {"seconds": 2.0}}) is None
    # a program without the ring or the parts, as the parent of PR 24
    store.record(_summary_record(0, T0, 100.0, {}))
    assert read({"phases": [{"decode_first_token": 50.0}],
                 "cell": {"seconds": 2.0}}) is None


def test_a_store_without_the_ring_reads_as_empty(monkeypatch, obs):
    monkeypatch.delattr(reqtrace.RequestTraceStore, "loop_records")
    assert loop_records.window(obs) == []
    assert readers.load_reader("loop_host_ms_mean.itl")(obs) is None


@pytest.mark.parametrize("workload", ["mistral-chat", "mistral-summarize",
                                      "gpt2-chat"])
def test_rehearsal_prints_every_new_metric(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         workload, "--seed", "2000000011", "--seconds", "3", "--trace",
         "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    want = {m["name"]: m["unit"] for m in NEW_METRICS
            if workload in m["workloads"]}
    assert len(want) in (5, 6, 7)
    for name, unit in want.items():
        got = result["metrics"][name]
        assert got["unit"] == unit
        assert math.isfinite(got["value"]) and got["value"] >= 0.0, name
    m = {k: v["value"] for k, v in result["metrics"].items()}
    slots = 4  # rehearse.py's toy max_batch
    for key in ("tick_live_slots_mean.itl", "tick_live_slots_mean.tput"):
        if key in m:
            assert 1.0 <= m[key] <= slots
    if "loop_host_ms_mean.itl" in m:
        assert m["loop_dispatch_ms_mean.itl"] \
            + m["loop_emit_ms_mean.itl"] <= m["loop_host_ms_mean.itl"]
    if "pool_commit_dispatches_mean.tput" in m:
        # prompts of 8 and 16 tokens in 16-token blocks
        assert m["pool_commit_dispatches_mean.tput"] == 2.0
        assert m["pool_commit_ms_mean.tput"] > 0.0
    if "engine_queue_ms_p95.lat" in m:
        assert m["engine_prefill_ms_p95.lat"] > 0.0
        assert m["engine_prefill_ms_p95.lat"] \
            <= m["first_token_wait_ms_p95.lat"]
