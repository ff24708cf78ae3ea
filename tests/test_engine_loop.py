"""The engine loop's own clock (models/engine.py, PR 24): one record per
iteration in the flight recorder's store, the first token's wait split
into the engine's queue and the request's own prefill, the same
boundaries as `engine.*` spans in a `jax.profiler` trace, and nothing
of it while `RAY_TPU_REQTRACE=0`."""
from __future__ import annotations

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.observability import requests as reqtrace
from ray_tpu.serve.disagg import DecodeServer, DisaggRouter, PrefillServer
from ray_tpu.util import envknobs, profiling

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4
PROMPT = list(range(1, 20))  # 19 tokens: four blocks of 4 and a tail of 3
FIELDS = {"engine_id", "ts", "live", "live_rows", "max_batch", "pending",
          "admit_ms", "admissions", "dispatch_ms", "readback_ms",
          "emit_ms", "total_ms"}
ADMISSION_FIELDS = {"rid", "prompt_tokens", "suffix_tokens",
                    "reused_tokens", "lookup_ms", "prefill_ms",
                    "commit_ms", "commit_dispatches", "commit_blocks",
                    "splice_ms"}


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def fresh_store():
    reqtrace._reset_store_for_tests()
    envknobs.clear()
    yield
    reqtrace._reset_store_for_tests()
    envknobs.clear()


@pytest.fixture()
def engine(model):
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4,
                                   kv_block_size=BS)
    yield eng
    eng.stop()


def _ring(eng):
    return [r for r in reqtrace.store().loop_records()
            if r["engine_id"] == eng.engine_id]


def test_one_record_per_iteration_and_the_parts_fit(engine):
    assert len(engine.generate(PROMPT, 6)) == 6
    ring = _ring(engine)
    # the admitting pass emits the prefill's token and one tick's;
    # every further pass emits one
    assert len(ring) == 5
    for r in ring:
        assert set(r) == FIELDS and r["max_batch"] == 4
        parts = (r["admit_ms"] + r["dispatch_ms"] + r["readback_ms"]
                 + r["emit_ms"])
        assert 0.0 < parts <= r["total_ms"]
        assert min(r["dispatch_ms"], r["readback_ms"], r["emit_ms"]) > 0
    assert [r["live"] for r in ring] == [0, 1, 1, 1, 1]
    assert ring[0]["pending"] == 1 and ring[1]["pending"] == 0
    assert all(a["ts"] <= b["ts"] for a, b in zip(ring, ring[1:]))
    # nothing was admitted after the first pass, so nothing is charged
    assert [r["admit_ms"] > 0 for r in ring] == [True] + [False] * 4


def test_live_is_the_slots_in_flight(engine):
    budgets = [5, 9, 7]
    streams = [engine.stream([3 + i, 5, 7], n)
               for i, n in enumerate(budgets)]
    for s in streams:
        assert len(list(s)) == s._req.max_new
    ring = _ring(engine)
    admitted = {a["rid"]: i for i, r in enumerate(ring)
                for a in r["admissions"]}
    assert sorted(admitted) == [0, 1, 2]
    for j, r in enumerate(ring):
        # admitted in pass i with a budget of n, a request decodes at
        # the top of passes i+1 .. i+n-2 (two tokens in pass i)
        want = sum(1 for rid, i in admitted.items()
                   if i < j <= i + budgets[rid] - 2)
        assert r["live"] == want, (j, r["live"], want)
        assert len(r["admissions"]) <= engine.max_prefills_per_tick


def test_admission_entry_on_a_cold_and_a_warm_cache(engine):
    engine.generate(PROMPT, 3)
    engine.generate(PROMPT, 3)
    cold, warm = [a for r in _ring(engine) for a in r["admissions"]]
    assert set(cold) == ADMISSION_FIELDS
    assert (cold["rid"], cold["prompt_tokens"], cold["suffix_tokens"],
            cold["reused_tokens"]) == (0, 19, 19, 0)
    # four full blocks and the tail in one program, launched for the
    # keys' pool and for the values': never a program a block
    assert cold["commit_blocks"] == 5
    assert 1 <= cold["commit_dispatches"] <= 2
    assert min(cold["lookup_ms"], cold["prefill_ms"], cold["commit_ms"],
               cold["splice_ms"]) > 0
    # at most 18 tokens may match (one is left to prefill): four blocks
    assert (warm["rid"], warm["prompt_tokens"], warm["suffix_tokens"],
            warm["reused_tokens"]) == (1, 19, 3, 16)
    assert warm["commit_blocks"] == 0 and warm["commit_dispatches"] == 0
    assert engine.kv_cache.last_commit == (0, 0)


def test_prompts_of_any_length_share_one_commit_program(model):
    """The commit's program takes the cache window whole and a vector of
    pool rows of fixed length, so its shape depends on the window and
    the pool alone: what keeps `compiles_in_window` at 0 on traffic of
    any length. The pool's size is this test's own, so that no other
    test's engine has compiled the program first."""
    from ray_tpu.models.kvcache import _commit_blocks

    eng = ContinuousBatchingEngine(model, CFG, max_batch=4,
                                   kv_block_size=BS, kv_pool_blocks=37)
    before = _commit_blocks._cache_size()
    try:
        for n in (3, 10, 19):   # a tail alone; two blocks and a tail; four
            eng.generate([40 + n + i for i in range(n)], 2)
    finally:
        eng.stop()
    blocks = [a["commit_blocks"] for r in _ring(eng)
              for a in r["admissions"]]
    assert blocks == [1, 3, 5]
    assert _commit_blocks._cache_size() - before == 1


def test_the_admissions_parts_fit_admit_ms(engine):
    engine.generate(PROMPT, 3)
    first = _ring(engine)[0]
    a = first["admissions"][0]
    assert (a["lookup_ms"] + a["prefill_ms"] + a["commit_ms"]
            + a["splice_ms"]) <= first["admit_ms"]


def test_stream_has_its_split_before_the_first_token(engine):
    stream = engine.stream(PROMPT, 4)
    assert next(stream) is not None
    q, p = stream.queue_ms, stream.prefill_ms
    assert q is not None and p is not None and q >= 0.0 and p > 0.0
    rest = list(stream)
    assert len(rest) == 3
    assert (stream.queue_ms, stream.prefill_ms) == (q, p)
    # the request's own work is what the admitting pass spent on it
    first = _ring(engine)[0]
    assert p <= first["admit_ms"]


def test_adoption_is_an_admission_without_a_prefill(model):
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=2)
    try:
        rec = pf.prefill(PROMPT)
        stream = dec.stream_from(rec, 4)
        assert len(list(stream)) == 4
        assert stream.queue_ms is not None and stream.prefill_ms > 0
    finally:
        dec.stop()
    ring = _ring(dec.engine)
    (a,) = [a for r in ring for a in r["admissions"]]
    assert set(a) == ADMISSION_FIELDS
    assert a["prompt_tokens"] == 19 and a["prefill_ms"] == 0.0
    assert a["commit_dispatches"] == 0 and a["splice_ms"] > 0
    # the prefill server shares _prefill_with_cache and needs no ring
    assert all(r["engine_id"] == dec.engine.engine_id
               for r in reqtrace.store().loop_records())


def test_speculative_tick_records_the_same_fields(model):
    eng = ContinuousBatchingEngine(
        model, CFG, max_batch=2, speculate_k=2,
        draft_source=lambda ctx, k: [ctx[-1]] * k)
    try:
        assert len(eng.generate([4, 5, 6], 8)) == 8
    finally:
        eng.stop()
    ring = _ring(eng)
    assert eng.spec_verify_ticks >= 1 and ring
    for r in ring:
        assert set(r) == FIELDS
        assert min(r["dispatch_ms"], r["readback_ms"], r["emit_ms"]) > 0
        assert (r["admit_ms"] + r["dispatch_ms"] + r["readback_ms"]
                + r["emit_ms"]) <= r["total_ms"]


@pytest.mark.parametrize("path", ["colocated", "disagg"])
def test_router_hands_the_split_to_the_flight_recorder(model, path):
    if path == "colocated":
        eng = ContinuousBatchingEngine(model, CFG, max_batch=2,
                                       kv_block_size=BS)
        router, stop = DisaggRouter(colocated=eng), eng.stop
    else:
        pf = PrefillServer(model, CFG, kv_block_size=BS,
                           kv_pool_blocks=32)
        dec = DecodeServer(model, CFG, max_batch=2)
        router = DisaggRouter(decode=[dec], prefill=[pf],
                              max_queue_depth=2, affinity_tokens=BS)
        stop = dec.stop
    try:
        for _ in range(3):
            assert len(router.generate(PROMPT, 5)) == 5
    finally:
        stop()
    store = reqtrace.store()
    rows = store.summaries_since(0)
    assert len(rows) == 3
    for row in rows:
        pm = row["phase_ms"]
        q = pm["decode_first_token.engine_queue"]
        p = pm["decode_first_token.engine_prefill"]
        assert q >= 0.0 and p > 0.0
        # rounded to a microsecond each
        assert q + p <= pm["decode_first_token"] + 2e-3
        assert row["ts"] is not None
    for kept in store.slowest(3):
        names = [ph["phase"] for ph in kept["phases"]]
        assert set(names) <= set(reqtrace.PHASES)
        assert "decode_first_token" in names
        first = next(ph for ph in kept["phases"]
                     if ph["phase"] == "decode_first_token")
        assert set(first["parts"]) == {"engine_queue", "engine_prefill"}
        # parts are children: the flat list, and with it the
        # phase-sum invariant, does not see them
        seq_ms = sum(ph["dur_ms"] for ph in kept["phases"]
                     if not ph.get("concurrent"))
        assert seq_ms <= kept["total_ms"] + 5.0


def test_parts_are_clipped_to_their_phase_and_summed_per_attempt():
    tr = reqtrace.RequestTrace("r-parts")
    tr.add_phase("decode_first_token", 10.0,
                 parts={"engine_prefill": 7.0, "engine_queue": 5.0})
    tr.begin_attempt()
    tr.add_phase("decode_first_token", 4.0,
                 parts={"engine_prefill": 1.5, "engine_queue": None})
    tr.add_phase("decode_steady", 20.0, tokens=3)
    rec = tr.finish("ok")
    assert [p["phase"] for p in rec["phases"]] == [
        "decode_first_token", "decode_first_token", "decode_steady"]
    assert rec["phases"][0]["parts"] == {"engine_prefill": 7.0,
                                         "engine_queue": 3.0}
    assert rec["phases"][1]["parts"] == {"engine_prefill": 1.5}
    assert "parts" not in rec["phases"][2]
    assert rec["phase_ms"] == {
        "decode_first_token": 14.0, "decode_steady": 20.0,
        "decode_first_token.engine_prefill": 8.5,
        "decode_first_token.engine_queue": 3.0}


def _summary(total, dft, queue, prefill, steady):
    return {"total_ms": total, "phase_ms": {
        "decode_first_token": dft, "decode_steady": steady,
        "decode_first_token.engine_queue": queue,
        "decode_first_token.engine_prefill": prefill}}


@pytest.mark.parametrize("slow,owner", [
    # the queue grows by 200 of the phase's 210; steady by 50
    (_summary(1000.0, 250.0, 220.0, 25.0, 100.0),
     "decode_first_token.engine_queue"),
    # queue and prefill share the phase's growth; steady's 150 is more
    # than either part's, so the parent is named
    (_summary(1000.0, 250.0, 120.0, 125.0, 200.0), "decode_first_token"),
])
def test_attribution_lists_parts_under_their_parent(slow, owner):
    fast = [_summary(100.0 + i, 40.0, 20.0, 15.0, 50.0)
            for i in range(20)]
    out = reqtrace.p99_attribution(fast + [slow])
    assert set(out["phases"]) == {"decode_first_token", "decode_steady"}
    parts = out["phases"]["decode_first_token"]["parts"]
    assert set(parts) == {"engine_queue", "engine_prefill"}
    assert parts["engine_queue"]["p50_ms"] == 20.0
    assert out["tail_owner"] == owner
    assert 0.0 < out["tail_share"] <= 1.0


def test_the_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(reqtrace, "LOOP_RING_CAP", 8)
    store = reqtrace.RequestTraceStore()
    for i in range(20):
        store.record_loop({"ts": float(i)})
    assert [r["ts"] for r in store.loop_records()] == [
        float(i) for i in range(12, 20)]


def test_recorder_off_reads_no_clock_and_builds_no_record(
        model, monkeypatch):
    monkeypatch.setenv("RAY_TPU_REQTRACE", "0")
    envknobs.clear()
    reads = []
    real_now = engine_mod._now

    def counting_now():
        reads.append(1)
        return real_now()

    monkeypatch.setattr(engine_mod, "_now", counting_now)
    eng = ContinuousBatchingEngine(model, CFG, max_batch=2,
                                   kv_block_size=BS)
    try:
        stream = eng.stream(PROMPT, 6)
        assert len(list(stream)) == 6
    finally:
        eng.stop()
    assert reads == []
    assert reqtrace.store().loop_records() == []
    assert stream.queue_ms is None and stream.prefill_ms is None
    # and on again it reads: the switch is live
    monkeypatch.setenv("RAY_TPU_REQTRACE", "1")
    envknobs.clear()
    eng = ContinuousBatchingEngine(model, CFG, max_batch=2)
    try:
        eng.generate(PROMPT, 3)
    finally:
        eng.stop()
    assert len(reads) >= 10 and _ring(eng)


def test_profiler_trace_holds_the_engine_spans(engine, tmp_path):
    engine.generate(PROMPT, 3)  # compile outside the trace
    with profiling.profile(log_dir=str(tmp_path)):
        engine.generate([2] + PROMPT, 4)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    lines = [(line.name, [e for e in line.events
                          if e.name.startswith("engine.")])
             for plane in data.planes for line in plane.lines]
    lines = [(name, evs) for name, evs in lines if evs]
    assert [name for name, _evs in lines] == ["cb-engine"]
    events = lines[0][1]
    assert {e.name for e in events} == {
        "engine.admit", "engine.prefill", "engine.pool_commit",
        "engine.splice", "engine.tick_dispatch", "engine.tick_readback",
        "engine.emit"}

    def one(name):
        (ev,) = [e for e in events if e.name == name]
        return ev, ev.start_ns, ev.start_ns + ev.duration_ns

    prefill, p0, p1 = one("engine.prefill")
    assert dict(prefill.stats) == {"rid": 1, "prompt_tokens": 20}
    commit, c0, c1 = one("engine.pool_commit")
    assert dict(commit.stats) == {"rid": 1}
    assert p0 <= c0 and c1 <= p1
    _splice, s0, _s1 = one("engine.splice")
    admits = [(e.start_ns, e.start_ns + e.duration_ns) for e in events
              if e.name == "engine.admit"]
    assert any(a0 <= p0 and p1 <= s0 <= a1 for a0, a1 in admits)
    ticks = [e for e in events if e.name == "engine.tick_dispatch"]
    assert len(ticks) == 3 and all(
        dict(e.stats) == {"live": 1} for e in ticks)
