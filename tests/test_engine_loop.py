"""The engine loop's own clock (models/engine.py, PR 24): one record per
iteration in the flight recorder's store, the first token's wait split
into the engine's queue and the request's own prefill, the same
boundaries as `engine.*` spans in a `jax.profiler` trace, and nothing
of it while `RAY_TPU_REQTRACE=0`. And the loop's one tick of lookahead
(PR 32): every stream is what decoding alone gives, token for token and
score for score, whatever the host learns a tick late. And the ledger of
the gaps between landings (PR 35): what each gap held, when the chip was
starved and by which step, and the prefills a request waited behind."""
from __future__ import annotations

import dataclasses
import glob
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.generate import _model_fns, generate
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.observability import requests as reqtrace
from ray_tpu.serve.disagg import DecodeServer, DisaggRouter, PrefillServer
from ray_tpu.util import envknobs, profiling

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4
PROMPT = list(range(1, 20))  # 19 tokens: four blocks of 4 and a tail of 3
# every record; one of a pass that landed a tick has the gap's too, and
# `gap_ms` where a stream felt the gap
FIELDS = {"engine_id", "pass", "ts", "live", "live_rows", "max_batch",
          "pending", "admit_ms", "admissions", "dispatch_ms",
          "readback_ms", "emit_ms", "total_ms", "inflight", "discarded",
          # tokens that left by a sink in the pass, and the sink calls
          "handed", "handovers"}
GAP_FIELDS = {"gap_streams", "gap_admissions", "gap_blocked_ms",
              "gap_empty_ms", "gap_empty_by"}
# and `slab_rows_read`, what the tick's walk read of the slab (PR 41)
LANDED = FIELDS | GAP_FIELDS | {"slab_rows_read"}
STEPS = {"bookkeeping", "lookup", "prefill", "first_token", "splice",
         "tick_dispatch", "emit"}
ADMISSION_FIELDS = {"rid", "prompt_tokens", "prefills_waited",
                    "suffix_tokens", "reused_tokens", "lookup_ms",
                    "prefill_ms", "commit_ms", "commit_dispatches",
                    "commit_blocks", "splice_ms"}


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
    engine.stop()  # the last pass is recorded after its last token
    ring = _ring(engine)
    # the admitting pass emits the prefill's token and one tick's;
    # every further pass emits one
    assert len(ring) == 5
    assert [r["pass"] for r in ring] == [0, 1, 2, 3, 4]
    for r in ring:
        assert set(r) - {"gap_ms"} == LANDED and r["max_batch"] == 4
        parts = (r["admit_ms"] + r["dispatch_ms"] + r["readback_ms"]
                 + r["emit_ms"])
        assert 0.0 < parts <= r["total_ms"]
        assert min(r["readback_ms"], r["emit_ms"]) > 0
    # `live` and `live_rows` are those of the tick the pass read back:
    # the request is in all five, one row deeper in each
    assert [r["live"] for r in ring] == [1, 1, 1, 1, 1]
    assert [r["live_rows"] for r in ring] == [19, 20, 21, 22, 23]
    # the admitting pass launches its tick and the one after it; every
    # pass launches one more while it waits, but for the last: the
    # budget says that no slot outlives the tick it reads
    assert [r["inflight"] for r in ring] == [1, 1, 1, 1, 0]
    assert [r["dispatch_ms"] > 0 for r in ring] == [True] * 4 + [False]
    assert all(r["discarded"] == 0 for r in ring)
    stats = engine.kv_stats()
    assert (stats["lookahead_ticks"], stats["lookahead_discarded"]) == (4, 0)
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
    engine.stop()  # the last pass is recorded after its last token
    ring = _ring(engine)
    admitted = {a["rid"]: i for i, r in enumerate(ring)
                for a in r["admissions"]}
    assert sorted(admitted) == [0, 1, 2]
    # admitted in pass i, a request is first in the tick that pass
    # launches: the one it reads too where it began with nothing on the
    # chip, else the one the next pass reads. With a budget of n it is
    # in n - 1 ticks (the prefill gave its first token)
    first = {rid: i if i == 0 or not ring[i - 1]["inflight"] else i + 1
             for rid, i in admitted.items()}
    for j, r in enumerate(ring):
        want = [rid for rid, i in first.items()
                if i <= j <= i + budgets[rid] - 2]
        assert r["live"] == len(want), (j, r["live"], want)
        # three prompt tokens, and a row more for each tick before
        assert r["live_rows"] == sum(3 + j - first[rid] for rid in want)
        assert len(r["admissions"]) <= engine.max_prefills_per_tick
        # a tick is queued behind the one read unless no slot is left
        # to decode for: no budget outlives the tick read, and nothing
        # was admitted since its launch
        outlives = [rid for rid in want
                    if j < first[rid] + budgets[rid] - 2]
        joins = [rid for rid, i in first.items() if i == j + 1]
        assert r["inflight"] == int(bool(outlives or joins)), j
    assert len(ring) == max(first[rid] + budgets[rid] - 1
                            for rid in first)


# rows of 2 KB of keys (4 heads of 128 float32), so the decode form's
# block is the served 128 rows and a window of 384 holds three
WALK_CFG = LlamaConfig(vocab_size=64, max_seq_len=384, num_layers=1,
                       num_heads=4, num_kv_heads=4, d_model=512, d_ff=128,
                       dtype=jnp.float32)
LONG_PROMPT = [1 + i % 60 for i in range(150)]     # into the second block


def test_a_dead_slot_is_parked_at_row_0_and_costs_one_block():
    """After a request finishes, the next launch's position vector holds
    0 at its slot, and the tick's record reads one block for it where it
    read the request's two."""
    params = llama_init(WALK_CFG, jax.random.PRNGKey(1))
    eng = ContinuousBatchingEngine(params, WALK_CFG, max_batch=2,
                                   prefix_cache=False)
    try:
        assert eng.kv_stats()["gqa_decode"] and eng._walk_block == 128
        short = eng.stream(LONG_PROMPT, 4)
        long = eng.stream([5, 6, 7], 12)
        assert len(list(short)) == 4 and len(list(long)) == 12
        slot = short._req.slot
        eng.stop()
        ring = [r for r in _ring(eng) if "slab_rows_read" in r]
        # two blocks for the long prompt's slot and one beside it, be
        # that slot empty yet or live
        both = [r for r in ring if r["live"] == 2]
        assert both and {r["slab_rows_read"] for r in both} == {3 * 128}
        assert ring[0]["slab_rows_read"] == 3 * 128
        # the short request gone: one block a slot, dead or live
        assert ring[-1]["live"] == 1 and ring[-1]["slab_rows_read"] == 2 * 128
        assert {r["slab_rows_read"] for r in ring} == {3 * 128, 2 * 128}
        assert eng._pos[slot] == 0
        assert int(np.asarray(eng._dev[1])[slot]) == 0
        assert int(np.asarray(eng._dev[2])[slot]) == 0
    finally:
        eng.stop()


def test_a_latent_familys_tick_walks_its_rows_and_counts_them(monkeypatch):
    """DeepSeek-V2's one latent row a token, with blocks of 128 rows in
    an entry of 384: the tick takes `mla_decode`'s kernel (interpreted),
    its tokens are the plain form's, and the ring counts the walk's rows
    by the function the kernel walks by: two blocks for the slot whose
    prompt reaches into the second, one for a slot beside it, be it empty
    yet, live or parked."""
    from ray_tpu.models import deepseek_v2 as ds
    from ray_tpu.ops import dispatch, swa

    monkeypatch.setattr(swa, "_DECODE_BLOCK_BYTES", 1)
    cfg = dataclasses.replace(ds.DeepseekV2Config.tiny(), max_seq_len=384,
                              dtype=jnp.float32)
    params = ds.deepseek_v2_init(cfg, jax.random.PRNGKey(2))
    with dispatch.pallas_interpret():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
        try:
            assert eng._walk_block == 128
            short = eng.stream(LONG_PROMPT, 4)
            long = eng.stream([5, 6, 7], 12)
            got = [[int(t) for t in s] for s in (short, long)]
            slot = short._req.slot
            eng.stop()
            took = [c for c in eng.kv_stats()["mla_decode"]
                    if tuple(c["shape"])[:2] + tuple(c["shape"])[-1:]
                    == (2, 1, 384)]
            assert eng._pos[slot] == 0
        finally:
            eng.stop()
    assert took and all(c["choice"] == "pallas" and c["block"] == 128
                        for c in took)
    for prompt, out in zip((LONG_PROMPT, [5, 6, 7]), got):
        want = generate(params, cfg, jnp.asarray(prompt)[None],
                        max_new_tokens=len(out))[0]
        assert out == [int(t) for t in want]
    ring = [r for r in _ring(eng) if "slab_rows_read" in r]
    both = [r for r in ring if r["live"] == 2]
    read = lambda *at: swa.decode_rows_read(np.asarray(at), 128, 384)
    assert both and {r["slab_rows_read"] for r in both} \
        == {read(len(LONG_PROMPT), 3)} == {3 * 128}
    assert ring[-1]["live"] == 1 \
        and ring[-1]["slab_rows_read"] == read(0, 14) == 2 * 128
    assert {r["slab_rows_read"] for r in ring} == {3 * 128, 2 * 128}


def test_a_request_spliced_into_a_parked_slot_decodes_what_it_does_fresh():
    """The parked slot's scatter lands in row 0, which the next splice
    overwrites: the request after decodes its own tokens."""
    params = llama_init(WALK_CFG, jax.random.PRNGKey(1))

    def serve(prompts):
        eng = ContinuousBatchingEngine(params, WALK_CFG, max_batch=1,
                                       prefix_cache=False)
        try:
            return [eng.generate(p, 8) for p in prompts]
        finally:
            eng.stop()

    later = [9, 8, 7, 6, 5]
    assert serve([LONG_PROMPT, later])[1] == serve([later])[0]


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
        stats = eng.kv_stats()
    finally:
        eng.stop()
    ring = _ring(eng)
    assert eng.spec_verify_ticks >= 1 and ring
    for r in ring:
        assert set(r) - {"gap_ms"} == LANDED
        assert min(r["dispatch_ms"], r["readback_ms"], r["emit_ms"]) > 0
        assert (r["admit_ms"] + r["dispatch_ms"] + r["readback_ms"]
                + r["emit_ms"]) <= r["total_ms"]
        # a pass with drafts is launched from the host's tokens and read
        # at once: nothing is queued behind it, nothing thrown away
        assert (r["live"], r["inflight"], r["discarded"]) == (1, 0, 0)
    assert len(ring) == eng.spec_verify_ticks
    assert stats["lookahead_ticks"] == 0


def test_a_pass_without_drafts_looks_ahead_and_one_with_drains(model):
    """A proposer that drafts in some passes only: the draftless ones
    keep a tick queued, the drafting ones read it first, and the stream
    is generate()'s either way."""
    prompt = [4, 5, 6, 7]
    eng = ContinuousBatchingEngine(
        model, CFG, max_batch=2, speculate_k=2,
        draft_source=lambda ctx, k: [ctx[-1]] * k
        if len(ctx) % 5 == 0 else [])
    try:
        got = eng.generate(prompt, 24)
        stats = eng.kv_stats()
    finally:
        eng.stop()
    want = np.asarray(generate(model, CFG, jnp.asarray([prompt], jnp.int32),
                               max_new_tokens=24))[0].tolist()
    assert got == want
    ring = _ring(eng)
    assert eng.spec_verify_ticks >= 2
    assert stats["lookahead_ticks"] >= 2
    # a verify tick is never queued behind, a lookahead always is
    assert sum(r["inflight"] for r in ring) == stats["lookahead_ticks"]
    assert sum(not r["inflight"] for r in ring) >= eng.spec_verify_ticks
    assert all(r["discarded"] == 0 for r in ring)


# ------------------------------------------------- one tick of lookahead

def _stirred(params):
    """Norm weights are ones and the layers a whisper at init, and the
    greedy stream then one token over and over: make every leaf count."""
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 200))
    return jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        next(keys), x.shape, x.dtype), params)


def _family(name):
    """A tiny model of each family the engine serves, in float32."""
    if name == "llama":
        return CFG, llama_init(CFG, jax.random.PRNGKey(0))
    if name == "gpt2":
        from ray_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
        return cfg, _stirred(gpt2_init(cfg, jax.random.PRNGKey(3)))
    if name == "nemotron_h":
        from ray_tpu.models.nemotron_h import (NemotronHConfig,
                                               nemotron_h_init)

        cfg = dataclasses.replace(NemotronHConfig.tiny(),
                                  dtype=jnp.float32)
        return cfg, nemotron_h_init(cfg, jax.random.PRNGKey(0))
    from ray_tpu.models import kimi_linear as kl

    cfg = dataclasses.replace(kl.KimiLinearConfig.tiny(),
                              dtype=jnp.float32)
    return cfg, _stirred(kl.kimi_linear_init(cfg, jax.random.PRNGKey(3)))


def _alone(params, cfg, prompt, n):
    """generate()'s algorithm for one sequence, with the scores beside
    the tokens: the family's `forward_cached` on a cache of one, a token
    at a time. Not the engine's ragged decode."""
    fwd, init_cache, _ = _model_fns(cfg)
    step = jax.jit(fwd, static_argnums=(2,))
    logits, cache = jax.jit(
        lambda prm, run, cache: fwd(prm, run, cfg, cache, 0))(
        params, jnp.asarray([prompt], jnp.int32), init_cache(cfg, 1))
    toks, scores = [], []
    for i in range(n):
        lp = jax.nn.log_softmax(
            logits[0, -1, :cfg.vocab_size].astype(jnp.float32))
        toks.append(int(jnp.argmax(lp)))
        scores.append(float(lp[toks[-1]]))
        logits, cache = step(params, jnp.asarray([[toks[-1]]], jnp.int32),
                             cfg, cache, jnp.int32(len(prompt) + i))
    return toks, scores


@pytest.mark.parametrize("family", ["gpt2", "llama", "nemotron_h",
                                    "kimi_linear"])
def test_streams_under_the_lookahead_are_what_each_decodes_alone(family):
    """Two slots and seven requests, so that every finish frees a slot
    that the very next pass admits into: budget finishes, an EOS the
    host learns of after the next tick was launched (its row is
    discarded), a cancel in mid-flight (likewise), and a request whose
    last token lands on the window's last row. A family whose slots own
    state gets it overwritten whole by the splice, or these would
    differ."""
    cfg, params = _family(family)
    rng = np.random.default_rng(11)
    plen, window = 7, cfg.max_seq_len
    prompts = [rng.integers(1, 500, plen).tolist() for _ in range(7)]
    budgets = [100, 9, 5, 40, 6, 8, window - plen]
    CANCEL, EOS, LAST = 0, 3, 6
    want = [_alone(params, cfg, p, n) for p, n in zip(prompts, budgets)]
    one = np.asarray(generate(params, cfg, jnp.asarray([prompts[1]],
                                                       jnp.int32),
                              max_new_tokens=budgets[1]))[0].tolist()
    assert want[1][0] == one
    # an EOS in mid-budget: a token the stream has not shown before
    toks = want[EOS][0]
    at = next(j for j in range(1, budgets[EOS] - 2)
              if toks[j] not in toks[:j])
    eos = [None] * 7
    eos[EOS] = toks[at]
    want[EOS] = (toks[:at + 1], want[EOS][1][:at + 1])
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    streams = []
    emit = eng._emit

    def emit_then_cancel(req, tok, score=0.0):
        # the cancel falls in the walk over a tick's tokens, the next
        # tick already launched: on the loop's thread, so that where it
        # lands does not hang on how the threads are scheduled
        emit(req, tok, score)
        if req is streams[CANCEL]._req and req.produced == 4:
            assert eng.cancel_slot(req) is True

    eng._emit = emit_then_cancel
    try:
        streams.append(eng.stream(prompts[0], budgets[0]))
        streams += [eng.stream(p, n, eos_token=e) for p, n, e
                    in zip(prompts[1:], budgets[1:], eos[1:])]
        got = [[int(t) for t in s] for s in streams]
        stats = eng.kv_stats()
    finally:
        eng.stop()
    want[CANCEL] = (want[CANCEL][0][:4], want[CANCEL][1][:4])
    for i, (stream, (toks, scores)) in enumerate(zip(streams, want)):
        assert got[i] == toks, i
        # the discarded token left no score either
        np.testing.assert_allclose(stream.scores, scores, atol=2e-4,
                                   rtol=0, err_msg=str(i))
    assert len(got[LAST]) == window - plen
    ring = _ring(eng)
    # exactly one row thrown away for the EOS and one for the cancel;
    # a budget's end is known before the launch and costs none
    assert stats["lookahead_discarded"] == 2 == sum(
        r["discarded"] for r in ring)
    assert stats["cancelled"] == 1 and stats["admitted"] == 7
    assert stats["lookahead_ticks"] == sum(r["inflight"] for r in ring)
    # a pass keeps a tick queued unless every slot's budget ends with
    # the tick it reads: the last pass, and at most the few in which
    # both slots ended together
    assert ring[-1]["inflight"] == 0
    assert stats["lookahead_ticks"] >= len(ring) - 4
    assert all(0 <= r["live_rows"] <= 2 * (window - 1) for r in ring)


def test_a_swap_with_a_tick_in_flight_holds_from_the_next_launch():
    """The tick in flight finishes on the weights it was launched with
    and the next is launched on the new: no token is dropped or emitted
    twice over the swap, and a request after it is a fresh engine's."""
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init

    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    old = _stirred(gpt2_init(cfg, jax.random.PRNGKey(0)))
    new = jax.tree.map(lambda x: x * 1.25, old)
    prompt, n = [1, 2, 3], 90
    eng = ContinuousBatchingEngine(old, cfg, max_batch=2, params_version=1)
    fresh = ContinuousBatchingEngine(new, cfg, max_batch=2)
    try:
        stream = eng.stream(prompt, n)
        head = [next(stream) for _ in range(5)]
        assert eng.update_params(new, version=2).wait(timeout=30.0)
        got = head + [int(t) for t in stream]
        assert len(got) == n and len(stream.scores) == n
        assert eng.swap_count == 1 and eng.params_version == 2
        # up to the first token the new weights chose, the stream is the
        # old weights'
        before = _alone(old, cfg, prompt, n)[0]
        cut = next((j for j in range(n) if got[j] != before[j]), n)
        assert 5 <= cut
        for p in ([5, 6], [9, 9, 9, 9]):
            assert eng.generate(p, 8) == fresh.generate(p, 8)
    finally:
        eng.stop()
        fresh.stop()
    # the swap emptied nothing: every pass of the long request but its
    # last kept a tick queued, one pass a token
    ring = _ring(eng)[:n - 1]
    assert [r["inflight"] for r in ring] == [1] * (n - 2) + [0]
    assert all(r["discarded"] == 0 for r in ring)


def test_a_long_prompt_is_not_prefilled_behind_the_tick_in_flight(model):
    """A prompt longer than half the window is admitted with nothing on
    the chip: the tick in flight is read and its tokens go out first,
    and the pass ends with the next tick launched. A short prompt, and
    the admission that follows another, queue behind the tick in flight.
    Every stream is what it decodes alone."""
    window = CFG.max_seq_len
    rng = np.random.default_rng(5)
    long1 = rng.integers(1, 500, window // 2 + 6).tolist()
    long2 = rng.integers(1, 500, window // 2 + 9).tolist()
    short = rng.integers(1, 500, window // 2).tolist()
    first = [9, 8, 7]
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4)
    later = {}
    emit = eng._emit

    def emit_then_submit(req, tok, score=0.0):
        # on the loop's thread, in the walk over a tick's tokens with
        # the next tick launched: where the submissions land does not
        # hang on how the threads are scheduled
        emit(req, tok, score)
        if req is head._req and req.produced == 4:
            later["long1"] = eng.stream(long1, 5)
            later["long2"] = eng.stream(long2, 5)
        if req is head._req and req.produced == 40:
            later["short"] = eng.stream(short, 5)

    eng._emit = emit_then_submit
    try:
        head = eng.stream(first, 90)
        got = {"first": [int(t) for t in head]}
        got.update({k: [int(t) for t in v] for k, v in later.items()})
    finally:
        eng.stop()
    for name, prompt, n in (("first", first, 90), ("long1", long1, 5),
                            ("long2", long2, 5), ("short", short, 5)):
        assert got[name] == _alone(model, CFG, prompt, n)[0], name
    ring = _ring(eng)
    at = {a["prompt_tokens"]: i for i, r in enumerate(ring)
          for a in r["admissions"] if a["rid"] > 0}
    held, chained, queued = (ring[at[len(p)]] for p in (long1, long2, short))
    # the long prompt's pass read the tick in flight, with the first
    # request alone in it, and left nothing queued behind it ...
    assert (held["inflight"], held["live"]) == (0, 1)
    assert held["readback_ms"] > 0 and held["admit_ms"] > 0
    assert ring[at[len(long1)] - 1]["inflight"] == 1
    # ... the one behind it and the short one kept a tick queued
    assert at[len(long2)] == at[len(long1)] + 1
    assert chained["inflight"] == 1 and queued["inflight"] == 1
    for r in ring:
        assert r["dispatch_ms"] + r["emit_ms"] <= (
            r["total_ms"] - r["readback_ms"] - r["admit_ms"]) + 1e-6


# ------------------------------------------------------ the gap ledger

def _scripted(eng, head_prompt, head_budget, script):
    """A head request, and on the loop's own thread (in the walk over a
    tick's tokens, so that where they land does not hang on how the
    threads are scheduled) the submissions `script` names: {tokens the
    head has produced: [(prompt, budget, eos), ...]}. Returns the
    streams in the order they were handed in, all read to their end."""
    streams = []
    emit = eng._emit

    def emit_then_submit(req, tok, score=0.0):
        emit(req, tok, score)
        if req is streams[0]._req:
            for prompt, budget, eos in script.get(req.produced, ()):
                streams.append(eng.stream(prompt, budget, eos_token=eos))

    eng._emit = emit_then_submit
    streams.append(eng.stream(head_prompt, head_budget))
    tokens = [[int(t) for t in streams[0]]]
    tokens += [[int(t) for t in s] for s in streams[1:]]
    return streams, tokens


def _gaps_fit(ring):
    """What holds of every record of a pass that landed a tick."""
    for r in ring:
        if "gap_streams" not in r:
            assert not GAP_FIELDS & set(r) and "gap_ms" not in r
            continue
        assert set(r) - {"gap_ms"} == LANDED
        assert set(r["gap_empty_by"]) <= STEPS
        assert r["gap_empty_ms"] == pytest.approx(
            sum(r["gap_empty_by"].values()))
        assert min([r["gap_blocked_ms"], r["gap_empty_ms"]]
                   + list(r["gap_empty_by"].values())) >= 0.0
        assert ("gap_ms" in r) == (r["gap_streams"] >= 1)
        if "gap_ms" in r:
            assert r["gap_blocked_ms"] + r["gap_empty_ms"] \
                <= r["gap_ms"] + 1e-6
            assert r["gap_streams"] <= r["live"]


def test_consecutive_gaps_tile_the_time_between_landings(engine):
    lands = []
    land = engine._gaps.land

    def noting(t1, streams, it):
        lands.append(t1)
        land(t1, streams, it)

    engine._gaps.land = noting
    assert len(engine.generate(PROMPT, 9)) == 9
    engine.stop()
    ring = _ring(engine)
    assert len(ring) == len(lands) == 8
    # the first landing ends no gap: nothing took a token before it
    assert "gap_ms" not in ring[0] and ring[0]["gap_streams"] == 0
    gaps = [r["gap_ms"] for r in ring[1:]]
    assert gaps == pytest.approx(
        [(b - a) * 1e3 for a, b in zip(lands, lands[1:])])
    assert sum(gaps) == pytest.approx((lands[-1] - lands[0]) * 1e3)
    assert [r["gap_streams"] for r in ring] == [0] + [1] * 7
    _gaps_fit(ring)


def test_a_steady_pass_leaves_the_chip_nothing_to_wait_for(engine):
    """With a tick queued behind the one read, the chip is never
    starved; the admitting pass's chain is, by the steps that ran."""
    engine.generate(PROMPT, 9)
    engine.stop()
    ring = _ring(engine)
    assert [r["inflight"] for r in ring] == [1] * 7 + [0]
    for r in ring[1:]:
        assert (r["gap_empty_ms"], r["gap_empty_by"]) == (0.0, {})
        assert 0.0 < r["gap_blocked_ms"] <= r["gap_ms"]
        assert r["gap_admissions"] == 0
    first = ring[0]
    assert first["gap_admissions"] == 1
    # (`bookkeeping`: the telemetry push between `_admit` and `_launch`)
    assert {"first_token", "splice", "tick_dispatch"} \
        <= set(first["gap_empty_by"]) \
        <= {"first_token", "splice", "tick_dispatch", "bookkeeping"}
    assert first["gap_blocked_ms"] > 0.0
    # an idle engine's chip is not a starved one: a second request
    # after a pause finds the ledger as the first did
    engine_gaps = engine.kv_stats()["gaps"]
    assert engine_gaps["stream_gaps"] == 7
    assert engine_gaps["with_admission"] == 0
    assert engine_gaps["chip_empty_ms"] == {}
    assert engine_gaps["chip_blocked_ms"] == pytest.approx(
        sum(r["gap_blocked_ms"] for r in ring[1:]))


@pytest.mark.parametrize("hold", [False, True])
def test_an_admission_is_counted_in_the_gap_its_launch_fell_in(model, hold):
    """In the gap that ENDS with the first landing after the admission's
    programs were launched, and in no other: the admitting pass's own
    landing where the prompt is queued behind the tick in flight, the
    next pass's after a hold (`_long_prompt_waits`: the held pass reads
    its tick BEFORE it admits)."""
    window = CFG.max_seq_len
    rng = np.random.default_rng(7)
    late = rng.integers(1, 500, window // 2 + 5 if hold else 9).tolist()
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4)
    try:
        streams, tokens = _scripted(eng, [9, 8, 7], 30,
                                    {6: [(late, 4, None)]})
    finally:
        eng.stop()
    assert [len(t) for t in tokens] == [30, 4]
    assert tokens[1] == _alone(model, CFG, late, 4)[0]
    ring = _ring(eng)
    _gaps_fit(ring)
    (at,) = [i for i, r in enumerate(ring)
             if [a["rid"] for a in r["admissions"]] == [1]]
    counted = at + 1 if hold else at
    assert ring[at]["inflight"] == (0 if hold else 1)
    for i, r in enumerate(ring):
        assert r["gap_admissions"] == (i in (0, counted)), i
    # the head felt that gap, and it held the prefill: the thread was
    # blocked on its logits, and the chip starved over the chain after
    gap = ring[counted]
    assert gap["gap_streams"] == 1
    assert gap["gap_ms"] >= ring[at]["admissions"][0]["prefill_ms"]
    assert gap["gap_blocked_ms"] > 0.0
    assert {"first_token", "splice", "tick_dispatch"} \
        <= set(gap["gap_empty_by"])
    if hold:
        # the held pass read its tick with nothing behind it, and the
        # host walked, looked up and launched the prefill meanwhile
        assert {"emit", "lookup", "prefill"} <= set(gap["gap_empty_by"])
        assert ring[at]["gap_empty_ms"] == 0.0
    stats = eng.kv_stats()["gaps"]
    assert stats["with_admission"] == 1
    assert stats["stream_gaps"] == sum(
        r["gap_streams"] for r in ring if "gap_ms" in r)


def test_gap_streams_leaves_out_a_joined_a_finished_and_a_discarded_row(
        model):
    """A stream feels a gap if it took a token from the tick before AND
    from this one: not the slot that joined, not the one whose budget
    ended with the tick before, not the row thrown away after an EOS
    (which `live` still counts: the host knew no better at the launch)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 500, 7).tolist() for _ in range(3)]
    budgets = [40, 5, 12]
    toks = _alone(model, CFG, prompts[2], budgets[2])[0]
    at = next(j for j in range(1, budgets[2] - 2)
              if toks[j] not in toks[:j])
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4)
    try:
        streams, tokens = _scripted(eng, prompts[0], budgets[0], {
            4: [(prompts[1], budgets[1], None)],
            12: [(prompts[2], budgets[2], toks[at])]})
    finally:
        eng.stop()
    assert tokens[2] == toks[:at + 1]
    ring = _ring(eng)
    _gaps_fit(ring)
    admitted = {a["rid"]: i for i, r in enumerate(ring)
                for a in r["admissions"]}
    # admitted in pass i, a request is first in the tick pass i reads
    # where that pass began with nothing on the chip, else in the next
    first = {rid: i if i == 0 or not ring[i - 1]["inflight"] else i + 1
             for rid, i in admitted.items()}
    # the ticks it took a token from: one less than it emitted
    last = {rid: first[rid] + len(tokens[rid]) - 2 for rid in first}
    for j, r in enumerate(ring):
        felt = [rid for rid in first if first[rid] < j <= last[rid]]
        assert r["gap_streams"] == len(felt), (j, felt)
    joined, ended, thrown = first[1], last[1] + 1, last[2] + 1
    assert ring[joined]["live"] == 2 and ring[joined]["gap_streams"] == 1
    assert ring[ended]["gap_streams"] == 1
    assert ring[ended - 1]["gap_streams"] == 2
    assert ring[thrown]["discarded"] == 1 and ring[thrown]["live"] == 2
    assert ring[thrown]["gap_streams"] == 1


@pytest.mark.parametrize("kind", ["plain", "speculative",
                                  "drafts_at_times", "two_a_pass"])
def test_the_parts_of_a_gap_fit_it(model, kind):
    """Blocked and starved time are stretches of the gap that do not
    overlap, and a record holds ONE landing, whatever the loop's shape:
    the lookahead, a verify tick read with nothing behind it, a
    speculating pass without drafts (it reads the lookahead, then
    launches and reads a plain tick), two admissions in one pass."""
    calls = itertools.count()
    kw = {"speculative": dict(speculate_k=2,
                              draft_source=lambda ctx, k: [ctx[-1]] * k),
          "drafts_at_times": dict(
              speculate_k=2, draft_source=lambda ctx, k:
              [ctx[-1]] * k if next(calls) % 3 == 0 else []),
          "two_a_pass": dict(max_prefills_per_tick=2)}.get(kind, {})
    rng = np.random.default_rng(13)
    others = [(rng.integers(1, 500, n).tolist(), 6, None) for n in (5, 11)]
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4, **kw)
    lands = []
    land = eng._gaps.land

    def noting(t1, streams, it):
        lands.append(t1)
        land(t1, streams, it)

    eng._gaps.land = noting
    try:
        streams, tokens = _scripted(eng, [4, 5, 6], 24, {5: others})
        stats = eng.kv_stats()["gaps"]
    finally:
        eng.stop()
    assert [len(t) for t in tokens] == [24, 6, 6]
    ring = _ring(eng)
    _gaps_fit(ring)
    assert [r["pass"] for r in ring] == list(range(len(ring)))
    # every landing has a record of its own, and the gaps tile the time
    # between the landings
    landed = [r for r in ring if "gap_streams" in r]
    assert len(landed) == len(lands)
    for i, r in enumerate(landed):
        if "gap_ms" in r:
            assert r["gap_ms"] == pytest.approx(
                (lands[i] - lands[i - 1]) * 1e3)
    if kind == "drafts_at_times":
        # both shapes of a speculating pass were taken
        assert {r["inflight"] for r in landed} == {0, 1}
        assert eng.spec_proposed > 0
    felt = [r for r in ring if "gap_ms" in r]
    assert len(felt) >= 8
    if kind == "speculative":
        # nothing is queued behind a verify tick: every gap has the
        # walk, the drafting and the next dispatch with the chip empty
        assert all({"emit", "bookkeeping", "tick_dispatch"}
                   <= set(r["gap_empty_by"]) for r in felt)
    if kind == "two_a_pass":
        (both,) = [r for r in ring if len(r["admissions"]) == 2]
        assert both["gap_admissions"] == 2
        # the second's lookup and launch ran behind the first's logits
        assert {"lookup", "prefill"} <= set(both["gap_empty_by"])
    # the operator's totals are the ring's sums
    assert stats["stream_gaps"] == sum(r["gap_streams"] for r in felt)
    assert stats["with_admission"] == sum(
        r["gap_streams"] for r in felt if r["gap_admissions"])
    assert stats["chip_blocked_ms"] == pytest.approx(
        sum(r["gap_blocked_ms"] for r in felt))
    for step in STEPS:
        assert stats["chip_empty_ms"].get(step, 0.0) == pytest.approx(
            sum(r["gap_empty_by"].get(step, 0.0) for r in felt))


def test_prefills_waited_counts_the_prefills_a_request_stood_behind(engine):
    lone = engine.stream(PROMPT, 3)
    assert lone.prefills_waited is None or lone.prefills_waited == 0
    assert len(list(lone)) == 3 and lone.prefills_waited == 0
    # two handed in together: the second waits for the first's prefill
    streams, tokens = _scripted(engine, [9, 8, 7], 20, {
        5: [([1, 2, 3, 4], 3, None), ([5, 6, 7, 8, 9], 3, None)]})
    engine.stop()
    assert [s.prefills_waited for s in streams] == [0, 0, 1]
    waited = {a["rid"]: a["prefills_waited"] for r in _ring(engine)
              for a in r["admissions"]}
    assert waited == {0: 0, 1: 0, 2: 0, 3: 1}


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
        # the count of prefills waited behind rides the phase: one
        # request at a time, so none
        assert first["prefills_waited"] == 0
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
    # the ledger stays at zero; the prefills waited behind are a count,
    # not a reading, and are kept
    assert eng.kv_stats()["gaps"] == {
        "stream_gaps": 0, "with_admission": 0, "chip_blocked_ms": 0.0,
        "chip_empty_ms": {}}
    assert stream.prefills_waited == 0
    # and on again it reads: the switch is live
    monkeypatch.setenv("RAY_TPU_REQTRACE", "1")
    envknobs.clear()
    eng = ContinuousBatchingEngine(model, CFG, max_batch=2)
    try:
        eng.generate(PROMPT, 3)
    finally:
        eng.stop()
    assert len(reads) >= 10 and _ring(eng)


def _traced_events(engine, tmp_path):
    """The `engine.*` events of a second request, traced on the CPU."""
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
    return lines[0][1]


def test_profiler_trace_holds_the_engine_spans(engine, tmp_path):
    events = _traced_events(engine, tmp_path)
    assert {e.name for e in events} == {
        "engine.admit", "engine.prefill", "engine.pool_commit",
        "engine.splice", "engine.tick_dispatch", "engine.tick_readback",
        "engine.emit"}

    def one(name):
        (ev,) = [e for e in events if e.name == name]
        return ev, ev.start_ns, ev.start_ns + ev.duration_ns

    prefill, p0, p1 = one("engine.prefill")
    admitted = dict(prefill.stats).pop("pass")
    assert dict(prefill.stats) == {"rid": 1, "prompt_tokens": 20,
                                   "pass": admitted}
    commit, c0, c1 = one("engine.pool_commit")
    assert dict(commit.stats) == {"rid": 1, "pass": admitted}
    assert dict(one("engine.splice")[0].stats) == {"rid": 1,
                                                   "pass": admitted}
    assert p0 <= c0 and c1 <= p1
    _splice, s0, _s1 = one("engine.splice")
    admits = [(e.start_ns, e.start_ns + e.duration_ns) for e in events
              if e.name == "engine.admit"]
    assert any(a0 <= p0 and p1 <= s0 <= a1 for a0, a1 in admits)
    ticks = [e for e in events if e.name == "engine.tick_dispatch"]
    assert len(ticks) == 3 and all(
        set(dict(e.stats)) == {"live", "pass"}
        and dict(e.stats)["live"] == 1 for e in ticks)


def test_a_span_carries_the_pass_of_its_ring_record(engine, tmp_path):
    """From a span in the profiler to the ring: `pass` on every
    `engine.*` event is the `pass` of the record its pass left."""
    events = _traced_events(engine, tmp_path)
    engine.stop()
    ring = {r["pass"]: r for r in _ring(engine)}
    assert sorted(ring) == list(range(len(ring)))
    by_pass = {}
    for e in events:
        by_pass.setdefault(dict(e.stats)["pass"], []).append(e.name)
    # the admitting pass of the traced request: its record holds the
    # admission, its spans the whole chain and two launches
    (admitting,) = [n for n, r in ring.items()
                    if [a["rid"] for a in r["admissions"]] == [1]]
    assert sorted(by_pass[admitting]) == sorted([
        "engine.admit", "engine.prefill", "engine.pool_commit",
        "engine.splice", "engine.tick_dispatch", "engine.tick_dispatch",
        "engine.tick_readback", "engine.emit"])
    # every pass that read a tick back has the two spans of `_land`,
    # and one dispatch where its record says a tick was queued behind
    for n, names in by_pass.items():
        if "engine.tick_readback" not in names:
            continue  # an idle pass: its spans carry the next number
        r = ring[n]
        assert names.count("engine.emit") == 1
        if n != admitting:
            assert names.count("engine.tick_dispatch") == r["inflight"]
        assert (r["readback_ms"] > 0) and "gap_streams" in r
