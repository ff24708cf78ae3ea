"""The Nemotron-H family (models/nemotron_h.py) on the CPU at a tiny
size: the chunked scan against the step-by-step recurrence, the expert
layer's shares against the uncut reference, state beside keys and values
in the engine's slab, and what the engine refuses of such a family."""
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.traffic import load_module  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                       latent_moe, nemotron_h_forward,
                                       nemotron_h_init)
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.ops.mamba2 import causal_conv, ssd_scan, ssd_step  # noqa: E402

F32 = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ------------------------------------------------------------ the scan

@pytest.mark.parametrize("t,chunk", [(4, 4), (9, 4), (13, 8), (3, 8),
                                     (16, 4)])
def test_chunked_scan_equals_the_recurrence(t, chunk):
    """Across chunk boundaries and a ragged tail, from a state that is
    not zero: the outputs and the state handed back."""
    b, h, p, g, n = 2, 4, 8, 2, 16
    x, dt = _rand(0, b, t, h, p), jax.nn.softplus(_rand(1, b, t, h))
    a = -jnp.exp(_rand(2, h))
    bm, cm, d = _rand(3, b, t, g, n), _rand(4, b, t, g, n), _rand(5, h)
    s0 = _rand(6, b, h, p, n)
    y, s = ssd_scan(x, dt, a, bm, cm, d, s0, chunk)
    want, state = [], s0
    for i in range(t):
        yi, state = ssd_step(x[:, i], dt[:, i], a, bm[:, i], cm[:, i], d,
                             state)
        want.append(yi)
    np.testing.assert_allclose(y, jnp.stack(want, 1), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, state, atol=2e-5, rtol=2e-5)


def test_convolution_tail_carries_over_a_split():
    x, w, bias = _rand(0, 2, 11, 6), _rand(1, 4, 6), _rand(2, 6)
    zero = jnp.zeros((2, 3, 6))
    whole, tail = causal_conv(x, zero, w, bias)
    first, mid = causal_conv(x[:, :5], zero, w, bias)
    second, last = causal_conv(x[:, 5:], mid, w, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(last, tail)
    np.testing.assert_allclose(tail, x[:, -3:])


# ------------------------------------------------------------ the share

def test_four_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """16 experts over 4 shares of 4: the routed parts the shares give,
    plus what every share computes alike (the shared expert) counted
    ONCE, add up to the uncut reference's whole layer."""
    ref = load_module("references", "nemotron_h")
    whole = dataclasses.replace(F32, experts_held=16)
    p = nemotron_h_init(whole, jax.random.PRNGKey(1))["blocks"][1]["moe"]
    p = dict(p, router_bias=0.3 * _rand(7, 16))
    x = _rand(8, 10, whole.d_model)
    # the reference norms its own input (unit weight, eps 0 here); the
    # program's layer takes the normed h
    h = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)))[None]

    def share(rank, w2_scale=1.0):
        cfg = dataclasses.replace(F32, experts_held=4, first_expert=4 * rank)
        held = dict(p, w1=p["w1"][4 * rank:4 * rank + 4],
                    w2=w2_scale * p["w2"][4 * rank:4 * rank + 4])
        return latent_moe(h, held, cfg)

    shared_alone, _ = share(0, w2_scale=0.0)
    got, pairs = shared_alone, 0
    for rank in range(4):
        out, counts = share(rank)
        got = got + (out - shared_alone)
        pairs += int(counts["pairs_held"])
    assert pairs == 10 * whole.num_experts_per_tok   # no pair dropped

    layer = {"norm": jnp.ones(whole.d_model), "gate": p["router"],
             "e_score_correction_bias": p["router_bias"],
             "fc1_latent_proj": p["w_down"], "fc2_latent_proj": p["w_up"],
             "experts_up_proj": p["w1"], "experts_down_proj": p["w2"],
             "shared_up_proj": p["s1"], "shared_down_proj": p["s2"]}
    with jax.default_matmul_precision("highest"):
        h_ref, u, per_expert = ref._route(
            x, layer, whole.num_experts_per_tok,
            whole.routed_scaling_factor, True, 0.0)
        routed = ref._experts(u, layer["experts_up_proj"],
                              layer["experts_down_proj"], per_expert)
        want = ref._moe_close(jnp.zeros_like(x), h_ref, routed, layer)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-4)


# ----------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def tiny():
    cfg = NemotronHConfig.tiny()
    return cfg, nemotron_h_init(cfg, jax.random.PRNGKey(0))


def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(1, 500, n).tolist() for n in (9, 14, 6)]


def test_slots_admitted_at_different_times_decode_what_each_does_alone(
        tiny):
    """State splice and per-slot positions: a stream that joins a running
    batch neither disturbs it nor is disturbed."""
    cfg, params = tiny
    engine = ContinuousBatchingEngine(params, cfg, max_batch=4)
    try:
        alone = [engine.generate(p, 12) for p in _prompts()]
        streams = []
        for p in _prompts():
            streams.append(engine.stream(p, 12))
            time.sleep(0.03)
        assert [list(s) for s in streams] == alone
        stats = engine.kv_stats()
    finally:
        engine.stop()
    assert engine.kv_cache is None and stats["enabled"] is False
    assert stats["stateful"] is True
    c = cfg
    per_layer = (4 * c.mamba_num_heads * c.mamba_head_dim * c.ssm_state_size
                 + 2 * (c.conv_kernel - 1) * c.conv_dim)
    assert stats["state_bytes_per_slot"] == 2 * per_layer
    assert stats["kv_bytes_per_token"] == 2 * 2 * c.num_kv_heads * c.head_dim


def test_the_loop_record_carries_state_bytes_and_the_experts_counts(tiny):
    cfg, params = tiny
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(params, cfg, max_batch=4)
    try:
        engine.generate(_prompts()[0], 6)
        stats = engine.kv_stats()
    finally:
        engine.stop()
    records = reqtrace.store().loop_records()
    reqtrace._reset_store_for_tests()
    entry = [a for r in records for a in r["admissions"]][0]
    assert entry["state_bytes"] == stats["state_bytes_per_slot"]
    assert entry["splice_ms"] > 0.0
    ticks = [r for r in records if "moe_pairs_held" in r]
    assert ticks
    pairs = 4 * cfg.num_experts_per_tok * cfg.pattern.count("E")
    for r in ticks:
        assert 0 <= r["moe_rows_max"] <= r["moe_pairs_held"] <= pairs


def test_a_family_without_state_records_neither():
    from ray_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.tiny()
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(
        llama_init(cfg, jax.random.PRNGKey(0)), cfg, max_batch=2)
    try:
        engine.generate([5, 6, 7, 8], 4)
        stats = engine.kv_stats()
    finally:
        engine.stop()
    records = reqtrace.store().loop_records()
    reqtrace._reset_store_for_tests()
    assert stats["stateful"] is False and stats["state_bytes_per_slot"] == 0
    assert not any("moe_pairs_held" in r for r in records)
    assert not any("state_bytes" in a for r in records
                   for a in r["admissions"])


@pytest.mark.parametrize("kwargs,reason", [
    ({"prefix_cache": True}, "cannot resume a recurrence"),
    ({"speculate_k": 2}, "cannot be un-advanced"),
    ({"lora_pool": object()}, "adapter pool"),
])
def test_the_engine_refuses_what_a_state_cannot_give(tiny, kwargs, reason):
    cfg, params = tiny
    with pytest.raises(ValueError, match=reason):
        ContinuousBatchingEngine(params, cfg, max_batch=2, **kwargs)


def test_adoption_and_a_cached_prefix_are_refused(tiny):
    cfg, params = tiny
    engine = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        kv = jnp.zeros((1, 4, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
        with pytest.raises(ValueError, match="ck/cv rows only"):
            engine.adopt_prefill(4, 1, kv, kv, 4)
    finally:
        engine.stop()
    with pytest.raises(ValueError, match="from position 0"):
        engine_mod._prefill_paged(params, jnp.ones((1, 4), jnp.int32), cfg,
                                  kv, kv)
    with pytest.raises(ValueError, match="cannot verify drafted"):
        _model_fns(cfg)[2](params, jnp.ones((2, 3), jnp.int32), cfg,
                           _model_fns(cfg)[1](cfg, 2),
                           jnp.zeros((2,), jnp.int32))


def test_the_splice_writes_rows_of_one_and_the_whole_of_the_other(tiny):
    cfg, params = tiny
    _fwd, init_cache, _ = _model_fns(cfg)
    slab = jax.tree.map(lambda x: x + 1, init_cache(cfg, 3))
    empty = jnp.zeros((len(slab), 0, cfg.num_kv_heads, cfg.head_dim),
                      cfg.dtype)
    prompt = jnp.asarray([_prompts()[0]], jnp.int32)
    _, ck, cv, state, _ = engine_mod._prefill_paged(params, prompt, cfg,
                                                 empty, empty)
    assert ck.shape[0] == cfg.pattern.count("*")
    assert len(state) == cfg.pattern.count("M")
    plen = prompt.shape[1]
    out = engine_mod._splice_slot(slab, ck, cv, np.int32(1), cfg, plen,
                                  state)
    kv = out[0]["k"]
    np.testing.assert_array_equal(kv[1, :plen], ck[0, :plen])
    np.testing.assert_array_equal(kv[1, plen:], 1)       # rows past: kept
    np.testing.assert_array_equal(kv[0], 1)              # other slots
    ssm = out[cfg.pattern.count("*")]["ssm"]
    np.testing.assert_array_equal(ssm[1], state[0]["ssm"][0])
    np.testing.assert_array_equal(ssm[2], 1)


def test_the_prefill_hands_back_the_last_positions_logits_only(tiny):
    cfg, params = tiny
    fwd, init_cache, _ = _model_fns(cfg)
    toks = jnp.asarray([_prompts()[1]], jnp.int32)
    logits, _ = jax.jit(lambda t, c: fwd(params, t, cfg, c, jnp.int32(0)))(
        toks, init_cache(cfg, 1))
    assert logits.shape == (1, 1, cfg.vocab_size)
    full = jax.jit(lambda t: nemotron_h_forward(params, t, cfg))(toks)
    np.testing.assert_allclose(logits[0, 0], full[0, -1], atol=2e-2)


# ------------------------------------------- a dead slot's state (PR 47)

def _ticks(engine):
    return [r for r in reqtrace.store().loop_records()
            if r["engine_id"] == engine.engine_id
            and "state_slots_stepped" in r]


@pytest.mark.parametrize("kernel", [False, True])
def test_a_slot_that_stood_dead_between_two_live_ones_is_admitted_again(
        tiny, kernel):
    """The state step visits the slots the chip holds live: slot 1's
    request ends, the slot stands dead between two that decode on, and the
    request spliced into it later decodes what it does on a fresh engine;
    its neighbours decode what they do beside a slot that never died. On
    the CPU the step is the plain one over every row; under interpret
    mode the kernel, which leaves a dead slot's state where it lies."""
    from contextlib import nullcontext

    from ray_tpu.ops import dispatch

    # a window of its own for each form: the tick's program is traced
    # once a config, under whichever form the process then had
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=120 if kernel else 124)
    left, short, right = _prompts()
    later = [7, 11, 13, 17, 19]

    def serve(dies):
        reqtrace._reset_store_for_tests()
        engine = ContinuousBatchingEngine(params, cfg, max_batch=3)
        try:
            a = engine.stream(left, 100)
            b = engine.stream(short, 4 if dies else 100)
            c = engine.stream(right, 100)
            out = {}
            if dies:
                assert len(list(b)) == 4
                # the slot stands dead for several ticks beside two live
                seen = len(_ticks(engine))
                until = time.time() + 60
                while len(_ticks(engine)) < seen + 8 and time.time() < until:
                    time.sleep(0.002)
                between = len(_ticks(engine))
                assert (a._req.slot, b._req.slot, c._req.slot) == (2, 1, 0)
                d = engine.stream(later, 8)
                out["later"] = list(d)
                assert d._req.slot == 1
            out["left"], out["right"] = list(a), list(c)
            stats = engine.kv_stats()
        finally:
            engine.stop()
        ticks = _ticks(engine)
        reqtrace._reset_store_for_tests()
        return out, ticks, stats, (seen, between) if dies else None

    with dispatch.pallas_interpret() if kernel else nullcontext():
        dispatch.reset_kernel_choices()
        died, ticks, stats, (seen, between) = serve(True)
        never, _, _, _ = serve(False)
        fresh = ContinuousBatchingEngine(params, cfg, max_batch=3)
        try:
            assert died["later"] == fresh.generate(later, 8)
        finally:
            fresh.stop()
    assert len(died["left"]) == 100 and died["left"] == never["left"]
    assert died["right"] == never["right"]
    # one layer's step visits the slots the chip held live at the launch:
    # the ones the tick decodes for, and at most those whose budget ended
    # with the tick ahead (the chip learns of an end one launch later)
    assert all(r["live"] <= r["state_slots_stepped"] <= 3 for r in ticks)
    assert ticks[0]["state_slots_stepped"] <= 2 or ticks[0]["live"] == 3
    # while the slot stood dead: the tick launched AHEAD of its last one
    # still held it live on the chip, every tick after the two live ones
    last = max(i for i in range(between) if ticks[i]["live"] == 3)
    dead = [r["state_slots_stepped"] for r in ticks[last + 1:between]]
    assert {r["live"] for r in ticks[last + 1:between]} == {2}
    assert len(dead) >= 4 and dead[0] == 3 and set(dead[1:]) == {2}
    assert 3 in {r["state_slots_stepped"] for r in ticks[between:]}
    assert stats["state_slots_stepped"] >= sum(
        r["state_slots_stepped"] for r in ticks)
    assert stats["ticks_launched"] >= len(ticks)
    c = cfg
    (choice,) = [s for s in stats["state_step"] if s["shape"] == (
        3, c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
        c.ssm_state_size)]
    assert choice["choice"] == ("pallas" if kernel else "reference")


def test_a_family_whose_step_does_not_walk_steps_every_slot():
    """Jamba's `selective_step` steps all slots' state: its ticks say so,
    and a family without state says nothing."""
    from ray_tpu.models.jamba import JambaConfig, jamba_init

    cfg = JambaConfig.tiny()
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(
        jamba_init(cfg, jax.random.PRNGKey(0)), cfg, max_batch=3)
    try:
        engine.generate([5, 6, 7, 8, 9], 4)
        stats = engine.kv_stats()
    finally:
        engine.stop()
    ticks = _ticks(engine)
    reqtrace._reset_store_for_tests()
    assert ticks and {r["state_slots_stepped"] for r in ticks} == {3}
    assert stats["state_slots_stepped"] == 3 * stats["ticks_launched"]
