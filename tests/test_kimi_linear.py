"""Kimi-Linear on the CPU at a tiny size: the program against the plain
reference, the chunked delta rule against the recurrence a token at a
time, absorbed against expanded latent attention, the engine's slab (a
latent row and a matrix-valued state a slot) against one full forward
pass, the four shares of the expert layer against the uncut layer, the
engine's refusals for state, and the older families' programs lowering
as they did before a sequence entry could hold one array."""
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import reference  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models import kimi_linear as kl  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.models.gpt2 import GPT2Config, gpt2_init  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                       nemotron_h_init)
from ray_tpu.ops import kda, mla  # noqa: E402

TOL = 2e-4      # float32 on both sides, different summation orders
CFG = dataclasses.replace(kl.KimiLinearConfig.tiny(), dtype=jnp.float32)
# `tiny()` under the published keys, for the reference
CONF = {"family": "kimi_linear", "hidden_size": 64, "num_hidden_layers": 4,
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
        "linear_attn_config": {"kda_layers": [1, 2, 4],
                               "full_attn_layers": [3], "num_heads": 4,
                               "head_dim": 16, "short_conv_kernel_size": 4},
        "num_attention_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "num_experts": 4, "expert_parallel_size": 4,
        "num_experts_per_token": 3, "routed_scaling_factor": 2.446,
        "moe_renormalize": True, "vocab_size": 512}
TOKENS = np.random.default_rng(0).integers(1, 500, 72).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    p = kl.kimi_linear_init(CFG, jax.random.PRNGKey(3))
    # norm weights are ones and the layers a whisper at init: make every
    # leaf count
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 200))
    return jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        next(keys), x.shape, x.dtype), p)


# one compiled program a length (a pass op by op costs ten times that)
_forward = jax.jit(lambda p, seq: kl.kimi_linear_forward(p, seq, CFG))


def test_forward_agrees_with_the_reference(params):
    got = _forward(params, TOKENS[None, :40])[0]
    want = reference.logits(CONF, params, TOKENS[:40])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_another_share_is_another_function(params):
    got = _forward(params, TOKENS[None, :24])[0]
    other = reference.logits({**CONF, "expert_parallel_rank": 1}, params,
                             TOKENS[:24])
    assert float(jnp.max(jnp.abs(got - other))) > TOL


# ------------------------------------------------------- the delta rule

def _recurrence(q, k, v, g, beta, state):
    """The published recurrence, a token and a head at a time, float64."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    s = np.array(state, np.float64)
    b, t, h, dk = k.shape
    out = np.zeros(v.shape)
    for i in range(t):
        for j in range(b):
            for n in range(h):
                kk, bt = k[j, i, n], beta[j, i, n]
                s[j, n] = (np.eye(dk) - bt * np.outer(kk, kk)) \
                    @ (np.exp(g[j, i, n])[:, None] * s[j, n]) \
                    + bt * np.outer(kk, v[j, i, n])
                out[j, i, n] = s[j, n].T @ q[j, i, n]
    return out, s


def _kda_inputs(t, seed, b=2, h=3, dk=8, dv=6):
    rng = np.random.default_rng(seed)
    q = kda.l2_normalize(rng.normal(size=(b, t, h, dk))) * dk ** -0.5
    k = kda.l2_normalize(rng.normal(size=(b, t, h, dk)))
    v = rng.normal(size=(b, t, h, dv)).astype(np.float32)
    # decays from none to e^-4 a step: 16 steps of the strongest cross
    # e^-64 inside one block of the factoring
    g = -np.abs(rng.normal(size=(b, t, h, dk))).astype(np.float32) * 2.0
    beta = rng.uniform(size=(b, t, h)).astype(np.float32)
    carried = rng.normal(size=(b, h, dk, dv)).astype(np.float32)
    return q, k, v, g, beta, carried


@pytest.mark.parametrize("carried", [False, True],
                         ids=["from-zero", "from-a-carried-state"])
@pytest.mark.parametrize("t,chunk", [(16, 8), (19, 8), (5, 8), (70, 32)],
                         ids=["whole-chunks", "ragged", "under-a-chunk",
                              "two-blocks-a-chunk"])
def test_the_chunked_form_is_the_recurrence(t, chunk, carried):
    q, k, v, g, beta, state = _kda_inputs(t, seed=t)
    if not carried:
        state = np.zeros_like(state)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = kda.kda_scan(q, k, v, g, beta, jnp.asarray(state), chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)


def test_the_one_step_form_is_the_recurrence():
    q, k, v, g, beta, state = _kda_inputs(12, seed=5)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    s, outs = jnp.asarray(state), []
    for i in range(12):
        o, s = kda.kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    np.testing.assert_allclose(np.stack(outs, 1), want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)


def test_a_padded_chunk_neither_decays_nor_feeds_the_state():
    """19 tokens in chunks of 8 end in a chunk of 3 real tokens and 5 of
    padding: the state handed back is the one after token 19."""
    q, k, v, g, beta, state = _kda_inputs(19, seed=7)
    _, whole = kda.kda_scan(q, k, v, g, beta, jnp.asarray(state), 8)
    _, first = kda.kda_scan(q[:, :16], k[:, :16], v[:, :16], g[:, :16],
                            beta[:, :16], jnp.asarray(state), 8)
    _, rest = kda.kda_scan(q[:, 16:], k[:, 16:], v[:, 16:], g[:, 16:],
                           beta[:, 16:], first, 3)
    np.testing.assert_allclose(whole, rest, atol=2e-5, rtol=0)


# ------------------------------------------------------ latent attention

def test_absorbed_attention_is_expanded_attention():
    rng = np.random.default_rng(11)
    b, t, h, rank, d_n, d_r, d_v = 2, 9, 4, 32, 16, 8, 16
    q_n = jnp.asarray(rng.normal(size=(b, t, h, d_n)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(b, t, h, d_r)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(b, t, rank)), jnp.float32)
    k_r = jnp.asarray(rng.normal(size=(b, t, d_r)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(rank, h, d_n + d_v)) * 0.2,
                    jnp.float32)
    want = mla.expanded_attention(q_n, q_r, lat, k_r, w)
    width = mla.row_width(rank, d_r)
    assert width == 128 and mla.row_width(512, 64) == 640
    rows = jnp.zeros((b, 24, width)).at[:, :t].set(
        mla.latent_row(lat, k_r, width, jnp.float32))
    # rows past a query's position hold what a longer sequence left
    rows = rows.at[:, t:].set(7.0)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    got = mla.absorbed_attention(q_n, q_r, rows, positions, w)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# --------------------------------------------------- through the engine

def test_prefill_then_ticks_through_the_slab_is_one_forward_pass(params):
    """Three prompts of different lengths (whole chunks, ragged, under
    three chunks) share the slab at different positions; each stream's 32
    tokens and their log-probabilities are what one full forward pass
    over prompt + emitted gives."""
    eng = ContinuousBatchingEngine(params, CFG, max_batch=4)
    try:
        assert eng.stateful and eng.kv_cache is None
        stats = eng.kv_stats()
        # 3 KDA layers x (4 x 16 x 16 float32 + 3 x 192 float32 tail),
        # one latent layer's row of 128 float32
        assert stats["state_bytes_per_slot"] == 3 * (4096 + 2304)
        assert stats["kv_bytes_per_token"] == 512
        prompts = [TOKENS[:16], TOKENS[20:39], TOKENS[40:67]]
        streams = [eng.stream(p, 32) for p in prompts]
        emitted = [[int(t) for t in s] for s in streams]
    finally:
        eng.stop()
    for prompt, out, stream in zip(prompts, emitted, streams):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        lg = _forward(params, seq[None])[0]
        lg = lg[len(prompt) - 1:]
        assert out == [int(t) for t in jnp.argmax(lg, -1)]
        lp = jax.nn.log_softmax(lg, -1)
        want = [float(lp[j, tok]) for j, tok in enumerate(out)]
        np.testing.assert_allclose(stream.scores, want, atol=TOL, rtol=0)


def test_decode_counts_what_the_expert_layers_saw(params):
    cache = kl.kimi_linear_init_cache(CFG, 4)
    assert [sorted(e) for e in cache] == [["k"]] + [["conv", "state"]] * 3
    assert cache[0]["k"].shape == (4, 128, 128)
    _, _, counts = jax.jit(lambda t, c, at: kl.kimi_linear_decode(
        params, t, CFG, c, at))(jnp.asarray(TOKENS[:4]), cache,
                                jnp.zeros(4, jnp.int32))
    # 4 tokens x 3 experts in each of the 3 expert layers, a quarter of
    # the router's width held
    assert 0 < int(counts["moe_pairs_held"]) <= 36
    assert 0 < int(counts["moe_experts_hit"]) <= min(
        12, int(counts["moe_pairs_held"]))
    assert 1 <= int(counts["moe_rows_max"]) <= 4
    with pytest.raises(ValueError, match="cannot verify drafted"):
        kl.kimi_linear_decode(params, jnp.zeros((4, 2), jnp.int32), CFG,
                              cache, jnp.zeros(4, jnp.int32))


def test_ticks_by_liveness_are_the_ticks_without_on_the_live_rows(params):
    """Four slots prefilled, then eight ticks: with `live` given (under
    interpret mode the walk `kda_step_live`) the live slots' logits, state
    and tails are those of the pass that steps every slot, and a dead
    slot's state lies where the prefill left it, to the bit, float32."""
    from ray_tpu.ops import dispatch

    live = jnp.asarray([1, 0, 1, 1], jnp.int32)
    lv = np.asarray(live) != 0
    _, cache0 = jax.jit(lambda t, c: kl.kimi_linear_forward_cached(
        params, t, CFG, c, 0))(jnp.asarray(TOKENS[:64].reshape(4, 16)),
                               kl.kimi_linear_init_cache(CFG, 4))
    shape = (4, CFG.kda_num_heads, CFG.kda_head_dim, CFG.kda_head_dim)
    with dispatch.pallas_interpret():
        walk = jax.jit(lambda cache, tok, pos: kl.kimi_linear_decode(
            params, tok, CFG, cache, pos, live)[:2])
        plain = jax.jit(lambda cache, tok, pos: kl.kimi_linear_decode(
            params, tok, CFG, cache, pos)[:2])
        walked, every = cache0, cache0
        for i in range(8):
            tok = jnp.asarray(TOKENS[4 * i:4 * i + 4])
            pos = jnp.full(4, 16 + i, jnp.int32)
            want, every = plain(every, tok, pos)
            if i == 0:
                (took,) = [c for c in dispatch.kernel_choices("state_step")
                           if c["shape"] == shape]
                assert took["choice"] == "reference" and "liveness" in \
                    took["reason"]
            got, walked = walk(walked, tok, pos)
            np.testing.assert_allclose(np.asarray(got)[lv],
                                       np.asarray(want)[lv], atol=TOL,
                                       rtol=0)
            assert np.isfinite(np.asarray(got)).all()
        (took,) = [c for c in dispatch.kernel_choices("state_step")
                   if c["shape"] == shape]
    assert took["choice"] == "pallas" and took["heads_block"] == 4
    for at in range(1, 4):
        assert walked[at]["state"].dtype == jnp.float32
        for leaf in ("state", "conv"):
            np.testing.assert_allclose(
                np.asarray(walked[at][leaf])[lv],
                np.asarray(every[at][leaf])[lv], atol=TOL, rtol=0)
        np.testing.assert_array_equal(np.asarray(walked[at]["state"])[~lv],
                                      np.asarray(cache0[at]["state"])[~lv])
        assert np.abs(np.asarray(every[at]["state"])[~lv]
                      - np.asarray(cache0[at]["state"])[~lv]).max() > 0


@pytest.mark.parametrize("form", ["reference", "pallas", "cannot-walk"])
def test_the_ring_says_how_many_slots_states_a_tick_stepped(
        params, form, monkeypatch):
    """The family's state step walks (`Family.state_walks`): a tick's
    record carries the slots the chip held live, between `live` and
    `max_batch`, with two streams of different budgets in four slots; on
    the CPU the step is the plain one over every row, under interpret mode
    the kernel. The same program without the flag steps every slot's
    state and says so; the streams are the same in all three, and the
    slab's state is float32."""
    from contextlib import nullcontext

    from ray_tpu.observability import requests as reqtrace
    from ray_tpu.ops import dispatch

    assert kl.FAMILY.state_walks
    if form == "cannot-walk":
        monkeypatch.setattr(kl, "FAMILY", dataclasses.replace(
            kl.FAMILY, state_walks=False))
    # a window of its own for each form: the tick's program is traced
    # once a config, under whichever form the process then had
    cfg = dataclasses.replace(CFG, max_seq_len=CFG.max_seq_len - 8 * (
        1 + ["reference", "pallas", "cannot-walk"].index(form)))
    reqtrace._reset_store_for_tests()
    with dispatch.pallas_interpret() if form == "pallas" else nullcontext():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=4)
        try:
            long, short = eng.stream(TOKENS[:16], 24), \
                eng.stream(TOKENS[20:39], 6)
            out = [int(t) for t in short], [int(t) for t in long]
            stats = eng.kv_stats()
            dtypes = {e["state"].dtype for e in eng._cache if "state" in e}
        finally:
            eng.stop()
    ticks = [r for r in reqtrace.store().loop_records()
             if r["engine_id"] == eng.engine_id
             and "state_slots_stepped" in r]
    reqtrace._reset_store_for_tests()
    assert dtypes == {jnp.dtype(jnp.float32)}
    assert (len(out[0]), len(out[1])) == (6, 24)
    seq = np.concatenate([TOKENS[:16], out[1][:-1]]).astype(np.int32)
    lg = kl.kimi_linear_forward(params, seq[None], cfg)[0][15:]
    assert out[1] == [int(t) for t in jnp.argmax(lg, -1)]
    stepped = [r["state_slots_stepped"] for r in ticks]
    assert ticks and stats["state_slots_stepped"] >= sum(stepped)
    (choice,) = [c for c in stats["state_step"] if c["shape"] == (
        4, cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim)]
    if form == "cannot-walk":
        assert set(stepped) == {4}
        assert stats["state_slots_stepped"] == 4 * stats["ticks_launched"]
        assert choice["choice"] == "reference" and "liveness" in \
            choice["reason"]
        return
    assert all(r["live"] <= r["state_slots_stepped"] <= 2 for r in ticks)
    # both streams live, then the long one alone: never all four slots
    assert {1, 2} <= set(stepped)
    assert stats["state_slots_stepped"] < 4 * stats["ticks_launched"]
    assert choice["choice"] == form


def test_a_run_of_tokens_must_start_at_position_zero(params):
    step, init_cache, _ = _model_fns(CFG)
    with pytest.raises(ValueError, match="prefill from position 0"):
        step(params, TOKENS[None, :8], CFG, init_cache(CFG, 1), 4)
    with pytest.raises(ValueError, match="prefill from position 0"):
        jax.jit(step, static_argnums=(2,))(
            params, TOKENS[None, :8], CFG, init_cache(CFG, 1), jnp.int32(0))


# ------------------------------------------------------ the expert layer

def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """Each share computes its own experts' part and the shared expert;
    the four parts, the shared expert counted once, are the layer with
    all 16 experts held."""
    moe = params["blocks"][1]["moe"]
    key = jax.random.PRNGKey(21)
    w1 = 0.2 * jax.random.normal(key, (16, 64, 64))
    w2 = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (16, 32, 64))
    h = jax.random.normal(jax.random.fold_in(key, 2), (2, 5, 64))
    whole, counts = kl.expert_layer(
        h, {**moe, "w1": w1, "w2": w2},
        dataclasses.replace(CFG, experts_held=16))
    assert int(counts["pairs_held"]) == 2 * 5 * 3
    parts, pairs = [], 0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part, counts = kl.expert_layer(
            h, {**moe, "w1": w1[held], "w2": w2[held]},
            dataclasses.replace(CFG, first_expert=4 * rank))
        parts.append(part)
        pairs += int(counts["pairs_held"])
    assert pairs == 2 * 5 * 3
    none_held = dataclasses.replace(CFG, n_routed_experts=20,
                                    first_expert=16)
    shared, counts = kl.expert_layer(
        h, {**moe, "w1": w1[:4], "w2": w2[:4],
            "router": jnp.pad(moe["router"], ((0, 0), (0, 4))),
            "router_bias": jnp.pad(moe["router_bias"], (0, 4),
                                   constant_values=-10.0)}, none_held)
    assert int(counts["pairs_held"]) == 0 == int(counts["experts_hit"])
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, atol=2e-5,
                               rtol=0)


# ----------------------------------------------------------- the engine

def test_the_engine_refuses_for_this_familys_state_in_words(params):
    for kwargs, why in [({"prefix_cache": True}, "snapshot of the"),
                        ({"speculate_k": 2}, "cannot be un-advanced"),
                        ({"lora_pool": object()}, "adapter pool")]:
        with pytest.raises(ValueError, match="slots own recurrent state"
                           ) as err:
            ContinuousBatchingEngine(params, CFG, max_batch=2, **kwargs)
        assert why in str(err.value)
    eng = ContinuousBatchingEngine(params, CFG, max_batch=2)
    try:
        rows = jnp.zeros((4, 8, 128), jnp.float32)
        with pytest.raises(ValueError, match="adopt_prefill"):
            eng.adopt_prefill(8, 1, rows, rows, 4)
    finally:
        eng.stop()


# ----------------- the older families' programs, as before the protocol

def _parent_programs():
    """`_prefill_paged`, `_splice_slot` and `_tick` as PR 31's parent
    commit had them (a sequence entry always held "k" AND "v"), under
    the engine's own names so that the lowered modules are named alike.
    `_tick` is that commit's but for its last output (PR 32)."""

    @functools.partial(jax.jit, static_argnums=(2,))
    def _prefill_paged(params, suffix, config, prefix_k, prefix_v):
        fwd, init_cache, _ = _model_fns(config)
        c = prefix_k.shape[1]
        cache = list(init_cache(config, 1))
        kv_at = [i for i, blk in enumerate(cache) if "k" in blk]
        base_k = jnp.zeros((len(kv_at), config.max_seq_len)
                           + prefix_k.shape[2:], prefix_k.dtype)
        base_v = jnp.zeros_like(base_k)
        if c:
            base_k = base_k.at[:, :c].set(prefix_k)
            base_v = base_v.at[:, :c].set(prefix_v)
        for j, i in enumerate(kv_at):
            cache[i] = {"k": base_k[j][None], "v": base_v[j][None]}
        logits, cache = fwd(params, suffix, config, cache, c)
        ck = jnp.stack([cache[i]["k"][0] for i in kv_at])
        cv = jnp.stack([cache[i]["v"][0] for i in kv_at])
        state = [blk for blk in cache if "k" not in blk]
        return logits[:, -1], ck, cv, state

    @functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
    def _splice_slot(cache, ck, cv, slot, config, plen, state=()):
        del config
        out, layer, states = [], 0, iter(state)
        for blk in cache:
            if "k" in blk:
                out.append({
                    "k": jax.lax.dynamic_update_slice(
                        blk["k"], ck[layer, :plen][None], (slot, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        blk["v"], cv[layer, :plen][None], (slot, 0, 0, 0)),
                })
                layer += 1
            else:
                out.append(jax.tree.map(
                    lambda slab, one: jax.lax.dynamic_update_slice(
                        slab, one.astype(slab.dtype),
                        (slot,) + (0,) * (slab.ndim - 1)),
                    blk, next(states)))
        return out

    @functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
    def _tick(params, config, cache, tokens, pos_vec):
        logits, cache, *counts = _model_fns(config)[2](
            params, tokens, config, cache, pos_vec)
        live = logits[..., :config.vocab_size].astype(jnp.float32)
        nxt = jnp.argmax(live, axis=-1).astype(jnp.int32)
        lp = jnp.max(live, axis=-1) - jax.nn.logsumexp(live, axis=-1)
        # PR 32: the position vector advanced comes back beside them,
        # the next tick's input where it lies; nothing else is new
        return cache, nxt, lp, (counts[0] if counts else None), pos_vec + 1

    return {"_prefill_paged": _prefill_paged, "_splice_slot": _splice_slot,
            "_tick": _tick}


OLDER = {"gpt2": (GPT2Config.tiny, gpt2_init),
         "llama": (LlamaConfig.tiny, llama_init),
         "nemotron_h": (NemotronHConfig.tiny, nemotron_h_init)}


def _lowered(programs, family, name):
    tiny, init = OLDER[family]
    cfg = tiny()
    shapes = functools.partial(jax.tree.map, lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype))
    params = shapes(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, 4)))
    n_entries, lead = len(cache), cache[0]["k"]
    n_kv = sum("k" in blk for blk in cache)
    state = tuple(jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (1,) + x.shape[1:], x.dtype), blk)
        for blk in cache if "k" not in blk)
    if name == "_tick":
        vec = jax.ShapeDtypeStruct((4,), jnp.int32)
        return programs[name].lower(params, cfg, cache, vec, vec).as_text()
    if name == "_prefill_paged":
        empty = jax.ShapeDtypeStruct((n_entries, 0) + lead.shape[2:],
                                     lead.dtype)
        suffix = jax.ShapeDtypeStruct((1, 12), jnp.int32)
        return programs[name].lower(params, suffix, cfg, empty,
                                    empty).as_text()
    rows = jax.ShapeDtypeStruct((n_kv, cfg.max_seq_len) + lead.shape[2:],
                                lead.dtype)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    return programs[name].lower(cache, rows, rows, slot, cfg, 12,
                                state).as_text()


@pytest.mark.parametrize("name", ["_prefill_paged", "_splice_slot", "_tick"])
@pytest.mark.parametrize("family", sorted(OLDER))
def test_an_older_family_lowers_to_the_program_it_lowered_to(family, name):
    now = {n: getattr(engine_mod, n) for n in
           ("_prefill_paged", "_splice_slot", "_tick")}
    text = _lowered(now, family, name)
    assert text == _lowered(_parent_programs(), family, name)
    assert f"@jit_{name}" in text
