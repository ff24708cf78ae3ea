"""Model family tests — training on the 8-device CPU mesh (the fake-GPU
analog, SURVEY.md §4): loss decreases, shardings compile, GQA/MoE paths
exercised."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import (LlamaConfig, init_kv_cache, llama_decode,
                                  llama_forward, llama_forward_cached,
                                  llama_init, llama_loss,
                                  llama_partition_specs)
from ray_tpu.models.moe_transformer import (MoEConfig, moe_forward,
                                            moe_init, moe_loss,
                                            moe_partition_specs)
from ray_tpu.ops.layers import mm, rms_norm
from ray_tpu.ops.rope import apply_rope, rope_table
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.trainer import TrainStep


def _batch(vocab, b, t, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (b, t + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:])}


def test_rope_rotation_properties():
    cos, sin = rope_table(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64))
    y = apply_rope(x, cos, sin)
    # norms are preserved per pair-plane rotation
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)
    # relative property: shifting positions changes embeddings
    y_shift = apply_rope(x, cos, sin,
                         positions=jnp.ones((2, 16), jnp.int32))
    assert not np.allclose(np.asarray(y), np.asarray(y_shift))


def test_llama_forward_shapes():
    cfg = LlamaConfig.tiny()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    logits = jax.jit(lambda p, t: llama_forward(p, t, cfg))(params, toks)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert logits.dtype == jnp.float32


def test_llama_gqa_kv_heads():
    cfg = LlamaConfig.tiny()
    assert cfg.num_kv_heads < cfg.num_heads  # GQA actually exercised
    params = llama_init(cfg, jax.random.PRNGKey(0))
    kv_dim = cfg.num_kv_heads * cfg.head_dim
    assert params["blocks"][0]["attn"]["wk"].shape == (cfg.d_model, kv_dim)


def test_llama_trains_on_mesh():
    cfg = LlamaConfig.tiny()
    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    step = TrainStep(
        lambda p, b: llama_loss(p, b["tokens"], b["targets"], cfg),
        optax.adamw(1e-2), mesh, llama_partition_specs(cfg))
    state = step.init_state(llama_init(cfg, jax.random.PRNGKey(0)))
    batch = _batch(cfg.vocab_size, 8, 32)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


def test_llama_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = LlamaConfig.tiny()
    params = llama_init(cfg, jax.random.PRNGKey(1))
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, -1].set(7)
    l1 = llama_forward(params, t1, cfg)
    l2 = llama_forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                               np.asarray(l2[:, :-1]), atol=2e-2)


def test_moe_forward_and_router():
    cfg = MoEConfig.tiny()
    params = moe_init(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    logits, router = moe_forward(params, toks, cfg,
                                 return_router_logits=True)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert len(router) == cfg.num_layers
    assert router[0].shape == (2 * 16, cfg.num_experts)


def test_moe_trains_on_mesh():
    cfg = MoEConfig.tiny()
    mesh = make_mesh(MeshConfig(dp=-1, ep=2))
    step = TrainStep(
        lambda p, b: moe_loss(p, b["tokens"], b["targets"], cfg),
        optax.adamw(1e-2), mesh, moe_partition_specs(cfg))
    state = step.init_state(moe_init(cfg, jax.random.PRNGKey(0)))
    batch = _batch(cfg.vocab_size, 8, 32)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


def test_moe_aux_loss_positive():
    cfg = MoEConfig.tiny()
    params = moe_init(cfg, jax.random.PRNGKey(0))
    b = _batch(cfg.vocab_size, 2, 16)
    with_aux = float(moe_loss(params, b["tokens"], b["targets"], cfg))
    import dataclasses
    no_aux = float(moe_loss(params, b["tokens"], b["targets"],
                            dataclasses.replace(cfg, aux_loss_coeff=0.0)))
    assert with_aux > no_aux  # balancing term contributes


def test_presets_are_consistent():
    for cfg in [LlamaConfig.llama2_7b(), LlamaConfig.llama3_8b()]:
        assert cfg.d_model % cfg.num_heads == 0
        assert cfg.num_heads % cfg.num_kv_heads == 0
    m = MoEConfig.mixtral_8x7b()
    assert m.num_experts == 8 and m.top_k == 2


# -- attention over the KV cache, grouped per key-value head -----------

_B, _S = 2, 64
_CACHE_PATHS = ["decode_one", "decode_verify", "forward_cached"]


def _gqa_cfg(rep, dtype=jnp.bfloat16):
    return LlamaConfig(vocab_size=512, max_seq_len=_S, num_layers=2,
                       num_heads=8, num_kv_heads=8 // rep, d_model=128,
                       d_ff=256, dtype=dtype)


def _cache_case(path, cfg):
    """(fn(params, cache), toks [B, t], positions [B, t]): one of the
    three entries into the cache paths. The decode slots are ragged,
    one of them on the slab's last rows."""
    t = {"decode_one": 1, "decode_verify": 3, "forward_cached": 5}[path]
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (_B, t), dtype=np.int32))
    base = jnp.asarray([7, 7] if path == "forward_cached" else [5, _S - t],
                       jnp.int32)
    if path == "forward_cached":
        def fn(params, cache):
            return llama_forward_cached(params, toks, cfg, cache, base[0])
    else:
        def fn(params, cache):
            return llama_decode(params, toks[:, 0] if t == 1 else toks,
                                cfg, cache, base)
    return fn, toks, base[:, None] + jnp.arange(t)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("path", _CACHE_PATHS)
def test_llama_cache_attention_never_repeats_the_slab(path):
    # no equation may build the cache at query-head width: on the chip
    # that array is a copy of the whole slab, once per layer (PERF.md)
    cfg = _gqa_cfg(rep=4)
    fn, _, _ = _cache_case(path, cfg)
    params = jax.eval_shape(lambda: llama_init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, _B))
    limit = _B * _S * cfg.num_heads * cfg.head_dim
    wide = [(eqn.primitive.name, v.aval.shape)
            for eqn in _equations(jax.make_jaxpr(fn)(params, cache).jaxpr)
            for v in eqn.outvars
            if hasattr(v.aval, "shape") and np.prod(v.aval.shape) >= limit]
    assert not wide, wide


def _ref_cache_forward(params, toks, cfg, cache, positions):
    """The formulation the model had before grouping, kept only here:
    keys and values repeated to the query heads with an explicit
    jnp.repeat, and the attention in float32 throughout."""
    c = cfg
    rep = c.num_heads // c.num_kv_heads
    b, t = toks.shape
    cos, sin = rope_table(c.head_dim, c.max_seq_len, c.rope_theta)
    x = params["tok_emb"][toks]
    rows = jnp.arange(b)[:, None]
    new_cache = []
    for p, blk in zip(params["blocks"], cache):
        q, k, v = llama._qkv(rms_norm(x, p["attn_norm"]["scale"]), p, c)
        q = apply_rope(q, cos, sin, positions).astype(jnp.float32)
        ck = blk["k"].at[rows, positions].set(
            apply_rope(k, cos, sin, positions))
        cv = blk["v"].at[rows, positions].set(v)
        kk = jnp.repeat(ck, rep, axis=2).astype(jnp.float32)
        vv = jnp.repeat(cv, rep, axis=2).astype(jnp.float32)
        scores = jnp.einsum("bthd,bshd->bhts", q, kk) / c.head_dim ** 0.5
        seen = jnp.arange(_S)[None, None, None] <= positions[:, None, :, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        a = jnp.einsum("bhts,bshd->bthd", probs, vv)
        a = a.reshape(b, t, c.d_model).astype(x.dtype)
        x = llama._mlp_res(x + mm(a, p["attn"]["wo"]), p)
        new_cache.append({"k": ck, "v": cv})
    x = rms_norm(x, params["norm_f"]["scale"])
    return jnp.dot(x, params["lm_head"],
                   preferred_element_type=jnp.float32), new_cache


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("path", _CACHE_PATHS)
@pytest.mark.parametrize("rep", [1, 2, 4])
def test_llama_cache_attention_means_what_the_repeat_meant(rep, path,
                                                           dtype, tol):
    cfg = _gqa_cfg(rep, dtype)
    params = llama_init(cfg, jax.random.PRNGKey(rep))
    for p in params["blocks"]:      # scores that tell the rows apart
        p["attn"]["wq"] = p["attn"]["wq"] * 4
        p["attn"]["wk"] = p["attn"]["wk"] * 4
    # a slab full of other sequences' rows: what lies past a slot's
    # position must stay unseen
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 2 * cfg.num_layers))
    cache = [{n: jax.random.normal(next(keys), blk[n].shape, dtype)
              for n in ("k", "v")} for blk in init_kv_cache(cfg, _B)]
    fn, toks, positions = _cache_case(path, cfg)
    logits, new_cache = fn(params, cache)
    want, want_cache = _ref_cache_forward(params, toks, cfg, cache,
                                          positions)
    np.testing.assert_allclose(np.asarray(logits).reshape(want.shape),
                               np.asarray(want), atol=tol, rtol=0)
    # layer 0's rows depend on the embeddings alone: bit for bit. Layer
    # 1's come after an attention whose probabilities the model rounds
    # to its dtype and the reference does not: a few ulp of a value.
    for n in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(new_cache[0][n], np.float32),
            np.asarray(want_cache[0][n], np.float32))
        np.testing.assert_allclose(
            np.asarray(new_cache[1][n], np.float32),
            np.asarray(want_cache[1][n], np.float32), atol=tol, rtol=tol)
