"""KV-cache decode + autoregressive generation (the inference half of
BASELINE's "Llama JAX replica, batched inference" serving config):
cache-path logits match the full forward, greedy generation matches a
no-cache argmax rollout, and stream_generate feeds Serve streaming."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import generate, stream_generate
from ray_tpu.models.llama import (LlamaConfig, init_kv_cache, llama_forward,
                                  llama_forward_cached, llama_init)

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


def test_cached_prefill_matches_full_forward(model):
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 16)), jnp.int32)
    full = llama_forward(model, toks, CFG)
    cache = init_kv_cache(CFG, 2)
    cached, _ = llama_forward_cached(model, toks, CFG, cache, 0)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_incremental_decode_matches_full_forward(model):
    """Prefill 8 tokens then decode 6 one at a time: each step's logits
    must match the full forward over the growing sequence."""
    rng = np.random.default_rng(1)
    seq = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 14)), jnp.int32)
    cache = init_kv_cache(CFG, 1)
    _, cache = llama_forward_cached(model, seq[:, :8], CFG, cache, 0)
    # one compiled step for the six, as the engine's tick is
    step = jax.jit(lambda s, c, at: llama_forward_cached(model, s, CFG, c,
                                                         at))
    for t in range(8, 14):
        step_logits, cache = step(seq[:, t:t + 1], cache, jnp.int32(t))
        full = llama_forward(model, seq[:, :t + 1], CFG)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full[:, -1]),
            rtol=3e-4, atol=3e-4, err_msg=f"step t={t}")


def test_greedy_generate_matches_nocache_rollout(model):
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 8)), jnp.int32)
    out = generate(model, CFG, prompt, max_new_tokens=6)
    assert out.shape == (2, 6) and out.dtype == jnp.int32

    # reference rollout: argmax over full forward, no cache
    seq = prompt
    want = []
    for _ in range(6):
        logits = llama_forward(model, seq, CFG)[:, -1, :CFG.vocab_size]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.stack(want, axis=1)))


def test_sampling_respects_vocab_and_runs(model):
    prompt = jnp.zeros((3, 4), jnp.int32)
    out = generate(model, CFG, prompt, max_new_tokens=5, temperature=0.8,
                   top_k=16, key=jax.random.PRNGKey(7))
    assert out.shape == (3, 5)
    assert int(out.max()) < CFG.vocab_size  # padded rows never sampled


def test_eos_masks_tail(model):
    prompt = jnp.zeros((1, 4), jnp.int32)
    greedy = generate(model, CFG, prompt, max_new_tokens=8)
    eos = int(np.asarray(greedy)[0, 2])  # force an early "EOS"
    out = generate(model, CFG, prompt, max_new_tokens=8, eos_token=eos)
    arr = np.asarray(out)[0]
    first = int(np.argmax(arr == eos))
    assert (arr[first:] == eos).all()


def test_stream_generate_yields_matching_tokens(model):
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 8)), jnp.int32)
    want = np.asarray(generate(model, CFG, prompt, max_new_tokens=5))
    got = [int(t[0]) for t in stream_generate(model, CFG, prompt,
                                              max_new_tokens=5)]
    np.testing.assert_array_equal(np.asarray(got), want[0])


def test_prompt_overflow_rejected(model):
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(model, CFG, jnp.zeros((1, 120), jnp.int32),
                 max_new_tokens=20)


def test_gpt2_generation_matches_full_forward():
    """GPT-2 rides the same generation loop (learned positions instead
    of rope): cached logits match the full forward and greedy decode
    matches a no-cache rollout."""
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_forward,
                                     gpt2_forward_cached,
                                     gpt2_init_kv_cache, gpt2_init)

    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    full = gpt2_forward(params, toks, cfg)
    cache = gpt2_init_kv_cache(cfg, 2)
    cached, cache = gpt2_forward_cached(params, toks[:, :8], cfg, cache, 0)
    np.testing.assert_allclose(np.asarray(cached),
                               np.asarray(full[:, :8]),
                               rtol=3e-4, atol=3e-4)
    step, cache = gpt2_forward_cached(params, toks[:, 8:9], cfg, cache, 8)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, 8]),
                               rtol=3e-4, atol=3e-4)

    prompt = toks[:, :6]
    out = np.asarray(generate(params, cfg, prompt, max_new_tokens=5))
    seq = prompt
    for i in range(5):
        logits = gpt2_forward(params, seq, cfg)[:, -1, :cfg.vocab_size]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(out[:, i], np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


def _per_head_attention(q, ck, cv, positions):
    """The plain per-head einsums of a [B, S, heads, head_dim] cache:
    what `gpt2._cache_attention` is held to, to the bit."""
    hd = q.shape[-1]
    scores = jnp.einsum("bthd,bshd->bhts", q, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / (hd ** 0.5)
    col = jnp.arange(ck.shape[1])[None, None, None, :]
    visible = col <= positions[:, None, :, None]
    scores = jnp.where(visible, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    a = jnp.einsum("bhts,bshd->bthd", probs, cv)
    return a.reshape(q.shape[0], q.shape[1], -1)


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("p,hd", [(1, 128), (2, 64), (4, 32), (4, 64)])
def test_gpt2_row_packed_attention_matches_per_head(p, hd, t):
    """p heads to a row of 128 lanes (heads of 128, 64, 32) or of 256
    (4 heads of 64, GPT-2 small's row), per-slot positions, the
    one-token tick (t = 1) and the verify form (t = 3): the row-wise
    contraction gives each head's own scores and output, to the bit."""
    from ray_tpu.models.gpt2 import _cache_attention

    b, s, heads = 4, 64, 4
    rng = np.random.default_rng(7 * p + hd + t)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q, ck, cv = rand(b, t, heads, hd), rand(b, s, heads, hd), \
        rand(b, s, heads, hd)
    positions = jnp.asarray(rng.integers(0, s - t, (b, 1)), jnp.int32) \
        + jnp.arange(t)[None, :]
    want = _per_head_attention(q, ck, cv, positions)
    got = _cache_attention(q.reshape(b, t, heads // p, p * hd),
                           ck.reshape(b, s, heads // p, p * hd),
                           cv.reshape(b, s, heads // p, p * hd),
                           positions, hd)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("name,lanes", [("small", 256), ("tiny", 128)])
def test_gpt2_cache_rows_fill_whole_lane_tiles(name, lanes):
    """Heads of 64 and of 32 are held several to a row of whole
    128-lane tiles, and every layer has its entry."""
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init_kv_cache

    cfg = getattr(GPT2Config, name)()
    cache = jax.eval_shape(lambda: gpt2_init_kv_cache(cfg, 2, 16))
    assert len(cache) == cfg.num_layers
    for blk in cache:
        for side in ("k", "v"):
            assert blk[side].shape == (2, 16, cfg.d_model // lanes, lanes)
