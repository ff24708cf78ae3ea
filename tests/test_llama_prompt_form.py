"""`models/llama.py`'s two prefill forms. A run of more than one block
of the prompt form from a concrete position 0 attends over its own rows
through `ops/swa.prompt_attention` (its `jax.numpy` blocks here, the
Pallas kernel under the interpreter); every other run keeps the slab
form. Both leave the same cache, and the engine, its prefix cache and
`generate()` give one answer over a prompt the prompt form takes."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.llama import (LlamaConfig, init_kv_cache, llama_forward,
                                  llama_forward_cached, llama_init)
from ray_tpu.ops import dispatch

BLOCK = llama._PROMPT_BLOCK
# 4 query heads a key-value head, Mistral's ratio; rows past the prompt
# stay zero in the slab
CFG = LlamaConfig(vocab_size=512, max_seq_len=3 * BLOCK, num_layers=2,
                  num_heads=8, num_kv_heads=2, d_model=128, d_ff=256,
                  dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    params = llama_init(CFG, jax.random.PRNGKey(4))
    for p in params["blocks"]:      # scores that tell the rows apart
        p["attn"]["wq"] = p["attn"]["wq"] * 4
        p["attn"]["wk"] = p["attn"]["wk"] * 4
    return params


def _tokens(t, seed=0, batch=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (batch, t), dtype=np.int32))


def _slab_form(model, toks, cache):
    """The slab form over the same run: a traced `pos` is no prompt."""
    return jax.jit(lambda pos: llama_forward_cached(
        model, toks, CFG, cache, pos))(jnp.int32(0))


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["blocks", "pallas_interpret"])
def test_a_prompt_from_position_0_takes_the_prompt_form(model, kernel):
    t = 2 * BLOCK + BLOCK // 2
    toks = _tokens(t, batch=2)
    cache = init_kv_cache(CFG, 2)
    want, want_cache = _slab_form(model, toks, cache)
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret() if kernel else contextlib.nullcontext():
        got, got_cache = jax.jit(lambda: llama_forward_cached(
            model, toks, CFG, cache, 0))()
    (choice,) = dispatch.kernel_choices("gqa_prefill")
    assert choice["shape"] == (2, t, CFG.num_heads, CFG.num_kv_heads,
                               CFG.head_dim, 0)
    assert choice["choice"] == ("pallas" if kernel else "reference")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got, llama_forward(model, toks, CFG),
                               atol=2e-5, rtol=0)
    assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    for n in ("k", "v"):
        # layer 0's rows come from the embeddings alone: bit for bit;
        # layer 1's follow an attention in the other form
        np.testing.assert_array_equal(got_cache[0][n], want_cache[0][n])
        np.testing.assert_allclose(got_cache[1][n], want_cache[1][n],
                                   atol=2e-5, rtol=0)
        assert not np.asarray(got_cache[1][n][:, t:]).any()


@pytest.mark.parametrize("t,pos", [
    (BLOCK, 0),                     # one block: nothing to skip
    (5, 0),
    (BLOCK + 88, 8),                # a suffix on top of cached rows
    (1, BLOCK + 3)],                # the scan's one token
    ids=["one_block", "short", "suffix", "one_token"])
def test_every_other_run_keeps_the_slab_form(model, t, pos):
    toks = _tokens(t, seed=t)
    dispatch.reset_kernel_choices()
    got, _ = llama_forward_cached(model, toks, CFG, init_kv_cache(CFG, 1),
                                  pos)
    assert dispatch.kernel_choices("gqa_prefill") == []
    if pos == 0:
        np.testing.assert_allclose(got, llama_forward(model, toks, CFG),
                                   atol=2e-5, rtol=0)


def test_a_traced_position_is_no_prompt(model):
    dispatch.reset_kernel_choices()
    _slab_form(model, _tokens(BLOCK + 8), init_kv_cache(CFG, 1))
    assert dispatch.kernel_choices("gqa_prefill") == []


def test_a_suffix_continues_what_the_prompt_form_left(model):
    """The cache the prompt form fills is the slab form's to read: a
    second run on top of it gives the whole run's logits."""
    t, more = BLOCK + 88, 40
    toks = _tokens(t + more, seed=7)
    whole, _ = _slab_form(model, toks, init_kv_cache(CFG, 1))
    _, cache = llama_forward_cached(model, toks[:, :t], CFG,
                                    init_kv_cache(CFG, 1), 0)
    got, _ = llama_forward_cached(model, toks[:, t:], CFG, cache, t)
    np.testing.assert_allclose(got, whole[:, t:], atol=2e-5, rtol=0)


def test_engine_prefix_cache_and_generate_agree_over_a_long_prompt(model):
    prompt = _tokens(600, seed=11)[0].tolist()
    want = np.asarray(generate(model, CFG, jnp.asarray([prompt], jnp.int32),
                               max_new_tokens=6))[0].tolist()
    shape = (1, 600, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim, 0)
    # the choices are the process's, recorded where a program is TRACED:
    # the second engine meets the first one's program
    dispatch.reset_kernel_choices()
    for prefix_cache in (False, True):
        eng = ContinuousBatchingEngine(model, CFG, max_batch=2,
                                       prefix_cache=prefix_cache)
        try:
            assert eng.generate(prompt, 6) == want
            assert [c["shape"] for c in eng.kv_stats()["gqa_prefill"]] \
                == [shape]
            if prefix_cache:
                # the replay prefills a suffix on top of the cached rows:
                # the slab form, and no new shape of the prompt form
                assert eng.generate(prompt, 6) == want
                stats = eng.kv_stats()
                assert stats["hits"] >= 1
                assert [c["shape"] for c in stats["gqa_prefill"]] == [shape]
        finally:
            eng.stop()
