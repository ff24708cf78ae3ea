"""Continuous-batching engine: ragged requests share one fixed-shape
decode loop; outputs must equal per-request generate() exactly
(greedy), including for requests that join mid-decode."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.llama import LlamaConfig, llama_init

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def engine(model):
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4)
    yield eng
    eng.stop()


def _reference(model, prompt, n):
    return np.asarray(generate(model, CFG, jnp.asarray([prompt],
                                                       jnp.int32),
                               max_new_tokens=n))[0].tolist()


def test_single_request_matches_generate(model, engine):
    prompt = [1, 2, 3, 4, 5]
    got = engine.generate(prompt, 8)
    assert got == _reference(model, prompt, 8)


def test_concurrent_ragged_requests_match(model, engine):
    """Different prompt lengths and budgets, submitted together, all
    decode in the shared loop and match solo generation."""
    import concurrent.futures as cf

    prompts = [[7], [1, 2, 3], [9, 8, 7, 6, 5, 4], [2, 4, 6, 8]]
    budgets = [6, 9, 4, 7]
    with cf.ThreadPoolExecutor(4) as pool:
        futs = [pool.submit(engine.generate, p, n)
                for p, n in zip(prompts, budgets)]
        got = [f.result(timeout=120) for f in futs]
    for p, n, g in zip(prompts, budgets, got):
        assert g == _reference(model, p, n), (p, n)


def test_join_mid_decode_matches(model, engine):
    """A request arriving while another decodes must not perturb either
    sequence (slot isolation through per-slot positions/masking)."""
    import concurrent.futures as cf

    with cf.ThreadPoolExecutor(2) as pool:
        long_fut = pool.submit(engine.generate, [1, 2, 3], 20)
        time.sleep(0.2)  # the first request is mid-decode now
        short = engine.generate([5, 5, 5, 5], 5)
        long = long_fut.result(timeout=120)
    assert long == _reference(model, [1, 2, 3], 20)
    assert short == _reference(model, [5, 5, 5, 5], 5)


def test_more_requests_than_slots(model):
    eng = ContinuousBatchingEngine(model, CFG, max_batch=2)
    try:
        import concurrent.futures as cf

        prompts = [[i + 1] for i in range(5)]
        with cf.ThreadPoolExecutor(5) as pool:
            futs = [pool.submit(eng.generate, p, 4) for p in prompts]
            got = [f.result(timeout=120) for f in futs]
        for p, g in zip(prompts, got):
            assert g == _reference(model, p, 4), p
    finally:
        eng.stop()


def test_eos_frees_slot_early(model, engine):
    ref = _reference(model, [3, 1, 4], 10)
    eos = ref[1]
    got = engine.generate([3, 1, 4], 10, eos_token=eos)
    assert got == ref[:2]
    assert engine.active_slots == 0


def test_slot_reuse_is_clean(model, engine):
    """A slot's previous occupant must never leak into the next (stale
    cache beyond the new prompt is masked out)."""
    a = engine.generate([9, 9, 9, 9, 9, 9, 9, 9], 6)  # long occupant
    b = engine.generate([2], 6)                        # short successor
    assert a == _reference(model, [9] * 8, 6)
    assert b == _reference(model, [2], 6)


def test_gpt2_engine_matches_generate():
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init

    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(3))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        import concurrent.futures as cf

        prompts = [[1, 2, 3], [4, 5]]
        with cf.ThreadPoolExecutor(2) as pool:
            got = [f.result(timeout=120) for f in
                   [pool.submit(eng.generate, p, 5) for p in prompts]]
        for p, g in zip(prompts, got):
            want = np.asarray(generate(params, cfg,
                                       jnp.asarray([p], jnp.int32),
                                       max_new_tokens=5))[0].tolist()
            assert g == want, p
    finally:
        eng.stop()


def test_gpt2_engine_prefix_pool_matches_generate():
    """GPT-2's row-packed cache through the paged pool: a prompt is
    committed, a second one that shares its prefix is gathered from the
    pool and spliced, and both decode to generate()'s tokens."""
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init

    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(3))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                   kv_block_size=4, kv_pool_blocks=16)
    try:
        shared = [7, 3, 9, 1, 4, 4, 8, 2, 6, 5]
        prompts = [shared + [11, 12], shared + [13]]
        got = [eng.generate(p, 5) for p in prompts]
        stats = eng.kv_stats()
        assert stats["reused_tokens"] >= 8, stats
        for p, g in zip(prompts, got):
            want = np.asarray(generate(params, cfg,
                                       jnp.asarray([p], jnp.int32),
                                       max_new_tokens=5))[0].tolist()
            assert g == want, p
    finally:
        eng.stop()


@pytest.mark.parametrize("small_width,slab", [(False, 1_048_576),
                                              (True, 50_331_648)])
def test_gpt2_tick_aliases_every_cache_entry(small_width, slab):
    """The donated slab comes back in place: the compiled tick aliases
    as many bytes as the slab holds, for tiny()'s heads of 32 and for
    two layers at small()'s width, heads of 64."""
    from ray_tpu.models.engine import _tick
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init,
                                     gpt2_init_kv_cache)

    cfg = dataclasses.replace(GPT2Config.small(), num_layers=2) \
        if small_width else GPT2Config.tiny()
    params = jax.eval_shape(lambda: gpt2_init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: gpt2_init_kv_cache(cfg, 8))
    vec = jax.ShapeDtypeStruct((8,), jnp.int32)
    assert slab == sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(cache))
    mem = _tick.lower(params, cfg, cache, vec, vec).compile() \
        .memory_analysis()
    assert mem.alias_size_in_bytes == slab
