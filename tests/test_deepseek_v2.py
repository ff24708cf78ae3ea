"""DeepSeek-V2 on the CPU at a tiny size: the program against the plain
reference (one forward pass, then prefill and decode through the cache),
the four shares of the expert layer against the uncut layer, the
engine's slab of latent rows alone against `generate` with the lookahead
on, what the engine refuses such a cache in words, and the counters a
prefill and a tick hand back."""
import dataclasses
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
from ray_tpu.models import deepseek_v2 as ds  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.family import family_of, slab_spec  # noqa: E402
from ray_tpu.models.generate import _model_fns, generate  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

TOL = 2e-4      # float32 on both sides, different summation orders
CFG = dataclasses.replace(ds.DeepseekV2Config.tiny(), dtype=jnp.float32)
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
# `tiny()` under the published keys, for the reference
CONF = {"family": "deepseek_v2", "hidden_size": 64, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 10000, "rope_scaling": YARN,
        "n_routed_experts": 4, "expert_parallel_size": 4, "n_group": 4,
        "topk_group": 2, "num_experts_per_tok": 3,
        "routed_scaling_factor": 16, "vocab_size": 512}
TOKENS = np.random.default_rng(0).integers(1, 500, 72).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    p = ds.deepseek_v2_init(CFG, jax.random.PRNGKey(3))
    # norm weights are ones and the layers a whisper at init: make every
    # leaf count
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 200))
    return jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        next(keys), x.shape, x.dtype), p)


def test_forward_agrees_with_the_reference(params):
    """37 tokens: five blocks of the prompt form and of the feed-forward
    part, the last of each ragged."""
    got = ds.deepseek_v2_forward(params, TOKENS[None, :37], CFG)[0]
    want = reference.logits(CONF, params, TOKENS[:37])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_another_share_is_another_function(params):
    got = ds.deepseek_v2_forward(params, TOKENS[None, :24], CFG)[0]
    other = reference.logits({**CONF, "expert_parallel_rank": 1}, params,
                             TOKENS[:24])
    assert float(jnp.max(jnp.abs(got - other))) > TOL


def test_prefill_then_decode_is_the_references_one_forward_pass(params):
    """A 21-token prefill through the prompt form, then 9 tokens through
    the absorbed form over the cache of rotated rows, each at its own
    position: the logits of every step against ONE full pass of the
    reference."""
    step, init_cache, _ = _model_fns(CFG)
    assert step is ds.deepseek_v2_forward_cached
    step = family_of(CFG).forward_counted
    want = reference.logits(CONF, params, TOKENS[:30])
    logits, cache, counts = step(params, TOKENS[None, :21], CFG,
                                 init_cache(CFG, 1), 0)
    np.testing.assert_allclose(logits[0, 0], want[20], atol=TOL, rtol=0)
    assert int(counts["attn_blocks"]) == 3 * 9     # 3 layers, 3 x 3 blocks
    # one compiled step for the nine
    one = jax.jit(lambda t, c, at: step(params, t, CFG, c, at))
    for pos in range(21, 30):
        logits, cache, counts = one(TOKENS[None, pos:pos + 1], cache,
                                    jnp.int32(pos))
        np.testing.assert_allclose(logits[0, 0], want[pos], atol=TOL,
                                   rtol=0)
        assert int(counts["attn_blocks"]) == 0
    # the row in the cache is [c | rope(k_pe) | 0]: 40 numbers of 128
    assert cache[0]["k"].shape == (1, 128, 128)
    assert float(jnp.abs(cache[0]["k"][0, :30, 40:]).max()) == 0.0
    assert float(jnp.abs(cache[0]["k"][0, 30:]).max()) == 0.0


def test_the_cache_is_latent_rows_alone_and_the_tick_counts(params):
    cache = ds.deepseek_v2_init_cache(CFG, 4)
    assert [sorted(e) for e in cache] == [["k"]] * 3
    assert slab_spec(CFG, 4).latent_only
    _, _, counts = ds.deepseek_v2_decode(
        params, jnp.asarray(TOKENS[:4]), CFG, cache,
        jnp.zeros(4, jnp.int32))
    # 4 tokens x 3 experts in each of the 2 expert layers, a quarter of
    # the router's width held
    assert 0 < int(counts["moe_pairs_held"]) <= 24
    assert 0 < int(counts["moe_experts_hit"]) <= min(
        8, int(counts["moe_pairs_held"]))
    assert 1 <= int(counts["moe_rows_max"]) <= 4
    with pytest.raises(ValueError, match="no verify form"):
        ds.deepseek_v2_decode(params, jnp.zeros((4, 2), jnp.int32), CFG,
                              cache, jnp.zeros(4, jnp.int32))


def test_a_run_of_tokens_must_start_at_position_zero(params):
    step, init_cache, _ = _model_fns(CFG)
    with pytest.raises(ValueError, match="prefill from position 0"):
        step(params, TOKENS[None, :8], CFG, init_cache(CFG, 1), 4)
    with pytest.raises(ValueError, match="prefill from position 0"):
        jax.jit(step, static_argnums=(2,))(
            params, TOKENS[None, :8], CFG, init_cache(CFG, 1), jnp.int32(0))


# ------------------------------------------------------ the expert layer

def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """Each share computes its own experts' part and the shared experts;
    the four parts, the shared experts counted once, are the layer with
    all 16 experts held: nothing is renormalised over what a share
    holds."""
    moe = params["blocks"][1]["moe"]
    key = jax.random.PRNGKey(21)
    w1 = 0.2 * jax.random.normal(key, (16, 64, 64))
    w2 = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (16, 32, 64))
    h = jax.random.normal(jax.random.fold_in(key, 2), (10, 64))
    valid = jnp.ones(10, bool)
    whole, sizes = ds.expert_layer(
        h, valid, {**moe, "w1": w1, "w2": w2},
        dataclasses.replace(CFG, experts_held=16))
    assert int(sizes.sum()) == 10 * 3
    parts, pairs = [], 0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part, got = ds.expert_layer(
            h, valid, {**moe, "w1": w1[held], "w2": w2[held]},
            dataclasses.replace(CFG, first_expert=4 * rank))
        parts.append(part)
        pairs += int(got.sum())
        np.testing.assert_array_equal(got, sizes[held])
    assert pairs == 10 * 3
    shared = ds._shared_mlp(h, moe["s1"], moe["s2"])
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, atol=2e-5,
                               rtol=0)
    # a padded row routes nowhere and counts nothing
    _, none = ds.expert_layer(h, jnp.zeros(10, bool),
                              {**moe, "w1": w1, "w2": w2},
                              dataclasses.replace(CFG, experts_held=16))
    assert int(none.sum()) == 0


def test_the_feed_forward_part_in_blocks_is_the_part_whole(params):
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 37, 64))
    for block in params["blocks"][:2]:      # the dense part, an expert one
        got, sizes = ds._ffn(x, block, CFG)            # blocks of 16
        want, whole = ds._ffn(x, block,
                              dataclasses.replace(CFG, ffn_block=64))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
        if "moe" in block:
            np.testing.assert_array_equal(sizes, whole)
        else:
            assert sizes is None and whole is None


# ----------------------------------------------------------- the engine

def test_the_engines_streams_are_generates_tokens_with_the_lookahead_on(
        params):
    """Three prompts through the engine at once (two slots: the third
    waits), prefilled through `_prefill_paged`, spliced as rows alone
    (`cv` is None) and decoded a tick ahead: the greedy tokens `generate`
    gives each prompt alone, with the scores of one full forward pass."""
    prompts = [TOKENS[:21], TOKENS[30:39], TOKENS[40:70]]
    eng = ContinuousBatchingEngine(params, CFG, max_batch=2)
    try:
        assert eng.kv_cache is None and eng.latent_only
        assert not eng.stateful
        streams = [eng.stream(p, 9) for p in prompts]
        emitted = [[int(t) for t in s] for s in streams]
        stats = eng.kv_stats()
    finally:
        eng.stop()
    assert stats["lookahead_ticks"] > 0 and stats["latent_only"]
    assert stats["kv_bytes_per_token"] == 3 * 128 * 4
    for prompt, out, stream in zip(prompts, emitted, streams):
        want = generate(params, CFG, jnp.asarray(prompt)[None],
                        max_new_tokens=9)[0]
        assert out == [int(t) for t in want]
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        lg = ds.deepseek_v2_forward(params, seq[None], CFG)[0]
        lp = jax.nn.log_softmax(lg[len(prompt) - 1:], -1)
        np.testing.assert_allclose(
            stream.scores, [float(lp[j, t]) for j, t in enumerate(out)],
            atol=TOL, rtol=0)
    # the ring: what each prefill's grouped products and prompt form saw
    ring = [r for r in reqtrace.store().loop_records()
            if r["engine_id"] == eng.engine_id]
    admissions = [a for r in ring for a in r["admissions"]]
    assert sorted(a["prompt_tokens"] for a in admissions) == [9, 21, 30]
    for a in admissions:
        nb = -(-a["prompt_tokens"] // 8)
        assert a["attn_blocks"] == 3 * nb * nb
        assert 0 < a["moe_pairs_held"] <= 2 * 3 * a["prompt_tokens"]
        assert 1 <= a["moe_rows_max"] <= a["prompt_tokens"]
        assert a["prefill_ms"] > 0 and a["splice_ms"] > 0
    totals = stats["prefill_counters"]
    assert totals["attn_blocks"] == sum(a["attn_blocks"]
                                        for a in admissions)
    assert totals["moe_pairs_held"] == sum(a["moe_pairs_held"]
                                           for a in admissions)
    ticks = [r for r in ring if r["live"]]
    assert ticks and all("moe_experts_hit" in r for r in ticks)


def test_the_engine_refuses_a_cache_of_latent_rows_alone_in_words(params):
    for kwargs, why in [({"prefix_cache": True}, "no block of one latent"),
                        ({"speculate_k": 2}, "pool proposer"),
                        ({"lora_pool": object()}, "adapter pool")]:
        with pytest.raises(ValueError, match="one latent row a token and "
                           "no values") as err:
            ContinuousBatchingEngine(params, CFG, max_batch=2, **kwargs)
        assert why in str(err.value)
    # left to the environment's default it builds no pool and serves
    os.environ["RAY_TPU_KV_CACHE"] = "1"
    try:
        eng = ContinuousBatchingEngine(params, CFG, max_batch=2)
    finally:
        del os.environ["RAY_TPU_KV_CACHE"]
    try:
        assert eng.kv_cache is None
        assert len(eng.generate(TOKENS[:9], 3)) == 3
        rows = jnp.zeros((3, 8, 128), jnp.float32)
        with pytest.raises(ValueError, match="adopt_prefill") as err:
            eng.adopt_prefill(8, 1, rows, rows, 4)
        assert "no values to carry" in str(err.value)
    finally:
        eng.stop()


def test_the_prefill_tier_refuses_it_before_it_builds_a_pool(params):
    from ray_tpu.serve.disagg import PrefillServer

    with pytest.raises(ValueError, match="cannot be served disaggregated"
                       ) as err:
        PrefillServer(params, CFG)
    assert "one latent row a token and no values" in str(err.value)
