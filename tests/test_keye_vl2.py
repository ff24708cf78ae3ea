"""The Keye-VL-2.0 family at its toy size: the program against the plain
reference over a prompt that crosses the indexer's top k, then decoding
through the slab (the index keys read where they lie, the picked rows of
keys and values gathered) against the full forward; the grouped-query
selected kernel against its `jax.numpy` twin on ragged lengths, padding
never selected; the sectioned multimodal rotary against the plain one;
the route against SmallThinker's; and what `models/family.py` says of a
cache that is pairs AND index."""
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

from benchmarks.harness import configs, reference, traffic  # noqa: E402
from ray_tpu.models import keye_vl2 as m  # noqa: E402
from ray_tpu.models import smallthinker  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.family import (family_of, refuse, slab_spec,  # noqa: E402
                                   stacks)
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.ops import dispatch, dsa  # noqa: E402
from ray_tpu.ops.rope import apply_rope, rope_table  # noqa: E402

CONFIG = "keye-vl2-30b-l6"
TOKENS = np.random.default_rng(3).integers(1, 500, 64).astype(np.int32)
PROMPT, TOTAL = 30, 50      # the top k is 12


@functools.lru_cache(maxsize=None)
def _toy(dtype=jnp.float32, **changed):
    """Made once a module for each set of arguments (the init and its 200
    draws of noise are a quarter of a minute; nothing writes into what is
    handed back)."""
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype,
                              **changed)
    params = configs.init_params(conf, cfg, 5)
    # at 64 wide the init's 0.02 leaves every layer a whisper: with noise
    # the layers, the indexer and the router all count
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 200))
    params = jax.tree.map(
        lambda x: x + (0.3 * jax.random.normal(
            next(keys), x.shape, jnp.float32)).astype(x.dtype), params)
    return conf, cfg, params


def test_the_program_is_the_reference_over_a_prompt_and_through_the_slab():
    conf, cfg, params = _toy()
    assert (cfg.index_topk, cfg.attn_block, cfg.head_group) == (12, 8, 2)
    want = np.asarray(reference.logits(conf, params, TOKENS[:TOTAL]))
    tokens = jnp.asarray(TOKENS[:TOTAL])[None]
    got = jax.jit(lambda t: m.keye_vl2_forward(params, t, cfg))(tokens)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)
    cache = m.keye_vl2_init_cache(cfg, 1)
    logits, cache, counts = jax.jit(
        lambda t, c: m.keye_vl2_forward_counted(params, t, cfg, c, 0))(
            tokens[:, :PROMPT], cache)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[PROMPT - 1],
                               atol=2e-4)
    assert int(counts["dsa_rows_visible"]) == PROMPT * (PROMPT + 1) // 2
    assert int(counts["dsa_rows_selected"]) == 12 * 13 // 2 + 18 * 12
    assert int(counts["dsa_rows_scored"]) == 32 * 32    # padded to 32
    assert int(counts["ring_rows_read"]) == 0
    assert int(counts["moe_pairs_held"]) == 3 * PROMPT * 3
    assert int(counts["moe_rows_mean"]) == 3 * PROMPT * 3 // (3 * 8)
    assert int(counts["moe_rows_max"]) >= int(counts["moe_rows_mean"])
    # 20 steps, every one past the 12th row: the tick selects (one
    # compiled step, as the engine's tick is)
    step = jax.jit(lambda t, c, at: m.keye_vl2_decode(params, t, cfg, c,
                                                      at))
    for pos in range(PROMPT, TOTAL):
        logits, cache, counts = step(tokens[:, pos], cache,
                                     jnp.asarray([pos]))
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=2e-4)
    assert int(counts["dsa_rows_selected"]) == 12
    assert int(counts["dsa_rows_visible"]) == TOTAL
    assert int(counts["dsa_rows_scored"]) == 64
    assert int(counts["moe_experts_hit"]) == 9      # 3 layers, 3 a token


def test_a_block_size_changes_nothing_beyond_rounding():
    _conf, cfg, params = _toy()
    tokens = jnp.asarray(TOKENS[:45])[None]
    forward = jax.jit(m.keye_vl2_forward, static_argnums=2)
    want = np.asarray(forward(params, tokens, cfg))
    for changed in (dict(head_group=4), dict(ffn_block=64),
                    dict(index_block=8), dict(attn_block=16)):
        got = forward(params, tokens, dataclasses.replace(cfg, **changed))
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    with pytest.raises(ValueError, match="whole heads of keys"):
        dataclasses.replace(cfg, head_group=1)
    with pytest.raises(ValueError, match="whole attn_blocks"):
        dataclasses.replace(cfg, index_block=12)
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, first_expert=1)


@pytest.mark.parametrize("length", [45, 64])     # 64: eight blocks of
def test_the_kernels_are_their_plain_forms(length):     # keys, a packed mask
    conf, cfg, params = _toy()
    tokens = jnp.asarray(TOKENS[:length])[None]
    # each form traced as ONE program under the mode the process then has
    plain = jax.jit(lambda t: m.keye_vl2_forward(params, t, cfg))(tokens)
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        kernels = jax.jit(
            lambda t: m.keye_vl2_forward(params, t, cfg))(tokens)
    took = {c["op"]: c["choice"] for c in dispatch.kernel_choices()}
    assert {took[op] for op in ("dsa_select", "gqa_selected")} == {"pallas"}
    assert dispatch.kernel_choices("gqa_selected")[0]["shape"] \
        == (length, 2, 1, 16, 8)    # a head of keys and its two a call
    np.testing.assert_allclose(np.asarray(kernels), np.asarray(plain),
                               atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(plain[0]),
        np.asarray(reference.logits(conf, params, TOKENS[:length])),
        atol=2e-4)


@pytest.mark.parametrize("tokens,tp", [(45, 64), (21, 24), (64, 64)])
def test_the_selected_kernel_is_its_twin_and_padding_is_never_selected(
        tokens, tp):
    """Ragged lengths: the prompt is padded to whole blocks, a padded key
    lies behind every real query, and the mask `block_selection` makes
    holds none of them; the kernel (interpret mode) and its twin agree
    with dense attention under that mask."""
    rng = np.random.default_rng(0)
    heads, kv_heads, d, idx_heads, dim, topk, block = 6, 2, 16, 16, 8, 12, 8
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = arr(heads, tp, d), arr(kv_heads, tp, d), arr(kv_heads, tp, d)
    q_i, k_i, w = arr(tp, idx_heads, dim), arr(tp, dim), arr(tp, idx_heads)
    with dispatch.pallas_interpret():
        tiles, _ = dsa.selection_tiles(tp, tp, tokens, idx_heads, dim, topk)
        mask = dsa.block_selection(q_i, k_i, w, 0, topk, tokens, tiles)
        got = dsa.gqa_selected_prompt_attention(
            q, k, v, dsa.mask_tiles(mask, block), 0.25, block, tokens)
    assert dispatch.kernel_choices("gqa_selected")[-1]["choice"] == "pallas"
    mask = np.asarray(mask) != 0
    assert not mask[:tokens, tokens:].any()         # padding: never
    assert (mask[:tokens].sum(1) == np.minimum(np.arange(tokens) + 1,
                                               topk)).all()
    twin = dsa._gqa_masked_blocked(q, k, v, dsa.mask_tiles(
        jnp.asarray(mask, jnp.int8), block), 0.25, block)
    s = jnp.einsum("gjtd,gsd->gjts", q.reshape(kv_heads, 3, tp, d), k) * 0.25
    want = jnp.einsum("gjts,gsd->gjtd", jax.nn.softmax(
        jnp.where(mask[None, None], s, -jnp.inf), -1), v
    ).reshape(heads, tp, d)
    for have in (got, twin):
        np.testing.assert_allclose(np.asarray(have)[:, :tokens],
                                   np.asarray(want)[:, :tokens], atol=1e-5)
    with pytest.raises(ValueError, match="query heads over"):
        dsa.gqa_selected_prompt_attention(q[:5], k, v, dsa.mask_tiles(
            jnp.asarray(mask, jnp.int8), block), 0.25, block, tokens)


def test_a_tick_attends_the_gathered_rows_alone():
    rng = np.random.default_rng(4)
    b, rows, heads, kv_heads, d, keep = 3, 40, 4, 2, 16, 6
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, keys, values = (arr(b, heads, d), arr(b, rows, kv_heads, d),
                       arr(b, rows, kv_heads, d))
    picked = jnp.asarray(np.stack([rng.permutation(rows)[:keep]
                                   for _ in range(b)]), jnp.int32)
    seen = jnp.asarray([[1] * 6, [1] * 4 + [0] * 2, [1] + [0] * 5], bool)
    got = dsa.gqa_selected_tick(q, keys, values, picked, seen, 0.25)
    for slot in range(b):
        at = np.asarray(picked[slot])[np.asarray(seen[slot])]
        for h in range(heads):
            g = h // 2
            s = np.asarray(keys)[slot, at, g] @ np.asarray(q)[slot, h] * 0.25
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ np.asarray(values)[slot, at, g]
            np.testing.assert_allclose(np.asarray(got)[slot, h], want,
                                       atol=1e-5)


def test_the_rows_a_tick_may_stop_at_by_hand():
    # the cell's slab: 32,768 + 1,024; every smaller power of two with
    # that tail, down to twice the 2,048 kept
    assert dsa.tick_rows(33792, 2048) == (5120, 9216, 17408, 33792)
    assert dsa.tick_rows(32768, 2048) == (4096, 8192, 16384, 32768)
    assert dsa.tick_rows(64, 12) == (32, 64)
    assert dsa.tick_rows(20, 12) == (20,)       # nothing shorter holds 24
    upto = dsa.tick_rows(33792, 2048)
    for furthest, want in ((0, 0), (5119, 0), (5120, 1), (9215, 1),
                           (17407, 2), (17408, 3), (33791, 3)):
        at = dsa.tick_upto(jnp.asarray([0, furthest, 7]), upto)
        assert int(at) == want, furthest


@pytest.mark.parametrize("rows,keep", [(33, 8), (40, 6), (72, 16), (136, 12),
                                       (48, 16), (64, 12), (20, 17),
                                       (10, 12)])
def test_a_sort_of_a_power_of_two_and_its_tail_is_top_k(rows, keep):
    rng = np.random.default_rng(rows)
    # few distinct values: ties in the head, in the tail and across both,
    # and rows no slot has seen
    scores = rng.integers(-2, 3, size=(3, rows)).astype(np.float32)
    scores[2] *= np.where(rng.random(rows) < 0.5, -0.0, 1.0)    # -0.0 too
    scores[1, rows // 3:] = dsa.NEG
    scores = jnp.asarray(scores)
    want = jax.lax.top_k(scores, min(keep, rows))
    got = dsa.top_rows(scores, keep)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("furthest", [13, 39, 40, 71, 72, 135])
def test_a_tick_that_stops_early_picks_the_same_rows(furthest):
    rng = np.random.default_rng(furthest)
    b, rows, heads, dim, keep = 3, 136, 4, 8, 12      # 128 + a tail of 8
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q_i, k_i, w = arr(b, heads, dim), arr(b, rows, dim), arr(b, heads)
    positions = jnp.asarray([0, furthest, min(furthest, 5)])
    upto = dsa.tick_rows(rows, keep)
    assert upto == (40, 72, 136)
    want = dsa.tick_selection(q_i, k_i, w, positions, keep)
    got = jax.jit(lambda *a: dsa.tick_selection(*a, keep, upto))(
        q_i, k_i, w, positions)
    seen = np.asarray(want[1])
    np.testing.assert_array_equal(np.asarray(got[1]), seen)
    np.testing.assert_array_equal(np.asarray(got[0])[seen],
                                  np.asarray(want[0])[seen])
    assert seen.sum(1).tolist() == [1, min(furthest + 1, keep),
                                    min(furthest, 5) + 1]


def test_a_prompt_no_longer_than_top_k_is_dense_attention():
    conf, cfg, params = _toy(index_topk=64)
    tokens = jnp.asarray(TOKENS[:40])[None]
    got = np.asarray(m.keye_vl2_forward(params, tokens, cfg)[0])
    dense = np.asarray(reference.logits(
        {**conf, "reference_selection": "dense"}, params, TOKENS[:40]))
    np.testing.assert_allclose(got, dense, atol=2e-4)
    # and the reference's selection is not dense at the toy's 12
    picked = np.asarray(reference.logits(conf, params, TOKENS[:40]))
    assert np.abs(picked - dense).max() > 1e-2
    first = np.asarray(reference.logits(
        {**conf, "reference_selection": "first"}, params, TOKENS[:40]))
    assert np.abs(picked - first).max() > 1e-2


def test_the_sectioned_rotary_is_the_plain_one_on_text_alone():
    ref = traffic.load_module("references", "keye_vl2")
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(20, 3, 16)), jnp.float32)
    text = jnp.broadcast_to(jnp.arange(20), (3, 20))
    plain = apply_rope(x[None], *rope_table(16, 20, 1e4))[0]
    np.testing.assert_allclose(
        np.asarray(ref.rotary(x, 1e4, (2, 3, 3), text)), np.asarray(plain),
        atol=1e-5)
    # an image's patches: one time, rows and columns of their own
    image = jnp.stack([jnp.full(20, 3), jnp.arange(20) // 5,
                       jnp.arange(20) % 5])
    off = np.asarray(ref.rotary(x, 1e4, (2, 3, 3), image))
    assert np.abs(off - np.asarray(plain)).max() > 0.1
    # each stream turns its own frequencies and no other: the temporal
    # stream alone moved, the last six frequencies stand
    moved = np.asarray(ref.rotary(x, 1e4, (2, 3, 3), text.at[0].add(7)))
    same = np.r_[2:8, 10:16]
    np.testing.assert_allclose(moved[..., same], np.asarray(plain)[..., same],
                               atol=1e-5)
    assert np.abs(moved[..., :2] - np.asarray(plain)[..., :2]).max() > 0.1
    # and through the whole model: text positions given are the default
    conf, _cfg, params = _toy()
    w = ref.weights(params)
    tokens = jnp.asarray(TOKENS[:20])
    np.testing.assert_allclose(
        np.asarray(ref.logits(w, tokens, conf, positions=text)),
        np.asarray(ref.logits(w, tokens, conf)), atol=1e-5)
    assert np.abs(np.asarray(ref.logits(w, tokens, conf, positions=image))
                  - np.asarray(ref.logits(w, tokens, conf))).max() > 1e-2


def test_the_route_is_smallthinkers_on_the_same_logits():
    _conf, cfg, params = _toy()
    p = params["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(2), (40, cfg.d_model))
    valid = jnp.ones(40, bool)
    out, sizes = m.expert_layer(h, valid, p, cfg)
    # the other family's expert layer is handed its route from outside
    st = smallthinker.SmallThinkerConfig.tiny()
    st = dataclasses.replace(st, num_experts=cfg.num_experts,
                             num_experts_per_tok=cfg.num_experts_per_tok,
                             dtype=jnp.float32)
    chosen, weights = smallthinker._route(h, {"moe": p}, st)
    logits = np.asarray(h @ p["router"])
    best = np.argsort(-logits, axis=-1)[:, :cfg.num_experts_per_tok]
    np.testing.assert_array_equal(np.asarray(chosen), best)
    top = np.take_along_axis(logits, best, -1)
    soft = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(weights),
                               soft / soft.sum(-1, keepdims=True), atol=1e-5)
    # the same experts under the same weights: this family's SwiGLU sum
    inter = cfg.moe_intermediate_size
    want = np.zeros_like(np.asarray(out))
    for t in range(40):
        for e, wt in zip(np.asarray(chosen[t]), np.asarray(weights[t])):
            gu = np.asarray(h[t] @ p["w1"][e])
            mid = gu[:inter] / (1 + np.exp(-gu[:inter])) * gu[inter:]
            want[t] += wt * (mid @ np.asarray(p["w2"][e]))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    assert int(sizes.sum()) == 40 * cfg.num_experts_per_tok
    # a padded row routes nowhere
    _, fewer = m.expert_layer(h, valid.at[-4:].set(False), p, cfg)
    assert int(fewer.sum()) == 36 * cfg.num_experts_per_tok


CAPABILITIES = (
    ("prefix_cache", "no block of an index key", "prefix_cache=True"),
    ("speculate_k", "each drafted token would select rows of its own",
     "speculate_k=2"),
    ("lora_pool", "not an indexer's", "lora_pool"),
    ("adopt_prefill", "the index keys are a second stack", "adopt_prefill"),
    ("transfer", "the index keys would be left behind",
     "cannot be served disaggregated"))


def test_a_cache_that_is_pairs_and_index():
    cfg = m.KeyeVL2Config.tiny()
    assert family_of(cfg) is m.FAMILY
    spec = slab_spec(cfg, 3)
    assert spec.kind == "pairs_index"
    assert not spec.latent_only and not spec.paired and not spec.stateful
    assert spec.ring_rows is None
    # entries of two widths under one row count, a stack each, one of
    # them in pairs
    cache = m.keye_vl2_init_cache(cfg, 3)
    assert list(stacks(cache)) == [(128, 2, 16), (128, 8)]
    assert list(spec.stacks.values()) == [[0, 2, 4], [1, 3, 5]]
    assert spec.paired_stacks == (True, False)
    assert spec.by_rows == {128: [0, 1, 2, 3, 4, 5]}
    assert spec.slab == [{"rows": 128, "layers": 6, "bytes_per_slot":
                          3 * 128 * (2 * 2 * 16 + 8) * 2}]
    assert spec.kv_bytes_per_token == 3 * (2 * 2 * 16 + 8) * 2
    assert m.entries_of(cfg) == ((0, 1), (2, 3), (4, 5))
    # the kinds that were there read as they did
    assert slab_spec(smallthinker.SmallThinkerConfig.tiny(), 2).kind == "ring"
    assert slab_spec(smallthinker.SmallThinkerConfig.tiny(), 2
                     ).paired_stacks == (True, True)


@pytest.mark.parametrize("capability,words,end", CAPABILITIES,
                         ids=[c[0] for c in CAPABILITIES])
def test_a_cache_of_pairs_and_index_is_refused_in_words(capability, words,
                                                        end):
    spec = slab_spec(m.KeyeVL2Config.tiny(), 3)
    with pytest.raises(ValueError, match=words) as err:
        refuse(spec, capability, k=2)
    assert "keys and values in pairs" in str(err.value)
    assert "an indexer's keys with no values" in str(err.value)
    assert end in str(err.value)
    refuse(spec, capability, None)      # left to its default: nothing


def test_the_engine_refuses_it_a_pool_and_builds_none():
    cfg = m.KeyeVL2Config.tiny()
    for asked in (dict(prefix_cache=True), dict(speculate_k=2)):
        with pytest.raises(ValueError, match="an indexer's keys"):
            ContinuousBatchingEngine(None, cfg, max_batch=2, **asked)
    engine = ContinuousBatchingEngine(None, cfg, max_batch=2)
    try:
        assert engine.kv_cache is None
        with pytest.raises(ValueError, match="a second stack"):
            engine.adopt_prefill(3, 1, None, None, 4)
    finally:
        engine.stop()


def test_the_engine_serves_it_as_generate_does():
    cfg = m.KeyeVL2Config.tiny()
    params = m.keye_vl2_init(cfg, jax.random.PRNGKey(0))
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(params, cfg, max_batch=3)
    try:
        stats = engine.kv_stats()
        assert stats["ring_rows"] is None and not stats["latent_only"]
        # both entries of a layer under the one row count
        assert stats["slab"] == [{"rows": 128, "layers": 6,
                                  "bytes_per_slot": 3 * 128 * 72 * 2}]
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (37, 20, 50, 13)]
        streams = [engine.stream(p, 30) for p in prompts]
        outs = [[int(t) for t in s] for s in streams]
        for prompt, out in zip(prompts, outs):
            want = generate(params, cfg, jnp.asarray([prompt]),
                            max_new_tokens=30)
            assert out == np.asarray(want)[0].tolist()
        met = engine.kv_stats()["prefill_counters"]
        assert met["dsa_rows_visible"] == sum(
            n * (n + 1) // 2 for n in (37, 20, 50, 13))
        assert met["dsa_rows_selected"] < met["dsa_rows_visible"]
        assert met["ring_rows_read"] == 0 and met["moe_pairs_held"]
    finally:
        engine.stop()
    # the admission's record and the pass's hold the counters
    records = reqtrace.store().loop_records()
    names = {"dsa_rows_scored", "dsa_rows_visible", "dsa_rows_selected",
             "ring_rows_read", "moe_experts_hit", "moe_rows_max",
             "moe_rows_mean"}
    admitted = [a for r in records for a in r["admissions"]]
    assert len(admitted) == 4 and all(names <= set(a) for a in admitted)
    ticks = [r for r in records if "dsa_rows_selected" in r]
    assert ticks and all(names <= set(r) for r in ticks)
    assert max(r["dsa_rows_selected"] for r in ticks) <= 3 * 12
    reqtrace._reset_store_for_tests()
