"""The dots3-note family at its toy size: the program against the plain
reference over a prompt that crosses both the window and the indexer's
top k, then decoding through the cache (the rings wrap, the index keys
are read where they lie) against the full forward; the selected set
against `top_k`'s, padding never in it; `t + 1 <= topk` is dense
attention; the kernels against their `jax.numpy` forms; the shares of an
expert layer add up; and what `models/family.py` says of a cache that is
latent AND ring."""
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

from benchmarks.harness import configs, reference  # noqa: E402
from ray_tpu.models import dots3_note as m  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.family import (family_of, refuse, slab_spec,  # noqa: E402
                                   stacks)
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.ops import dispatch, dsa, mla, swa  # noqa: E402

TOKENS = np.random.default_rng(3).integers(1, 500, 64).astype(np.int32)
PROMPT, TOTAL = 30, 50      # the window is 9, the top k 12, the ring 12


@functools.lru_cache(maxsize=None)
def _toy(dtype=jnp.float32, **changed):
    """Made once a module for each set of arguments (the init and its 200
    draws of noise are a quarter of a minute; nothing writes into what is
    handed back)."""
    conf = configs.load_config("dots3-note-l5-e32")
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


def test_the_program_is_the_reference_over_a_prompt_and_through_the_cache():
    conf, cfg, params = _toy()
    assert cfg.ring_rows == 12 and cfg.band_block == 8
    want = np.asarray(reference.logits(conf, params, TOKENS[:TOTAL]))
    tokens = jnp.asarray(TOKENS[:TOTAL])[None]
    got = jax.jit(lambda t: m.dots3_note_forward(params, t, cfg))(tokens)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)
    cache = m.dots3_note_init_cache(cfg, 1)
    logits, cache, counts = jax.jit(
        lambda t, c: m.dots3_note_forward_counted(params, t, cfg, c, 0))(
            tokens[:, :PROMPT], cache)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[PROMPT - 1],
                               atol=2e-4)
    assert int(counts["dsa_rows_visible"]) == PROMPT * (PROMPT + 1) // 2
    assert int(counts["dsa_rows_selected"]) == 12 * 13 // 2 + 18 * 12
    assert int(counts["ring_rows_read"]) == 9 * 10 // 2 + 21 * 9
    # 20 steps through a ring of 12: it wraps, twice nearly (one
    # compiled step, as the engine's tick is)
    step = jax.jit(lambda t, c, at: m.dots3_note_decode(params, t, cfg, c,
                                                        at))
    for pos in range(PROMPT, TOTAL):
        logits, cache, counts = step(tokens[:, pos], cache,
                                     jnp.asarray([pos]))
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=2e-4)
    assert int(counts["dsa_rows_selected"]) == 12
    assert int(counts["dsa_rows_visible"]) == TOTAL
    assert int(counts["dsa_rows_scored"]) == 64
    assert int(counts["ring_rows_read"]) == 9


@pytest.mark.parametrize("length", [45, 64])     # 64: eight blocks of
def test_the_kernels_are_their_plain_forms(length):     # keys, a packed mask
    _conf, cfg, params = _toy()
    tokens = jnp.asarray(TOKENS[:length])[None]
    # each form traced as ONE program under the mode the process then has
    plain = jax.jit(lambda t: m.dots3_note_forward(params, t, cfg))(tokens)
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        kernels = jax.jit(
            lambda t: m.dots3_note_forward(params, t, cfg))(tokens)
    took = {c["op"]: c["choice"] for c in dispatch.kernel_choices()}
    assert {took[op] for op in ("dsa_select", "mla_selected", "mla_band")
            } == {"pallas"}
    np.testing.assert_allclose(np.asarray(kernels), np.asarray(plain),
                               atol=2e-4)
    if length == 64:
        conf = _toy()[0]
        np.testing.assert_allclose(
            np.asarray(plain[0]),
            np.asarray(reference.logits(conf, params, TOKENS[:64])),
            atol=2e-4)


def test_a_packed_mask_holds_eight_blocks_of_keys_a_byte():
    rng = np.random.default_rng(2)
    mask = jnp.asarray(rng.integers(0, 2, (16, 64)), jnp.int8)
    packed = dsa.mask_tiles(mask, 8)
    assert packed.shape == (2, 1, 8, 8) and packed.dtype == jnp.int8
    assert dsa.mask_tiles(mask[:, :56], 8).shape == (2, 7, 8, 8)
    for q in range(2):
        for k in range(8):
            np.testing.assert_array_equal(
                np.asarray(dsa._kept(packed[q, 0], k)),
                np.asarray(mask[8 * q:8 * q + 8, 8 * k:8 * k + 8]) != 0)


@pytest.mark.parametrize("interpret", [False, True])
def test_the_selected_set_is_top_ks_and_holds_no_padding(interpret):
    rng = np.random.default_rng(0)
    tokens, tp, heads, dim, topk, block = 45, 64, 8, 16, 12, 16
    q = jnp.asarray(rng.normal(size=(tp, heads, dim)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(tp, dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(tp, heads)), jnp.float32)
    scores = np.einsum("thd,sd->ths", q, k)
    scores = (np.maximum(scores, 0) * np.asarray(w)[..., None]).sum(1)
    want = np.zeros((tp, tp), bool)
    for t in range(tp):
        best = np.argsort(-scores[t, :t + 1], kind="stable")[:topk]
        want[t, best] = True
    ctx = dispatch.pallas_interpret() if interpret else _nothing()
    with ctx:
        tiles, scored = dsa.selection_tiles(block, tp, tokens, heads, dim,
                                            topk)
        got = np.concatenate([np.asarray(dsa.block_selection(
            q[n:n + block], k, w[n:n + block], n, topk, tokens, tiles))
            for n in range(0, tp, block)])
    assert bool(tiles) == interpret
    # one tile of keys holds all 64 here: every pair is scored either way
    assert scored == tp * tp
    with (dispatch.pallas_interpret() if interpret else _nothing()):
        assert dsa.selection_tiles(256, 1024, 1000, heads, dim, topk)[1] == (
            256 * (512 + 512 + 1024 + 1024) if interpret else 1024 * 1024)
    np.testing.assert_array_equal(got != 0, want)
    assert not got[:tokens, tokens:].any()          # padding: never
    assert (got[:tokens].sum(1) == np.minimum(np.arange(tokens) + 1,
                                              topk)).all()
    # in a tick: the same rows, as indices
    at = np.asarray([44, 5, 20])
    rows, seen = dsa.tick_selection(
        q[at], jnp.broadcast_to(k, (3, tp, dim)), w[at], jnp.asarray(at),
        topk)
    for b, t in enumerate(at):
        kept = set(np.asarray(rows[b])[np.asarray(seen[b])].tolist())
        assert kept == set(np.flatnonzero(want[t]).tolist())


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_a_prompt_no_longer_than_top_k_is_dense_attention():
    conf, cfg, params = _toy(index_topk=64)
    tokens = jnp.asarray(TOKENS[:40])[None]
    got = np.asarray(jax.jit(
        lambda t: m.dots3_note_forward(params, t, cfg))(tokens)[0])
    dense = np.asarray(reference.logits(
        {**conf, "reference_selection": "dense"}, params, TOKENS[:40]))
    np.testing.assert_allclose(got, dense, atol=2e-4)
    # and the reference's selection is not dense at the toy's 12
    picked = np.asarray(reference.logits(conf, params, TOKENS[:40]))
    assert np.abs(picked - dense).max() > 1e-2
    first = np.asarray(reference.logits(
        {**conf, "reference_selection": "first"}, params, TOKENS[:40]))
    assert np.abs(picked - first).max() > 1e-2


@pytest.mark.parametrize("interpret", [False, True])
def test_the_band_is_masked_dense_attention(interpret):
    rng = np.random.default_rng(1)
    h, tp, d_n, d_r, d_v, window, block = 2, 32, 24, 8, 16, 9, 8
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q_n, q_r, k_n, k_r, v = (arr(h, tp, d_n), arr(h, tp, d_r),
                             arr(h, tp, d_n), arr(tp, d_r), arr(h, tp, d_v))
    s = (jnp.einsum("htd,hsd->hts", q_n, k_n)
         + jnp.einsum("htd,sd->hts", q_r, k_r)) * 0.2
    at = jnp.arange(tp)
    seen = (at[None] <= at[:, None]) & (at[:, None] - at[None] < window)
    want = jnp.einsum("hts,hsd->htd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)
    with (dispatch.pallas_interpret() if interpret else _nothing()):
        got = mla.band_prompt_attention(q_n, q_r, k_n, k_r, v, 0.2, window,
                                        block, tp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError, match="whole blocks of at least 8"):
        mla.band_prompt_attention(q_n, q_r, k_n, k_r, v, 0.2, window, 4, tp)


def test_a_ring_of_latent_rows_across_its_wrap():
    rows, window = 12, 9
    seen = np.asarray(mla.ring_visible(jnp.asarray([[3], [11], [12], [30]]),
                                       rows, window))[:, 0]
    for pos, got in zip((3, 11, 12, 30), seen):
        held = {p % rows for p in range(max(0, pos - window + 1), pos + 1)}
        assert set(np.flatnonzero(got).tolist()) == held
    x = jnp.arange(30)[None, :, None]
    ring = np.asarray(swa.ring_rows(x, rows))[0, :, 0]
    assert all(ring[p % rows] == p for p in range(18, 30))
    assert swa.ring_rows(x[:, :7], rows).shape[1] == 7


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    _conf, cfg, params = _toy()
    p = params["blocks"][1]["moe"]
    whole = dataclasses.replace(cfg, experts_held=16)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    w1 = 0.3 * jax.random.normal(keys[0], (16,) + p["w1"].shape[1:])
    w2 = 0.3 * jax.random.normal(keys[1], (16,) + p["w2"].shape[1:])
    h = jax.random.normal(keys[2], (128, cfg.d_model))  # over COMPACT_ABOVE
    valid = jnp.ones(128, bool)
    full, sizes = m.expert_layer(h, valid, dict(p, w1=w1, w2=w2), whole)
    shared = m._shared_mlp(h, p["s1"], p["s2"])
    total, rows = 0.0, 0
    for share in range(8):          # eight chips, two experts each
        cut = dataclasses.replace(cfg, experts_held=2,
                                  first_expert=2 * share)
        part, got = m.expert_layer(
            h, valid, dict(p, w1=w1[2 * share:2 * share + 2],
                           w2=w2[2 * share:2 * share + 2]), cut)
        total = total + (part - shared)
        rows += int(got.sum())
    # the shared expert counted once
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full),
                               atol=1e-4)
    assert rows == int(sizes.sum()) == 128 * cfg.num_experts_per_tok


def test_compacted_pairs_are_the_whole_buffers_numbers(monkeypatch):
    """A block's held pairs go through a buffer of twice an even router's
    share; a router that sends more takes the whole buffer."""
    _conf, cfg, params = _toy()
    p = params["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(4), (128, cfg.d_model))
    valid = jnp.arange(128) < 120
    skewed = dict(p, router_bias=p["router_bias"].at[:4].set(10.0))
    got = [m.expert_layer(h, valid, q, cfg) for q in (p, skewed)]
    monkeypatch.setattr(m, "COMPACT_ABOVE", 10 ** 9)    # never compact
    want = [m.expert_layer(h, valid, q, cfg) for q in (p, skewed)]
    for (a, rows), (b, rows_b) in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_b))
    # an even router: 90 of 360 pairs fall on the 4 held of 16 (cap 192);
    # the skewed one sends all 360 here
    assert int(got[0][1].sum()) < 192 < int(got[1][1].sum()) == 360


def test_a_cache_that_is_latent_and_ring():
    cfg = m.Dots3NoteConfig.tiny()
    assert family_of(cfg) is m.FAMILY
    spec = slab_spec(cfg, 3)
    assert spec.kind == "latent_ring" and spec.latent_only
    assert spec.ring_rows == 12 and not spec.stateful and not spec.paired
    # entries of three widths under two row counts, a stack each
    cache = m.dots3_note_init_cache(cfg, 3)
    assert list(stacks(cache)) == [(128, 128), (128, 16), (12, 128)]
    assert list(spec.stacks.values()) == [[0, 2], [1, 3], [4, 5, 6]]
    assert spec.by_rows == {128: [0, 1, 2, 3], 12: [4, 5, 6]}
    assert [e["layers"] for e in spec.slab] == [4, 3]
    assert m.entries_of(cfg) == ((0, 1), (2, 3), (4,), (5,), (6,))
    for capability, words in (
            ("prefix_cache", "no block of one latent row"),
            ("speculate_k", "overwritten rows the window still sees"),
            ("lora_pool", "per-tenant prefix namespaces"),
            ("adopt_prefill", "entries of several widths"),
            ("transfer", "ONE stack of ck and cv rows in pairs")):
        with pytest.raises(ValueError, match=words) as err:
            refuse(spec, capability, k=2)
        assert "one latent row a token" in str(err.value)
        assert "in rings" in str(err.value)
    refuse(spec, "prefix_cache", None)      # left to its default: nothing
    params = m.dots3_note_init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="some of them in rings"):
        ContinuousBatchingEngine(params, cfg, max_batch=2, prefix_cache=True)


def test_the_engine_serves_it_as_generate_does():
    cfg = m.Dots3NoteConfig.tiny()
    params = m.dots3_note_init(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(params, cfg, max_batch=3)
    try:
        stats = engine.kv_stats()
        assert stats["ring_rows"] == 12 and stats["latent_only"]
        assert [(e["rows"], e["layers"]) for e in stats["slab"]] \
            == [(128, 4), (12, 3)]
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
        assert met["ring_rows_read"] and met["moe_pairs_held"]
    finally:
        engine.stop()
