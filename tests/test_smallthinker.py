"""`models/smallthinker.py` and the engine's fourth kind of slab entry:
the program against its plain reference at the family's `TOY` on seeded
weights (full forward, loss, prefill then decode through a ring that
wraps twice, the same through `ContinuousBatchingEngine` with two slots
at different depths), the ring's prefill and splice for a prompt shorter
than, as long as and longer than the ring, the slab's bytes at the
published widths, each refusal's words, and the families that were
there: one stack, the splice they had."""
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

from benchmarks.harness import configs, reference  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models import llama, smallthinker  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.family import slab_spec  # noqa: E402
from ray_tpu.models.generate import (_model_fns, generate,  # noqa: E402
                                     stream_generate)
from ray_tpu.models.smallthinker import SmallThinkerConfig  # noqa: E402

CONFIG = "smallthinker-21b-l8"
# float32 on both sides, different summation orders: a few 1e-6
TOL = 2e-4
TOKENS = np.random.default_rng(5).integers(1, 500, 60).astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    """The family at its `TOY` (a window of 4 in layers 1 to 3 of 5) in
    float32, every leaf moved off its initial value."""
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64),
                              dtype=jnp.float32)
    params = configs.init_params(conf, cfg, 7)
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 200))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                               x.dtype), params)
    return conf, cfg, params


def test_forward_and_loss_agree_with_the_reference(toy):
    conf, cfg, params = toy
    assert cfg.window == 4 and cfg.window_layout == (0, 1, 1, 1, 0)
    got = smallthinker.smallthinker_forward(params, TOKENS[None, :40], cfg)
    want = reference.logits(conf, params, TOKENS[:40])
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=0)
    toks, tgts = TOKENS[None, :39], TOKENS[None, 1:40]
    assert float(smallthinker.smallthinker_loss(params, toks, tgts, cfg)) \
        == pytest.approx(reference.mean_loss(conf, params, toks, tgts),
                         abs=TOL)


def test_prefill_then_decode_through_a_ring_that_wraps_twice(toy):
    """11 tokens prefilled (longer than the window of 4 and not a whole
    number of the prompt form's blocks of 4), 12 decoded one at a time:
    the rings of 4 rows wrap three times."""
    conf, cfg, params = toy
    step, init_cache, _ = _model_fns(cfg)
    cache = init_cache(cfg, 1)
    assert [blk["k"].shape[1] for blk in cache] == [64, 4, 4, 4, 64]
    logits, cache = step(params, TOKENS[None, :11], cfg, cache, 0)
    rows = [logits[0, -1]]
    # one compiled step for the twelve, as the engine's tick is
    one = jax.jit(lambda t, c, pos: step(params, t, cfg, c, pos))
    for pos in range(11, 23):
        logits, cache = one(TOKENS[None, pos:pos + 1], cache,
                            jnp.int32(pos))
        rows.append(logits[0, -1])
    want = reference.logits(conf, params, TOKENS[:23])[10:]
    np.testing.assert_allclose(jnp.stack(rows), want, atol=TOL, rtol=0)


def test_the_engine_with_two_slots_at_different_depths(toy):
    """One request decodes while the other is admitted; each emitted
    token's log-probability against the reference's full forward pass,
    and the reference would have chosen the same token."""
    conf, cfg, params = toy
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        first = eng.stream(TOKENS[:19], 16)
        it = iter(first)
        head = [next(it) for _ in range(4)]
        second = eng.stream(TOKENS[30:37], 12)
        out2 = [int(t) for t in second]
        out1 = head + [int(t) for t in it]
        stats = eng.kv_stats()
    finally:
        eng.stop()
    for prompt, out, stream in ((TOKENS[:19], out1, first),
                                (TOKENS[30:37], out2, second)):
        ref = reference.score_emitted(conf, params, list(prompt), out)
        for score, r in zip(stream.scores, ref):
            assert abs(score - r["logprob"]) < TOL and r["margin"] < TOL
    assert stats["ring_rows"] == 4 and stats["enabled"] is False
    assert stats["slab"] == [
        {"rows": 64, "layers": 2, "bytes_per_slot": 2 * 2 * 64 * 2 * 16 * 4},
        {"rows": 4, "layers": 3, "bytes_per_slot": 3 * 2 * 4 * 2 * 16 * 4}]
    # 19 tokens in blocks of 4: five blocks; a window layer's walk visits
    # 1 + 2 + 2 + 2 + 2, a global layer's 1 + 2 + 3 + 4 + 5; 7 tokens: two
    assert stats["prefill_counters"]["attn_blocks"] \
        == (3 * 9 + 2 * 15) + (3 * 3 + 2 * 3)
    assert stats["prefill_counters"]["attn_blocks_causal"] == 5 * 15 + 5 * 3


def test_generate_and_stream_generate_take_the_family(toy):
    _conf, cfg, params = toy
    prompt = jnp.asarray(TOKENS[None, :9])
    out = np.asarray(generate(params, cfg, prompt, max_new_tokens=10))
    streamed = [int(t[0]) for t in stream_generate(
        params, cfg, prompt, max_new_tokens=10)]
    assert out.shape == (1, 10) and list(out[0]) == streamed
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        assert eng.generate(TOKENS[:9], 10) == streamed
    finally:
        eng.stop()


@pytest.mark.parametrize("plen", [5, 8, 13])
def test_the_rings_prefill_and_splice(plen):
    """A prompt shorter than, as long as and longer than the ring of 8:
    the prefill hands back one stack a row count, the ring's as it lies
    after the prompt, and the splice writes rows [0, min(plen, rows))."""
    cfg = dataclasses.replace(SmallThinkerConfig.tiny(), dtype=jnp.float32,
                              max_seq_len=32)
    params = smallthinker.smallthinker_init(cfg, jax.random.PRNGKey(1))
    empty = jnp.zeros((cfg.num_layers, 0, 2, 16), jnp.float32)
    _lg, ck, cv, state, counts = engine_mod._prefill_paged(
        params, jnp.asarray(TOKENS[None, :plen]), cfg, empty, empty)
    assert isinstance(ck, tuple) and [x.shape for x in ck] == [
        (2, 32, 2, 16), (3, 8, 2, 16)] and state == []
    assert int(counts["attn_blocks"]) <= int(counts["attn_blocks_causal"])
    # every position's key, from a window long enough to keep them all
    full = dataclasses.replace(cfg, window=32)
    _lg, every, _cv, _s, _c = engine_mod._prefill_paged(
        params, jnp.asarray(TOKENS[None, :plen]), full, empty, empty)
    # (layer 1's keys do not depend on the window: layer 0 is global)
    for p in range(max(0, plen - 8), plen):
        np.testing.assert_array_equal(ck[1][0, p % 8], every[1, p])
    slab = _model_fns(cfg)[1](cfg, 3)
    slab = jax.tree.map(lambda x: x + 7.0, slab)
    out = engine_mod._splice_slot(slab, ck, cv, np.int32(1), cfg, plen)
    n = min(plen, 8)
    np.testing.assert_array_equal(out[1]["k"][1, :n], ck[1][0, :n])
    np.testing.assert_array_equal(out[3]["v"][1, :n], cv[1][2, :n])
    np.testing.assert_array_equal(out[4]["k"][1, :plen], ck[0][1, :plen])
    assert float(out[1]["k"][1, n:].min(initial=7.0)) == 7.0
    assert float(out[1]["k"][0].min()) == 7.0 == float(out[4]["v"][2].min())


def test_a_slot_at_the_published_widths_costs_176_megabytes():
    """3 x 16,384 x 2 KB + 9 x 4,096 x 2 KB at twelve layers (three
    periods), 2 x 16,384 x 2 KB + 6 x 4,096 x 2 KB at the eight the cell
    runs: by shapes alone, nothing allocated."""
    conf = configs.load_config(CONFIG)
    for layers, want in ((12, 176_160_768), (8, 117_440_512)):
        c = dict(conf, num_hidden_layers=layers,
                 rope_layout=[0, 1, 1, 1] * (layers // 4),
                 sliding_window_layout=[0, 1, 1, 1] * (layers // 4))
        cfg = configs.program_config(c, 16384)
        cache = jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, 16))
        assert sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(cache)) == 16 * want
        assert slab_spec(cfg, 16).ring_rows == 4096
        one = 2048 * (layers // 4) * (16384 + 3 * 4096)
        assert want == one
    # a window no shorter than the cell's positions is no ring
    cfg = configs.program_config(conf, 4096)
    assert slab_spec(cfg, 1).ring_rows is None


def test_what_stands_on_the_pool_is_refused_in_words(toy):
    _conf, cfg, params = toy
    for kwargs, words in (
            (dict(prefix_cache=True), "one block shape and one length"),
            (dict(speculate_k=2), "overwritten rows the window still sees"),
            (dict(lora_pool=object()), "prefix namespaces")):
        with pytest.raises(ValueError, match="holds a ring") as e:
            ContinuousBatchingEngine(params, cfg, max_batch=2, **kwargs)
        assert words in str(e.value)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        assert eng.kv_cache is None and eng.ring_rows == 4
        with pytest.raises(ValueError, match="holds a ring") as e:
            eng.adopt_prefill(4, 1, None, None, 4)
        assert "a stack of their own" in str(e.value)
    finally:
        eng.stop()
    from ray_tpu.serve.disagg import PrefillServer

    with pytest.raises(ValueError, match="holds a ring") as e:
        PrefillServer(params, cfg)
    assert "cannot be served disaggregated" in str(e.value)


def test_the_families_that_were_there_get_the_stack_and_splice_they_had():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32,
                              max_seq_len=32)
    params = llama.llama_init(cfg, jax.random.PRNGKey(2))
    empty = jnp.zeros((cfg.num_layers, 0, 2, 32), jnp.float32)
    prompt = jnp.asarray(TOKENS[None, :9])
    _lg, ck, cv, state, counts = engine_mod._prefill_paged(
        params, prompt, cfg, empty, empty)
    assert ck.shape == cv.shape == (2, 32, 2, 32) and state == []
    assert counts is None
    # the stack is bit for bit what the prefill's body built before it
    # knew of row counts (ONE stack `[L, max_seq_len, ...]` laid under
    # the family's own cache), and the splice writes rows [0, plen) of it
    # and nothing else

    @jax.jit
    def as_it_was(params, suffix):
        base = jnp.zeros((cfg.num_layers, cfg.max_seq_len, 2, 32),
                         jnp.float32)
        cache = [{"k": base[j][None], "v": base[j][None]}
                 for j in range(cfg.num_layers)]
        _logits, cache = llama.llama_forward_cached(params, suffix, cfg,
                                                    cache, 0)
        return (jnp.stack([blk["k"][0] for blk in cache]),
                jnp.stack([blk["v"][0] for blk in cache]))

    was_k, was_v = as_it_was(params, prompt)
    np.testing.assert_array_equal(ck, was_k)
    np.testing.assert_array_equal(cv, was_v)
    slab = jax.tree.map(lambda x: x + 3.0, llama.init_kv_cache(cfg, 3))
    out = engine_mod._splice_slot(slab, ck, cv, np.int32(2), cfg, 9)
    for i in range(cfg.num_layers):
        np.testing.assert_array_equal(out[i]["k"][2, :9], ck[i, :9])
        np.testing.assert_array_equal(out[i]["v"][2, :9], cv[i, :9])
        assert float(out[i]["k"][2, 9:].min()) == 3.0 \
            == float(out[i]["v"][:2].min())
    assert slab_spec(cfg, 3).ring_rows is None
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        stats = eng.kv_stats()
        assert stats["ring_rows"] is None and stats["slab"] == [
            {"rows": 32, "layers": 2, "bytes_per_slot": 2 * 2 * 32 * 64 * 4}]
        out = eng.generate(TOKENS[:9], 6)
    finally:
        eng.stop()
    want = generate(params, cfg, prompt, max_new_tokens=6)
    assert out == [int(t) for t in want[0]]
