"""`models/zaya.py` (ZAYA1): the program against the plain reference of
`benchmarks/references/zaya.py` at the family's toy size, two layers so
that the router's state carries: a prefill split over two blocks, then a
decode of a few tokens through the cache and the carried tails; the
seventeenth choice; each assumed term shown to matter; what the cache's
kind is refused, in `family.refuse`'s words; the engine."""
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
from ray_tpu.models import ContinuousBatchingEngine, zaya  # noqa: E402
from ray_tpu.models.family import family_of, refuse, slab_spec  # noqa: E402
from ray_tpu.models.zaya import ZayaConfig  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.ops import grouped_moe  # noqa: E402

F32 = jnp.float32
TOKENS = np.random.default_rng(0).integers(1, 500, 40).astype(np.int32)
TERMS = ("conv_bias", "qk_mean", "tau", "value_shift", "residual_scale",
         "gamma", "router_bias", "skip")


@pytest.fixture(scope="module")
def toy():
    """The family at `TOY` in float32 with the seeded init AS IT IS: the
    init itself draws every assumed term away from its neutral value."""
    conf = configs.load_config("zaya1-8b-l16")
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 48), dtype=F32)
    return conf, cfg, configs.init_params(conf, cfg, 7)


@pytest.fixture(scope="module")
def want(toy):
    conf, _cfg, params = toy
    return np.asarray(reference.logits(conf, params, TOKENS))


def test_two_blocks_of_prefill_then_a_decode_agree_with_the_reference(
        toy, want):
    conf, cfg, params = toy
    assert cfg.num_layers == 2 and cfg.ffn_block == 8
    step = jax.jit(lambda t, c, pos: zaya.zaya_forward_cached(
        params, t, cfg, c, pos))
    cache = zaya.zaya_init_cache(cfg, 1)
    # from a concrete 0: the prompt form; then a block over the cache
    logits, cache = zaya.zaya_forward_cached(params, TOKENS[None, :20],
                                             cfg, cache, 0)
    rows = {19: logits[0, -1]}
    logits, cache = step(TOKENS[None, 20:33], cache, jnp.int32(20))
    rows[32] = logits[0, -1]
    for pos in range(33, 39):
        logits, cache = step(TOKENS[None, pos:pos + 1], cache,
                             jnp.int32(pos))
        rows[pos] = logits[0, -1]
    for pos, got in rows.items():
        np.testing.assert_allclose(got, want[pos], atol=2e-4, rtol=0,
                                   err_msg=str(pos))
    # and the whole forward pass, no cache
    got = zaya.zaya_forward(params, TOKENS[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("term", TERMS)
def test_each_assumed_term_matters(toy, want, term):
    """The reference with ONE assumed term at its neutral value is
    another function: a program that left the term out would be told."""
    conf, _cfg, params = toy
    other = np.asarray(reference.logits(
        {**conf, "reference_without": [term]}, params, TOKENS))
    assert np.abs(other - want).max() > 50 * 2e-4, term


def test_the_init_draws_every_assumed_term_off_its_neutral_value(toy):
    _conf, _cfg, params = toy
    for b in params["blocks"]:
        for res in (b["res1"], b["res2"]):
            assert float(jnp.abs(res["stream_scale"] - 1).max()) > 0.05
            assert float(jnp.abs(res["out_scale"] - 1).max()) > 0.05
            assert float(jnp.abs(res["stream_bias"]).max()) > 0.01
            assert float(jnp.abs(res["out_bias"]).max()) > 0.01
        assert 0.3 <= float(b["router"]["gamma"]) <= 0.7
        assert float(jnp.abs(b["router"]["bias"]).max()) > 0.001
        assert float(jnp.abs(b["attn"]["tau"] - 1).min()) > 0.05
        assert float(b["router"]["bias"][-1]) < -0.01   # the skip, rare
        assert float(jnp.abs(b["attn"]["conv0_b"]).max()) > 0.05
        assert float(jnp.abs(b["attn"]["conv1_b"]).max()) > 0.05
        assert b["router"]["w_down"].dtype == F32       # float32 router
        # experts alike in most and different in part
        w1 = b["moe"]["w1"].astype(F32)
        apart = jnp.sqrt(jnp.mean((w1[0] - w1[1]) ** 2) / jnp.mean(w1 ** 2))
        assert 0.1 < float(apart) < 0.2        # sqrt(2) x EXPERT_OWN
        # the router's last two matrices: centred columns at a fixed norm
        for name, norm in (("w2", 1.0), ("w3", 2.0)):
            w = b["router"][name]
            np.testing.assert_allclose(w.sum(0), 0.0, atol=1e-5)
            np.testing.assert_allclose(jnp.linalg.norm(w, axis=0), norm,
                                       rtol=1e-5)


def test_the_last_choice_is_no_expert_and_its_output_is_zero(toy,
                                                             monkeypatch):
    _conf, cfg, params = toy
    cfg = dataclasses.replace(cfg, ffn_block=64)    # one block: eager
    seen = []
    real = grouped_moe.held_experts

    def spy(u, chosen, weights, *rest):
        out, counts = real(u, chosen, weights, *rest)
        seen.append((np.asarray(chosen), np.asarray(out)))
        return out, counts

    monkeypatch.setattr(zaya, "held_experts", spy)
    _x, _c, counts = zaya._walk(params, TOKENS[None, :32], cfg, None, 0)
    skipped = 0
    for chosen, out in seen:       # a layer's 32 tokens
        none = chosen[:, 0] == cfg.num_experts
        skipped += int(none.sum())
        assert (out[none] == 0.0).all()                  # exactly
        assert np.abs(out[~none]).min(axis=-1).max() > 0.0
        assert chosen.max() <= cfg.num_experts and chosen.min() >= 0
    assert len(seen) == 2 and skipped > 0
    assert int(counts["moe_pairs_skipped"]) == skipped
    # every expert is held: a pair is held or skipped
    assert int(counts["moe_pairs_held"]) + skipped == 2 * 32
    assert int(counts["moe_experts_hit"]) <= 2 * cfg.num_experts


def test_a_ragged_tick_is_each_slot_alone(toy, want):
    """decode() for two slots at their own positions against each slot's
    own forward pass (the reference's: both hold prefixes of one
    sequence), and the counters the loop ring takes."""
    _conf, cfg, params = toy
    cache = zaya.zaya_init_cache(cfg, 2)
    ends = (11, 17)
    prefill = jax.jit(lambda t: zaya.zaya_forward_cached(
        params, t, cfg, zaya.zaya_init_cache(cfg, 1), 0))
    for slot, upto in enumerate(ends):
        _lg, one = prefill(TOKENS[None, :upto])
        cache = [jax.tree.map(lambda s, o: s.at[slot].set(o[0]), blk, new)
                 for blk, new in zip(cache, one)]
    logits, cache, counts = jax.jit(
        lambda t, c, pos: zaya.zaya_decode(params, t, cfg, c, pos))(
            jnp.asarray([TOKENS[11], TOKENS[17]]), cache,
            jnp.asarray(ends, jnp.int32))
    for slot, upto in enumerate(ends):
        np.testing.assert_allclose(logits[slot], want[upto], atol=2e-4)
    assert sorted(counts) == ["moe_experts_hit", "moe_pairs_held",
                              "moe_pairs_skipped", "moe_rows_max"]
    assert int(counts["moe_pairs_held"]) \
        + int(counts["moe_pairs_skipped"]) == 2 * cfg.num_layers
    with pytest.raises(ValueError, match="cannot verify drafted"):
        zaya.zaya_decode(params, jnp.zeros((2, 3), jnp.int32), cfg, cache,
                         jnp.asarray(ends, jnp.int32))


def test_the_cache_is_pairs_first_and_a_state_of_kilobytes_after():
    cfg = ZayaConfig()
    spec = slab_spec(cfg, 64)
    assert spec.kind == "state" and spec.paired and spec.stateful
    assert spec.ring_rows is None and len(spec.stacks) == 1
    assert spec.slab == [{"rows": 2816, "layers": 16,
                          "bytes_per_slot": 16 * 2816 * 1024}]
    assert spec.kv_bytes_per_token == 16 * 1024
    assert spec.state_bytes_per_slot == 16 * 5376
    assert spec.stack_shape(5) == (32, 5, 2, 128)
    cache = jax.eval_shape(lambda: zaya.zaya_init_cache(cfg, 64))
    assert all(set(blk) == {"k", "v"} for blk in cache[:16])
    assert all(set(blk) == {"conv0", "conv1", "v2"} for blk in cache[16:])
    rec = family_of(cfg)
    assert rec.decode_walks and not rec.state_walks
    assert rec.forward_counted is zaya.zaya_forward_counted


REFUSALS = {
    "prefix_cache": "cannot resume a recurrence without a snapshot",
    "speculate_k": "cannot be un-advanced",
    "adopt_prefill": "no way to hand over the state",
    "lora_pool": "adapter pool",
    "transfer": "carries ck/cv rows only",
}


@pytest.mark.parametrize("capability", sorted(REFUSALS))
def test_what_the_caches_kind_is_refused(capability):
    cfg = ZayaConfig.tiny()
    with pytest.raises(ValueError, match=REFUSALS[capability]) as e:
        refuse(slab_spec(cfg, 2), capability, True, k=2)
    assert str(e.value).startswith("this family's slots own recurrent "
                                   "state")
    # an option left to its default asks for nothing
    refuse(slab_spec(cfg, 2), capability, None)


def test_the_engine_refuses_the_prefix_pool_and_speculation():
    cfg = ZayaConfig.tiny()
    for kw, words in (({"prefix_cache": True}, "prefix_cache=True"),
                      ({"speculate_k": 2}, "speculate_k=2")):
        with pytest.raises(ValueError, match=words):
            ContinuousBatchingEngine(None, cfg, max_batch=2, **kw)


def test_the_engine_serves_it_and_its_ring_holds_the_counters():
    cfg = dataclasses.replace(ZayaConfig.tiny(), dtype=F32)
    params = zaya.zaya_init(cfg, jax.random.PRNGKey(0))
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(params, cfg, max_batch=4)
    try:
        prompt = [int(t) for t in TOKENS[:13]]
        emitted = [int(t) for t in engine.stream(prompt, 6, timeout_s=120)]
        stats = engine.kv_stats()
    finally:
        engine.stop()
    toks = list(prompt)
    fwd = jax.jit(lambda t: zaya.zaya_forward(params, t, cfg)[0, -1])
    for _ in range(6):
        toks.append(int(jnp.argmax(fwd(jnp.asarray(toks)[None]))))
    assert emitted == toks[13:]
    assert stats["state_bytes_per_slot"] == 2 * (2 * 96 + 16) * 4
    assert stats["slab"] == [{"rows": 128, "layers": 2,
                              "bytes_per_slot": 2 * 128 * 2 * 2 * 16 * 4}]
    # the tick's walk (the registry is the process's: other shapes too)
    assert (4, 1, 4, 2, 16, 128) in [w["shape"]
                                     for w in stats["gqa_decode"]]
    records = reqtrace.store().loop_records()
    (admission,) = [a for r in records for a in r["admissions"]]
    assert admission["prompt_tokens"] == 13
    assert admission["moe_pairs_held"] \
        + admission["moe_pairs_skipped"] == 2 * 13
    assert admission["moe_rows_max"] >= 1
    assert admission["state_bytes"] == stats["state_bytes_per_slot"]
    ticks = [r for r in records if "moe_pairs_skipped" in r]
    assert ticks and all(
        r["moe_pairs_held"] + r["moe_pairs_skipped"] == 4 * 2
        and "moe_experts_hit" in r and "live_rows" in r
        and r["state_slots_stepped"] == 4 for r in ticks)
    reqtrace._reset_store_for_tests()


def test_the_partition_specs_follow_the_parameters():
    cfg = ZayaConfig.tiny()
    params = jax.eval_shape(lambda: zaya.zaya_init(cfg,
                                                   jax.random.PRNGKey(0)))
    specs = zaya.zaya_partition_specs(cfg)
    leaf = lambda x: not isinstance(x, (dict, list))
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=leaf)
    assert specs["blocks"][0]["moe"]["w1"][0] == "ep"


@pytest.mark.parametrize("change,words", [
    ({"num_heads": 3}, "multiple of num_kv_heads"),
    ({"rotary_dim": 7}, "even share"),
    ({"rotary_dim": 32}, "even share"),
    ({"cca_time0": 1}, "carries no tail"),
])
def test_the_config_refuses_what_it_cannot_be(change, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(ZayaConfig.tiny(), **change)
