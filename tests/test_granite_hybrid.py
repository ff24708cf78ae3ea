"""`models/granite_hybrid.py` (Granite 4.0-H): the program against the
plain reference of `benchmarks/references/granite_hybrid.py` at the
family's toy size: the whole forward pass; a prefill of two token blocks
(the second ragged, its last chunk ragged) and eight ticks through a slab
with dead slots beside the live one; the two shares of the expert layer
against the uncut layer; each multiplier shown to matter; the scan and
the step kernel at ONE group; the engine."""
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
from benchmarks.harness.traffic import load_module  # noqa: E402
from ray_tpu.models import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models import granite_hybrid as gh  # noqa: E402
from ray_tpu.models.family import family_of, slab_spec  # noqa: E402
from ray_tpu.models.granite_hybrid import GraniteHybridConfig  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.ops import dispatch, mamba2  # noqa: E402

F32 = jnp.float32
TOKENS = np.random.default_rng(0).integers(1, 500, 24).astype(np.int32)
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
TOL = 2e-4


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, F32)


@pytest.fixture(scope="module")
def toy():
    """The family at `TOY` in float32 with the seeded init, the query and
    key projections eight times it: at 64 channels the scores of the
    init as it is are all alike, and their scale would show nothing."""
    conf = configs.load_config("granite-4.0-h-small-l10-e36")
    conf = {**conf, **configs.family(conf).toy, "prefill_token_block": 8}
    cfg = dataclasses.replace(configs.program_config(conf, 48), dtype=F32)
    params = configs.init_params(conf, cfg, 7)
    attn = params["blocks"][cfg.pattern.index("*")]["attn"]
    attn.update(wq=8 * attn["wq"], wk=8 * attn["wk"])
    return conf, cfg, params


@pytest.fixture(scope="module")
def want(toy):
    conf, _cfg, params = toy
    return np.asarray(reference.logits(conf, params, TOKENS))


# ----------------------------------------------- program and reference

def test_the_whole_forward_pass_is_the_reference(toy, want):
    _conf, cfg, params = toy
    assert cfg.pattern == "MM*M" and cfg.mamba_n_groups == 1
    got = jax.jit(lambda t: gh.granite_hybrid_forward(params, t, cfg))(
        TOKENS[None])[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # the init gives the tied head's logits the spread an untied 0.02
    # head would have (sqrt(D) x 0.02), and no token predicts itself
    assert 0.5 < float(want.std()) / (0.02 * cfg.d_model ** 0.5) < 2.0
    own = want[np.arange(len(TOKENS)), TOKENS]
    assert float(np.abs(own).max()) < 4 * float(want.std())


def test_two_blocks_then_eight_ticks_through_the_slab_are_one_pass(
        toy, want):
    """15 tokens in blocks of 8: the second block is ragged (7) and its
    last chunk of 4 is ragged (3); the state, the tail and the rows land
    in slot 1 of a slab of three, whose other slots stand dead."""
    _conf, cfg, params = toy
    assert (cfg.prefill_token_block, cfg.mamba_chunk_size) == (8, 4)
    logits, one, counts = jax.jit(
        lambda t, c: gh.granite_hybrid_forward_counted(params, t, cfg, c,
                                                       0))(
        TOKENS[None, :15], gh.granite_hybrid_init_cache(cfg, 1))
    np.testing.assert_allclose(logits[0, -1], want[14], atol=TOL, rtol=0)
    held = int(counts["moe_pairs_held"])
    assert 0 < held < 15 * cfg.num_experts_per_tok * cfg.num_layers
    assert int(counts["moe_experts_hit"]) <= 4 * cfg.num_layers
    slab = jax.tree.map(lambda s, o: s.at[1].set(o[0]),
                        gh.granite_hybrid_init_cache(cfg, 3), one)
    tick = jax.jit(lambda t, c, p, live: gh.granite_hybrid_decode(
        params, t, cfg, c, p, live))
    live = jnp.asarray([0, 1, 0], jnp.int32)
    for pos in range(15, 23):
        tokens = jnp.asarray([3, TOKENS[pos], 5], jnp.int32)
        logits, slab, counts = tick(tokens, slab,
                                    jnp.asarray([0, pos, 0], jnp.int32),
                                    live)
        np.testing.assert_allclose(logits[1], want[pos], atol=TOL, rtol=0,
                                   err_msg=str(pos))
    assert set(counts) == {"moe_pairs_held", "moe_rows_max",
                           "moe_experts_hit"}


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_and_the_folded_score_scale_move_the_logits(
        toy, want, name, side):
    """One multiplier at its neutral value (1; `head_dim ** -0.5` for the
    scores, which undoes the fold into the queries) is another function:
    the comparison that passes above fails, whichever side leaves it
    out."""
    conf, cfg, params = toy
    if side == "program":
        neutral = cfg.head_dim ** -0.5 if name == "attention_multiplier" \
            else 1.0
        off = dataclasses.replace(cfg, **{name: neutral})
        other = jax.jit(lambda t: gh.granite_hybrid_forward(params, t, off))(
            TOKENS[None])[0]
    else:
        other = reference.logits({**conf, "reference_without": [name]},
                                 params, TOKENS)
    assert np.abs(np.asarray(other) - want).max() > 25 * TOL, name


def test_the_two_shares_and_the_shared_mlp_once_make_the_uncut_layer(toy):
    """8 experts over 2 shares of 4: the routed parts the shares give,
    plus what both compute alike (the shared MLP) counted ONCE, add up to
    the uncut reference's whole layer."""
    ref = load_module("references", "granite_hybrid")
    _conf, cfg, _params = toy
    whole = dataclasses.replace(cfg, experts_held=8)
    p = gh.granite_hybrid_init(whole, jax.random.PRNGKey(1))["blocks"][0]
    x = _rand(8, 1, 10, whole.d_model)

    def share(rank, w2_scale=1.0):
        c = dataclasses.replace(cfg, experts_held=4, first_expert=4 * rank)
        moe = {"w1": p["moe"]["w1"][4 * rank:4 * rank + 4],
               "w2": w2_scale * p["moe"]["w2"][4 * rank:4 * rank + 4]}
        out, sizes = jax.jit(lambda x, p: gh._ffn(x, p, c))(
            x, dict(p, moe=moe))
        return out - x, sizes

    shared_alone, _ = share(0, w2_scale=0.0)
    got, pairs = shared_alone, 0
    for rank in range(2):
        out, sizes = share(rank)
        got = got + (out - shared_alone)
        pairs += int(sizes.sum())
    assert pairs == 10 * whole.num_experts_per_tok   # no pair dropped

    layer = ref.weights({"tok_emb": None, "norm_f": {"scale": None},
                         "blocks": [p]})["layers"][0]
    with jax.default_matmul_precision("highest"):
        h, per_expert = ref._route(x[0], layer, whole.num_experts_per_tok,
                                   whole.norm_eps)
        routed = ref._experts(h, layer["experts_input_linear"],
                              layer["experts_output_linear"], per_expert)
        want = ref._ffn_close(jnp.zeros_like(x[0]), h, routed, layer,
                              whole.residual_multiplier)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-4)


# ------------------------------------------------- the ops at ONE group

@pytest.mark.parametrize("t,chunk", [(300, 256), (11, 4)])
def test_the_scan_at_one_group_equals_the_recurrence(t, chunk):
    """B and C shared by every head (G = 1), the published chunk of 256
    with a ragged second chunk, from a state that is not zero."""
    b, h, p, n = 1, 4, 8, 16
    x, dt = _rand(0, b, t, h, p), jax.nn.softplus(_rand(1, b, t, h))
    a = -jnp.exp(_rand(2, h))
    bm, cm, d = _rand(3, b, t, 1, n), _rand(4, b, t, 1, n), _rand(5, h)
    s0 = _rand(6, b, h, p, n)
    y, s = jax.jit(mamba2.ssd_scan, static_argnums=7)(
        x, dt, a, bm, cm, d, s0, chunk)

    def step(state, inp):
        yi, state = mamba2._step_all(*inp[:2], a, *inp[2:], d, state)
        return state, yi

    state, want = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    # float32 sums over 256 steps in another order
    np.testing.assert_allclose(y, jnp.moveaxis(want, 0, 1), atol=5e-4,
                               rtol=5e-4)
    np.testing.assert_allclose(s, state, atol=5e-4, rtol=5e-4)


def test_the_step_kernel_at_one_group_leaves_dead_slots_alone():
    """`ssd_step_live` in interpret mode with rep = heads (one block of B
    and C a slot) against the plain step over every row."""
    b, h, p, n = 4, 8, 16, 16
    x, dt = _rand(0, b, h, p), jax.nn.softplus(_rand(1, b, h))
    a = -jnp.exp(_rand(2, h))
    bm, cm, d = _rand(3, b, 1, n), _rand(4, b, 1, n), _rand(5, h)
    s0 = _rand(6, b, h, p, n)
    live = jnp.asarray([1, 0, 0, 1], jnp.int32)
    want_y, want_s = mamba2._step_all(x, dt, a, bm, cm, d, s0)
    with dispatch.pallas_interpret():
        dispatch.reset_kernel_choices()
        y, s = mamba2.ssd_step(x, dt, a, bm, cm, d, s0, live)
        (choice,) = [c for c in dispatch.kernel_choices("state_step")
                     if c["shape"] == (b, h, p, 1, n)]
    assert choice["choice"] == "pallas" and choice["heads_block"] == h
    for slot in (0, 3):
        np.testing.assert_allclose(y[slot], want_y[slot], atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(s[slot], want_s[slot], atol=1e-5,
                                   rtol=1e-5)
    np.testing.assert_array_equal(s[1:3], s0[1:3])     # not touched


# ----------------------------------------------------------- the engine

def test_the_cache_is_the_rows_first_and_the_states_after():
    cfg = GraniteHybridConfig.tiny()
    cache = jax.eval_shape(lambda: gh.granite_hybrid_init_cache(cfg, 2))
    assert [sorted(e) for e in cache] == [["k", "v"]] + 3 * [
        ["conv", "ssm"]]
    assert cache[1]["ssm"].dtype == F32
    spec = slab_spec(cfg, 2)
    assert spec.kind == "state" and spec.paired
    per_layer = (4 * cfg.mamba_num_heads * cfg.mamba_head_dim
                 * cfg.mamba_d_state
                 + 2 * (cfg.mamba_d_conv - 1) * cfg.conv_dim)
    assert spec.state_bytes_per_slot == 3 * per_layer
    fam = family_of(cfg)
    assert fam.decode_walks and fam.state_walks
    for kw, words in (({"prefix_cache": True}, "prefix_cache=True"),
                      ({"speculate_k": 2}, "speculate_k=2")):
        with pytest.raises(ValueError, match=words):
            ContinuousBatchingEngine(None, cfg, max_batch=2, **kw)


def test_the_engine_serves_it_as_generate_does():
    cfg = dataclasses.replace(GraniteHybridConfig.tiny(), dtype=F32)
    params = gh.granite_hybrid_init(cfg, jax.random.PRNGKey(0))
    reqtrace._reset_store_for_tests()
    engine = ContinuousBatchingEngine(params, cfg, max_batch=4)
    try:
        prompt = [int(t) for t in TOKENS[:13]]
        emitted = [int(t) for t in engine.stream(prompt, 6, timeout_s=120)]
        stats = engine.kv_stats()
    finally:
        engine.stop()
    toks = list(prompt)
    fwd = jax.jit(lambda t: gh.granite_hybrid_forward(params, t,
                                                      cfg)[0, -1])
    for _ in range(6):
        toks.append(int(jnp.argmax(fwd(jnp.asarray(toks)[None]))))
    assert emitted == toks[13:]
    assert stats["stateful"] is True
    assert stats["slab"] == [{"rows": 128, "layers": 1,
                              "bytes_per_slot": 2 * 128 * 2 * 16 * 4}]
    assert (4, 1, 4, 2, 16, 128) in [w["shape"]
                                     for w in stats["gqa_decode"]]
    assert (4, 8, 16, 1, 16) in [s["shape"] for s in stats["state_step"]]
    records = reqtrace.store().loop_records()
    (admission,) = [a for r in records for a in r["admissions"]]
    assert admission["prompt_tokens"] == 13
    assert 0 < admission["moe_pairs_held"] < 13 * 3 * 4
    assert admission["state_bytes"] == stats["state_bytes_per_slot"]
    ticks = [r for r in records if "moe_pairs_held" in r]
    assert ticks and all(
        r["moe_rows_max"] <= r["moe_pairs_held"] <= 4 * 3 * 4
        and r["moe_experts_hit"] <= 4 * 4 and "live_rows" in r
        and r["live"] <= r["state_slots_stepped"] <= 4 for r in ticks)
    reqtrace._reset_store_for_tests()


def test_the_partition_specs_follow_the_parameters():
    cfg = GraniteHybridConfig.tiny()
    params = jax.eval_shape(
        lambda: gh.granite_hybrid_init(cfg, jax.random.PRNGKey(0)))
    specs = gh.granite_hybrid_partition_specs(cfg)
    leaf = lambda x: not isinstance(x, (dict, list))
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=leaf)
    assert specs["blocks"][0]["moe"]["w1"][0] == "ep"


@pytest.mark.parametrize("change,words", [
    ({"pattern": "ME"}, "only M and"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"num_heads": 3}, "divide by num_kv_heads"),
    ({"first_expert": 6}, "outside the router"),
])
def test_the_config_refuses_what_it_cannot_be(change, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(GraniteHybridConfig.tiny(), **change)
