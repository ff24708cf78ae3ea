"""`ops/grouped_moe.py`: the streamed grouped product (the Pallas kernel
`grouped_stream`, here in interpret mode) against `jax.lax.ragged_dot`
on the same sorted rows, and the shape rule that says which of the two
a `held_experts` call takes."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dispatch, grouped_moe
from ray_tpu.ops.grouped_moe import (ROW_TILE, held_experts,
                                     mlp_top1_route)

F32 = jnp.float32
BF16 = jnp.bfloat16


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _swiglu(x):
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# the three callers' expert shapes at toy widths: (held, width of a row,
# w1's columns, w2's rows, activation, bytes a weight tile may hold)
EXPERTS = {
    # experts in a latent space under relu squared, as nemotron_h holds
    "latent-relu2": (8, 32, 80, 80, _relu2, None),
    # SwiGLU, gate and up packed in one w1, as kimi_linear holds
    "swiglu": (4, 48, 64, 32, _swiglu, None),
    # the same packing with w1 cut into two tiles of columns, as
    # deepseek_v2's 31 MB experts are
    "swiglu-column-tiles": (5, 256, 256, 128, _swiglu, 256 * 128 * 2),
}


def _quarter(held):
    """Sizes that sum to a quarter of a 16 x held row buffer, uneven."""
    sizes = np.zeros(held, np.int64)
    for i in range(4 * held):
        sizes[(i * i) % held] += 1
    return sizes.tolist(), 16 * held


# name -> held -> (rows each held expert gets, rows in the buffer)
SPLITS = {
    "every-expert-4-rows": lambda h: ([4] * h, 16 * h),
    "one-expert-holds-every-row": lambda h: (
        [0] * (h - 2) + [3 * ROW_TILE] + [0], 3 * ROW_TILE),
    "most-experts-empty": lambda h: ([0] * (h - 3) + [3, 0, 1], 4 * h),
    "one-row-over-the-row-tile": lambda h: (
        [2, ROW_TILE + 1] + [2] * (h - 2), 8 * h + 2 * ROW_TILE),
    "a-quarter-held-garbage-past-it": _quarter,
    "zero-held-pairs": lambda h: ([0] * h, 4 * h),
    # a prompt block's groups, taller than a row tile, in buffers of 160
    # to 192 rows an expert held (past what PR 34's cap let through): 1,
    # 64 and 192 rows and an empty group; one group of 3 times the mean; a
    # group that ends on the edge of a tile of either height below
    "tall-groups-1-64-192-and-none": lambda h: (
        [1, 64, 192, 0] + [5] * (h - 4), 160 * h),
    "one-group-of-3x-the-mean": lambda h: (
        [150] * (h - 2) + [450, 0], 192 * h),
    "a-group-ends-on-a-tile-edge": lambda h: (
        [4 * ROW_TILE - 7, 7, 4 * ROW_TILE] + [3] * (h - 3), 176 * h),
}
# the tile every call takes, and a taller one (the kernel's `tm` is an
# argument: `examples/grouped_product_sweep.py` times it at 64 to 256)
ROW_TILES = [ROW_TILE, 2 * ROW_TILE]


def _weights(held, width, cols, inner, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (held, width, cols), BF16) * 0.3,
            jax.random.normal(k2, (held, inner, width), BF16) * 0.3)


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("split", list(SPLITS))
@pytest.mark.parametrize("experts", list(EXPERTS))
def test_streamed_product_is_ragged_dot(experts, split, tm, monkeypatch):
    held, width, cols, inner, act, tile_bytes = EXPERTS[experts]
    if tile_bytes:
        monkeypatch.setattr(grouped_moe, "WEIGHT_TILE_BYTES", tile_bytes)
        assert grouped_moe._weight_tile(width, cols, 2, tile_bytes) < cols
    sizes, m = SPLITS[split](held)
    total = sum(sizes)
    assert total <= m
    w1, w2 = _weights(held, width, cols, inner, seed=len(split))
    x = jax.random.normal(jax.random.PRNGKey(7), (m, width), BF16)
    # the rows past the last group are another expert's: whatever they
    # hold must not reach a row that is a group's
    x = x.at[total:].set(jnp.nan)
    s = jnp.asarray(sizes, jnp.int32)

    def both(product):
        mid = product(x, w1, s)
        out = product(act(mid).astype(BF16), w2, s)
        return np.asarray(mid[:total]), np.asarray(out[:total])

    visits = grouped_moe._visits(s, m, tm)
    got = both(lambda a, w, g: grouped_moe._streamed_product(
        a, w, visits, interpret=True, tm=tm, tn=grouped_moe._weight_tile(
            w.shape[1], w.shape[2], 2, grouped_moe.WEIGHT_TILE_BYTES)))
    want = both(lambda a, w, g: jax.lax.ragged_dot(
        a, w, g, preferred_element_type=F32))
    # Both sum the same exact products of bf16 numbers in float32 and
    # differ in the order alone: at most K x 2^-24 of the sum of the
    # terms' sizes, under a thousandth of the half unit (2^-9) a bf16
    # output is rounded by. `mid` is held to that; `out` is taken from a
    # `mid` ROUNDED to bf16, where such a difference may turn a rounding:
    # one unit, 2^-8, of a term.
    xs = np.abs(np.asarray(x[:total], np.float32))
    for name, a, b, scale, rel in (
            ("mid", got[0], want[0], xs, 2.0 ** -9),
            ("out", got[1], want[1],
             np.abs(np.asarray(act(want[0]))), 2.0 ** -8)):
        w = w1 if name == "mid" else w2
        group = np.repeat(np.arange(held), sizes)
        bound = rel * np.einsum(
            "mk,mkn->mn", scale, np.abs(np.asarray(w, np.float32))[group])
        assert a.shape == b.shape and np.isfinite(a).all(), name
        assert (np.abs(a - b) <= bound + 1e-30).all(), (
            name, float(np.abs(a - b).max()))


@pytest.mark.parametrize("split", list(SPLITS))
@pytest.mark.parametrize("experts", list(EXPERTS))
def test_held_experts_counters_and_result(experts, split, monkeypatch):
    """A whole `held_experts` call on the streamed path against the same
    call on `ragged_dot`: the three counters to the number, the result
    within the rounding of `mid`."""
    held, width, cols, inner, act, tile_bytes = EXPERTS[experts]
    if tile_bytes:
        monkeypatch.setattr(grouped_moe, "WEIGHT_TILE_BYTES", tile_bytes)
    sizes, m = SPLITS[split](held)
    k, first, experts_all = 2, held, 4 * held
    m += m % k
    # the pairs of held experts as the split has them, the rest held
    # elsewhere (below `first` and above the run), shuffled over tokens
    local = np.repeat(np.arange(held), sizes)
    away = np.arange(m - local.size)
    away = np.where(away % 2 == 0, away % first,
                    first + held + away % (experts_all - first - held))
    rng = np.random.default_rng(3)
    chosen = rng.permutation(np.concatenate([local + first, away]))
    chosen = jnp.asarray(chosen.reshape(m // k, k), jnp.int32)
    weights = jax.random.uniform(jax.random.PRNGKey(5), chosen.shape, F32)
    u = jax.random.normal(jax.random.PRNGKey(6), (m // k, width), BF16)
    w1, w2 = _weights(held, width, cols, inner, seed=1)
    call = lambda: jax.jit(lambda *a: held_experts(*a, first, act))(
        u, chosen, weights, w1, w2)
    dispatch.reset_kernel_choices()
    want, want_counts = call()
    assert [c["choice"] for c in dispatch.kernel_choices("grouped_product")
            ] == ["reference"]
    with dispatch.pallas_interpret():
        dispatch.reset_kernel_choices()
        got, got_counts = call()
    (choice,) = dispatch.kernel_choices("grouped_product")
    assert choice["choice"] == "pallas"
    assert choice["row_tile"] == ROW_TILE == 64, choice
    assert sorted(got_counts) == ["pairs_held", "rows_max", "sizes"]
    for name in got_counts:
        np.testing.assert_array_equal(got_counts[name], want_counts[name])
    np.testing.assert_array_equal(got_counts["sizes"], sizes)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    # k pairs a token, each off by at most a bf16 unit of a term of `mid`
    assert np.abs(got - want).max(initial=0.0) <= 2.0 ** -7 * max(
        1.0, np.abs(want).max(initial=0.0))


# the callers' programs at the cells' real static shapes: (tokens,
# experts a token, w1, w2, the path the issue's table gives it; a prefill
# by `STREAM_ROWS_AN_EXPERT`, the prompt blocks of deepseek_v2 never)
_NEMOTRON = ((128, 1024, 2688), (128, 2688, 1024))
_KIMI = ((64, 2304, 2048), (64, 1024, 2304))
_DEEPSEEK = ((40, 5120, 3072), (40, 1536, 5120))
_SMALLTHINKER = ((64, 2560, 1536), (64, 768, 2560))
_ZAYA = ((16, 2048, 4096), (16, 2048, 2048))
PROGRAMS = {
    "nemotron_h-tick": (96, 22, *_NEMOTRON, "pallas"),
    "kimi_linear-tick": (128, 8, *_KIMI, "pallas"),
    "deepseek_v2-tick": (16, 6, *_DEEPSEEK, "pallas"),
    "smallthinker-tick": (16, 6, *_SMALLTHINKER, "pallas"),
    "nemotron_h-prefill-192": (192, 22, *_NEMOTRON, "pallas"),
    "nemotron_h-prefill-768": (768, 22, *_NEMOTRON, "pallas"),
    "nemotron_h-prefill-1024": (1024, 22, *_NEMOTRON, "pallas"),
    "kimi_linear-prefill-288": (288, 8, *_KIMI, "pallas"),
    "kimi_linear-prefill-1024": (1024, 8, *_KIMI, "pallas"),
    "kimi_linear-prefill-2048": (2048, 8, *_KIMI, "reference"),
    "deepseek_v2-prefill-block-2048": (2048, 6, *_DEEPSEEK, "reference"),
    "deepseek_v2-prefill-1024": (1024, 6, *_DEEPSEEK, "pallas"),
    "smallthinker-prefill-block-2048": (2048, 6, *_SMALLTHINKER, "pallas"),
    # ONE expert a token: 4 rows an expert a tick of 64 slots, 66 a
    # prompt of 1,056 tokens, 128 a block of 2,048
    "zaya-tick": (64, 1, *_ZAYA, "pallas"),
    "zaya-prefill-1056": (1056, 1, *_ZAYA, "pallas"),
    "zaya-prefill-block-2048": (2048, 1, *_ZAYA, "pallas"),
}


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_shape_rule(program):
    tokens, k, w1, w2, path = PROGRAMS[program]
    held, width, cols = w1
    act = _swiglu if cols == 2 * w2[1] else _relu2
    S = jax.ShapeDtypeStruct
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():      # a backend with the kernel
        out, _ = jax.eval_shape(
            lambda *a: held_experts(*a, 0, act),
            S((tokens, width), BF16), S((tokens, k), jnp.int32),
            S((tokens, k), F32), S(w1, BF16), S(w2, BF16))
    assert out.shape == (tokens, width) and out.dtype == F32
    (choice,) = dispatch.kernel_choices("grouped_product")
    assert choice["shape"] == (tokens * k, held, width, w2[1])
    assert choice["choice"] == path, choice
    # every streamed shape, the ones PR 34 streamed too, at ONE row tile
    assert choice["row_tile"] == (64 if path == "pallas" else None), choice
    if path == "reference":
        assert str(grouped_moe.STREAM_ROWS_AN_EXPERT) in choice["reason"]


def test_shape_rule_names_no_model_and_reads_no_environment():
    source = inspect.getsource(grouped_moe._grouped_product).lower()
    for word in ("nemotron", "kimi", "deepseek", "environ", "getenv",
                 "config"):
        assert word not in source
    # without the kernel's backend the reference, and the reason says so
    dispatch.reset_kernel_choices()
    grouped_moe._grouped_product(
        96, jnp.zeros((4, 8, 16), BF16), jnp.zeros((4, 16, 8), BF16),
        jnp.zeros((4,), jnp.int32))
    (choice,) = dispatch.kernel_choices("grouped_product")
    assert choice["choice"] == "reference" and "backend" in choice["reason"]


@pytest.mark.parametrize("family", ["nemotron_h", "kimi_linear",
                                    "deepseek_v2", "smallthinker", "zaya",
                                    "granite_hybrid"])
def test_a_family_forward_under_the_kernel(family):
    """Each caller's whole forward pass at its toy size with the streamed
    kernel (interpret mode) against the same pass on `ragged_dot`, each
    traced as ONE program under the form the process then has."""
    import importlib
    mod = importlib.import_module(f"ray_tpu.models.{family}")
    config = next(v for k, v in vars(mod).items()
                  if k.endswith("Config") and hasattr(v, "tiny")).tiny()
    params = getattr(mod, f"{family}_init")(config, jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 6), 0,
                                config.vocab_size)
    forward = getattr(mod, f"{family}_forward")
    want = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config))(params, tokens))
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        got = np.asarray(jax.jit(
            lambda p, t: forward(p, t, config))(params, tokens))
    choices = dispatch.kernel_choices("grouped_product")
    assert choices and all(c["choice"] == "pallas" for c in choices)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=0)


# ------------------------------------------------- the fifth router

def _router(seed=0, d=24, r=8, experts=4):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda *s: jax.random.normal(next(k), s, F32)
    return {"w_down": n(d, r) * d ** -0.5, "gamma": jnp.float32(0.5),
            "norm": 1.0 + 0.1 * n(r),
            "mlp": (n(r, r) * r ** -0.5, n(r, r) * r ** -0.5,
                    2.0 * n(r, experts + 1) * r ** -0.5),
            "bias": jnp.zeros((experts + 1,), F32)}


def _route(p, h, carried, eps=1e-5):
    return mlp_top1_route(h, carried, p["w_down"], p["gamma"], p["norm"],
                          p["mlp"], p["bias"], eps)


def test_mlp_top1_route_by_hand():
    """`r = h W + gamma r'`, RMSNorm, two GELU layers (erf), softmax over
    experts + 1, ONE choice, its probability the weight."""
    from math import erf

    p = _router()
    h = jax.random.normal(jax.random.PRNGKey(1), (9, 24), F32)
    carried = jax.random.normal(jax.random.PRNGKey(2), (9, 8), F32)
    chosen, weights, state = _route(p, h, carried)
    w = jax.tree.map(lambda x: np.asarray(x, np.float64), p)
    r = np.asarray(h, np.float64) @ w["w_down"] \
        + 0.5 * np.asarray(carried, np.float64)
    z = r / np.sqrt((r * r).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    gelu = np.vectorize(lambda x: 0.5 * x * (1.0 + erf(x / 2 ** 0.5)))
    z = gelu(gelu(z @ w["mlp"][0]) @ w["mlp"][1]) @ w["mlp"][2]
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    np.testing.assert_allclose(state, r, atol=1e-5)
    assert state.dtype == F32 and weights.dtype == F32
    assert chosen.shape == weights.shape == (9, 1)
    assert chosen.dtype == jnp.int32
    np.testing.assert_array_equal(chosen[:, 0], prob.argmax(-1))
    np.testing.assert_allclose(weights[:, 0], prob.max(-1), atol=1e-6)
    assert 0 <= int(chosen.min()) and int(chosen.max()) <= 4


def test_mlp_top1_route_carries_its_state_and_its_bias_only_chooses():
    p = _router(seed=3)
    h = jax.random.normal(jax.random.PRNGKey(4), (64, 24), F32)
    zero = jnp.zeros((64, 8), F32)
    chosen, weights, state = _route(p, h, zero)
    # the state handed on is the down-projection plus gamma times the last
    _c, _w, again = _route(p, h, state)
    np.testing.assert_allclose(again, 1.5 * state, atol=1e-5)
    # the same direction at another length is the same choice (the norm)
    np.testing.assert_array_equal(_c, chosen)
    # another layer's state moves it
    other = jax.random.normal(jax.random.PRNGKey(5), (64, 8), F32)
    assert not np.array_equal(_route(p, h, 4.0 * other)[0], chosen)
    # gamma 0: the last layer's state is not read
    still = _route({**p, "gamma": jnp.float32(0.0)}, h, 7.0 + state)
    np.testing.assert_array_equal(still[0], chosen)
    # a bias that lifts ONE choice over every probability: it is chosen,
    # and its weight is still its probability, without the bias
    for lifted in (1, 4):       # an expert; the last choice, no expert
        bias = jnp.zeros((5,), F32).at[lifted].set(2.0)
        forced, weight, _s = _route({**p, "bias": bias}, h, zero)
        assert (np.asarray(forced) == lifted).all()
        assert float(weight.max()) < 1.0
        kept = np.asarray(chosen[:, 0]) == lifted
        np.testing.assert_allclose(weight[kept], weights[kept], atol=1e-7)


@pytest.mark.parametrize("path", ["reference", "pallas"])
def test_the_last_choice_takes_the_path_of_a_pair_held_elsewhere(path):
    """`held_experts` under ONE choice a token of which the last is no
    expert: those tokens' rows are sorted last and dropped, their output
    is exactly 0, the others' is their one expert's at its weight."""
    held, width, inner = 4, 32, 16
    w1, w2 = _weights(held, width, 2 * inner, inner, seed=2)
    u = jax.random.normal(jax.random.PRNGKey(7), (24, width), BF16)
    chosen = jnp.asarray(np.arange(24) % (held + 1), jnp.int32)[:, None]
    weights = jax.random.uniform(jax.random.PRNGKey(8), (24, 1), F32,
                                 0.1, 0.9)
    call = lambda: jax.jit(lambda *a: held_experts(*a, 0, _swiglu))(
        u, chosen, weights, w1, w2)
    if path == "pallas":
        with dispatch.pallas_interpret():
            out, counts = call()
    else:
        out, counts = call()
    out, none = np.asarray(out), np.asarray(chosen[:, 0]) == held
    assert (out[none] == 0.0).all() and none.sum() == 4
    assert int(counts["pairs_held"]) == 20
    np.testing.assert_array_equal(counts["sizes"], [5, 5, 5, 5])
    for t in np.flatnonzero(~none)[:6]:
        e = int(chosen[t, 0])
        mid = _swiglu(jnp.dot(u[t], w1[e], preferred_element_type=F32))
        want = float(weights[t, 0]) * jnp.dot(
            mid.astype(BF16), w2[e], preferred_element_type=F32)
        np.testing.assert_allclose(out[t], want, atol=2e-2, rtol=2e-2)
