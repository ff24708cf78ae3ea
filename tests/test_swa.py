"""`ops/swa.py`: the grouped-query prompt form under a window. The Pallas
kernel in interpret mode against its `jax.numpy` blocks and against the
dense masked softmax, the blocks a walk visits by hand, the choice
recorded in `ops/dispatch`; and `ops/grouped_moe.softmax_topk_route`
against both readings of the two published router keys."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dispatch, swa
from ray_tpu.ops.grouped_moe import softmax_topk_route

W, BLOCK = 16, 8


def _qkv(t, seed=0, heads=6, groups=2, d=16):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(2, t, n, d)), jnp.float32)
                 for n in (heads, groups, groups))


def _dense(q, k, v, window):
    """Head h over key-value head h // rep, one head at a time."""
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    at = np.arange(t)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    out = np.zeros(q.shape, np.float32)
    for i in range(h):
        s = np.einsum("btd,bsd->bts", q[:, :, i], k[:, :, i // rep]) \
            / np.sqrt(d)
        s = np.where(seen[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, i] = np.einsum("bts,bsd->btd", p / p.sum(-1,
                                 keepdims=True), v[:, :, i // rep])
    return out


# T < W, T = W, T > 2 W, T not a whole number of blocks; by hand, blocks
# of 8 under a window of 16 (two blocks): a block of queries visits its
# own block, the two before it where the band's edge crosses the older
# one, so 1 + 2 + 3 + 3 + ... and the causal walk 1 + 2 + 3 + 4 + ...
@pytest.mark.parametrize("t,window,visited,causal", [
    (12, W, 3, 3), (12, None, 3, 3),        # T < W: the window is none
    (16, W, 3, 3), (16, None, 3, 3),        # T = W
    (40, W, 12, 15), (40, None, 15, 15),    # T > 2 W
    (37, W, 12, 15), (37, None, 15, 15),    # padded to 40
    (37, 5, 9, 15),                         # a window shorter than a block
    (64, 4, 15, 36)])
def test_the_kernel_its_blocks_and_the_dense_softmax_agree(
        t, window, visited, causal):
    q, k, v = _qkv(t, seed=t)
    want = _dense(np.asarray(q), np.asarray(k), np.asarray(v), window)
    assert swa.visited_blocks(t, BLOCK, window if window and window < t
                              else None) == (visited, causal)
    dispatch.reset_kernel_choices()
    blocks, n_ref = swa.prompt_attention(q, k, v, window, BLOCK)
    assert dispatch.kernel_choices("gqa_prefill")[0]["choice"] == "reference"
    with dispatch.pallas_interpret():
        kernel, n_kernel = swa.prompt_attention(q, k, v, window, BLOCK)
    choice = dispatch.kernel_choices("gqa_prefill")[0]
    assert choice["choice"] == "pallas" and choice["shape"][:2] == (2, t)
    assert n_ref == n_kernel == visited
    np.testing.assert_allclose(blocks, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, want, atol=2e-6, rtol=0)


def test_a_prompt_of_one_block_takes_the_plain_form():
    q, k, v = _qkv(8)
    dispatch.reset_kernel_choices()
    out, visited = swa.prompt_attention(q, k, v, 4, BLOCK)
    assert visited == 1 and not dispatch.kernel_choices("gqa_prefill")
    np.testing.assert_allclose(
        out, _dense(np.asarray(q), np.asarray(k), np.asarray(v), 4),
        atol=2e-6, rtol=0)


def test_the_walks_at_the_served_lengths_by_hand():
    # blocks of 512 under 4,096: ten blocks, the ninth and tenth visit 9
    assert swa.visited_blocks(5120, 512, 4096) == (36 + 9 + 9, 55)
    assert swa.visited_blocks(4608, 512, 4096) == (45, 45)
    assert swa.visited_blocks(15872, 512, 4096) == (36 + 23 * 9, 496)
    # three window layers in four: what the band leaves of the walk
    assert (3 * 54 + 55) / (4 * 55) == pytest.approx(0.986, abs=5e-4)
    assert (3 * 243 + 496) / (4 * 496) == pytest.approx(0.617, abs=5e-4)


@pytest.mark.parametrize("rep,block", [(4, 512), (7, 512), (8, 512),
                                       (20, 256), (64, 128), (512, 128)])
def test_the_block_follows_from_the_groups_size(rep, block):
    """Mistral's group of 4 and SmallThinker's of 7 keep the 512 their
    cells were measured at; Jamba's of 20 takes 256: 5,120 stacked rows
    and 5.2 MB of float32 scores a key block, under SmallThinker's 7.3."""
    assert swa._fit_block(512, rep) == block
    assert 4 * rep * block * block <= swa._SCORE_BYTES or block == 128
    assert swa._fit_block(8, rep) == 8      # a caller's smaller block stands


def test_one_key_value_head_under_twenty_query_heads():
    """Multi-query attention at Jamba's group through the prompt form, a
    length that ends in a ragged block: the kernel in interpret mode, its
    blocks and the dense softmax. The fitted block is in the walk: 600
    tokens at 256 are three blocks, 6 visited (512 would give 3)."""
    q, k, v = _qkv(600, seed=3, heads=20, groups=1, d=32)
    q, k, v = q[:1], k[:1], v[:1]
    want = swa.plain_attention(q, k, v)
    dispatch.reset_kernel_choices()
    blocks, visited = swa.prompt_attention(q, k, v)
    assert dispatch.kernel_choices("gqa_prefill")[0]["choice"] == "reference"
    with dispatch.pallas_interpret():
        kernel, n_kernel = swa.prompt_attention(q, k, v)
    assert visited == n_kernel == 6
    choice = dispatch.kernel_choices("gqa_prefill")[0]
    assert choice["choice"] == "pallas" \
        and tuple(choice["shape"]) == (1, 600, 20, 1, 32, 0)
    np.testing.assert_allclose(blocks, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(
        kernel, _dense(np.asarray(q), np.asarray(k), np.asarray(v), None),
        atol=2e-6, rtol=0)


def test_the_blocks_can_be_differentiated():
    q, k, v = _qkv(24)
    grad = jax.grad(lambda x: swa.prompt_attention(x, k, v, W, BLOCK)[0]
                    .sum())(q)
    want = jax.grad(lambda x: swa.plain_attention(x, k, v, W).sum())(q)
    np.testing.assert_allclose(grad, want, atol=1e-5, rtol=0)


def test_softmax_topk_route_is_both_readings_of_the_router_keys():
    """`moe_primary_router_apply_softmax` with `norm_topk_prob`: a
    softmax over the chosen logits, or a softmax over all experts
    renormalised over the chosen: the same numbers."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(9, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(32, 12)), jnp.float32)
    chosen, weights = softmax_topk_route(h, w, 4)
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    logits = np.asarray(h, np.float32) @ np.asarray(w)
    order = np.argsort(-logits, -1)[:, :4]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(order, -1))
    top = np.take_along_axis(logits, np.asarray(chosen), -1)
    over_chosen = np.exp(top) / np.exp(top).sum(-1, keepdims=True)
    every = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    picked = np.take_along_axis(every, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, over_chosen, atol=1e-6)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)


# ------------------------------------------------------------- decode form
S_ROWS = 300        # no whole number of blocks of 128: the last is held


def _slab(seed, t, groups, rep, d=16, batch=4, rows=S_ROWS):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(batch, t, groups * rep, d)),
                    jnp.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(batch, rows, groups, d)),
                          jnp.float32) for _ in range(2))
    return q, ck, cv


def _positions(base, t):
    return jnp.asarray(np.asarray(base)[:, None] + np.arange(t)[None],
                       jnp.int32)


@pytest.fixture()
def blocks_of_128(monkeypatch):
    """Toy rows are a few hundred bytes: the served block's bytes would
    make one block of the whole entry."""
    monkeypatch.setattr(swa, "_DECODE_BLOCK_BYTES", 1)


# the edges of a block and of the entry; a parked slot, a one-row slot
# and a full one beside a slot mid-block; a ring's positions as
# `smallthinker._attn_decode` clamps them once it has wrapped
BASES = {"edges": [0, 127, 128, S_ROWS - 1],
         "mixed": [0, 0, S_ROWS - 1, 200],
         "ring": [S_ROWS - 1] * 4}


@pytest.mark.parametrize("where", list(BASES))
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("groups,rep", [(8, 4), (4, 7), (2, 16), (1, 20)])
def test_the_decode_form_is_the_slab_form(groups, rep, t, where,
                                          blocks_of_128):

    q, ck, cv = _slab(groups + t, t, groups, rep)
    base = np.minimum(BASES[where], S_ROWS - t)
    pos = _positions(base, t)
    want = swa.slab_attention(q, ck, cv, pos)
    dispatch.reset_kernel_choices()
    blocks = swa.decode_attention(q, ck, cv, pos)
    choice = dispatch.kernel_choices("gqa_decode")[0]
    assert choice["choice"] == "reference" and choice["block"] == 128
    with dispatch.pallas_interpret():
        kernel = swa.decode_attention(q, ck, cv, pos)
    choice = dispatch.kernel_choices("gqa_decode")[0]
    assert choice["choice"] == "pallas" and tuple(choice["shape"]) == (
        4, t, groups * rep, groups, 16, S_ROWS)
    np.testing.assert_allclose(blocks, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, want, atol=2e-6, rtol=0)


@pytest.mark.parametrize("form", ["blocks", "kernel"])
@pytest.mark.parametrize("groups,rep", [(8, 4), (1, 20)])
def test_a_verify_row_is_the_sequential_ticks_to_the_bit(groups, rep, form,
                                                         blocks_of_128):
    """Row j of a run of four is what a one-token tick at its position
    gives, bit for bit: what the speculation's accept rule stands on
    (`tests/test_speculate.py` holds the engine's tokens to it). The run
    crosses a block's edge (126 .. 129), so rows 0 and 1 meet a block
    they see nothing of."""
    q, ck, cv = _slab(7, 4, groups, rep)
    pos = _positions([126, 0, S_ROWS - 4, 254], 4)
    how = dispatch.pallas_interpret if form == "kernel" \
        else contextlib.nullcontext
    with how():
        run = swa.decode_attention(q, ck, cv, pos)
        for j in range(4):
            one = swa.decode_attention(q[:, j:j + 1], ck, cv,
                                       pos[:, j:j + 1])
            np.testing.assert_array_equal(run[:, j:j + 1], one)


def test_a_block_wholly_masked_for_a_row_leaves_its_carry_alone(
        blocks_of_128):
    """Row 0 stands at position 5 and row 1 at 299: the walk visits three
    blocks, and row 0's max, sum and accumulator after it are, to the
    bit, what a walk that ends at block 0 leaves."""
    q, ck, cv = _slab(11, 2, 2, 3, batch=1)
    both = swa._decode_walk(q, ck, cv, jnp.asarray([[5, 299]]), 128)
    alone = swa._decode_walk(q[:, :1], ck, cv, jnp.asarray([[5]]), 128)
    for run, tick in zip(both, alone):          # [B, G, rep, t, .]
        np.testing.assert_array_equal(run[:, :, :, :1], tick)
    # and row 1 did walk on: 300 keys in its sum for row 0's six
    assert float(both[1][0, 0, 0, 1, 0]) > float(both[1][0, 0, 0, 0, 0])


@pytest.mark.parametrize("positions,block,rows,read", [
    # 1 + 1 + 2 + 18 blocks of 128
    ([0, 127, 128, 2303], 128, 2304, 22 * 128),
    # a verify pass: the run's last position ends the walk
    ([[126, 127, 128], [0, 1, 2]], 128, 2304, 3 * 128),
    # 29 parked slots and three live ones, as `mistral-chat` holds them
    ([0] * 29 + [450, 520, 610], 128, 2304, (29 + 4 + 5 + 5) * 128),
    # an entry of no whole number of blocks has three, the last held
    ([299, 0], 128, 300, 4 * 128),
    # a position past the entry visits every block and no more
    ([5000], 256, 1024, 4 * 256),
    ([0, 0], 300, 300, 600)])
def test_the_rows_a_walk_reads_by_hand(positions, block, rows, read):
    assert swa.decode_rows_read(np.asarray(positions), block, rows) == read


@pytest.mark.parametrize("shape,block", [
    ((128, 2816, 640), 256),        # kimi-linear: 1,280 bytes a latent row
    ((16, 8448, 640), 256),         # deepseek-v2
    ((2, 96, 128), 96),             # never over the entry
    ((32, 2304, 8, 128), 128)])     # keys and values: `_decode_block`'s
def test_one_latent_array_a_step_doubles_the_blocks_rows(shape, block):
    """A latent row is key and value: a step copies one array where keys
    and values are two, so at the same bytes a step its block holds twice
    the rows; the rule reads the entry's rank."""
    assert swa.decode_block(shape, jnp.bfloat16) == block
    if len(shape) == 3:
        assert block == shape[1] or block == 2 * swa._decode_block(
            shape[1], 1, shape[2], 2)


@pytest.mark.parametrize("rows,groups,d,block", [
    (2304, 8, 128, 128),        # mistral-chat: 2 KB a row of keys
    (4160, 8, 128, 128),        # mistral-summarize
    (16384, 4, 128, 256),       # smallthinker's global layers
    (4096, 4, 128, 256),        # and its rings
    (33280, 1, 128, 1024),      # jamba: one key-value head
    (1536, 2, 128, 512),        # nemotron
    (1024, 3, 256, 128),        # gpt2-chat: four heads of 64 to a row
    (96, 2, 16, 96)])           # never over the entry
def test_the_decode_block_follows_from_the_entrys_shape(rows, groups, d,
                                                        block):
    assert swa._decode_block(rows, groups, d, 2) == block
    assert block * groups * d * 2 <= swa._DECODE_BLOCK_BYTES
