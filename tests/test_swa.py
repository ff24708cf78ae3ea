"""`ops/swa.py`: the grouped-query prompt form under a window. The Pallas
kernel in interpret mode against its `jax.numpy` blocks and against the
dense masked softmax, the blocks a walk visits by hand, the choice
recorded in `ops/dispatch`; and `ops/grouped_moe.softmax_topk_route`
against both readings of the two published router keys."""
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
