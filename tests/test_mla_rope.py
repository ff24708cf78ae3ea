"""What DeepSeek-V2 added to the shared ops, on the CPU: YaRN's table
against numbers worked by hand, the group-limited choice on a hand-made
score matrix, the prompt form of latent attention (in `jax.numpy` and as
the Pallas kernel, interpreted) against the unblocked expanded form and
the absorbed form with rotated parts, and Kimi-Linear's numbers
bit for bit under the default scale."""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import dispatch, mla, rope, swa  # noqa: E402
from ray_tpu.ops.grouped_moe import (sigmoid_topk_route,  # noqa: E402
                                     softmax_group_limited_route)

F32 = jnp.float32


# ------------------------------------------------------------------ YaRN

def test_yarn_by_hand_at_the_published_numbers():
    """DeepSeek-V2: 64 rotary numbers, theta 10000, factor 40 over 4,096,
    beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707."""
    # cd(r) = 64 ln(4096 / (2 pi r)) / (2 ln 10000)
    assert math.log(4096 / (2 * math.pi * 32)) == pytest.approx(3.01416,
                                                                abs=1e-4)
    assert 64 * 3.01416 / (2 * 9.21034) == pytest.approx(10.472, abs=1e-3)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * 9.21034) \
        == pytest.approx(22.513, abs=1e-3)
    assert rope.yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    inv = np.asarray(rope.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1))
    f = [10000.0 ** (-2 * i / 64) for i in range(32)]
    # below the ramp as published, above it slowed forty times, between
    # them blended: index 15 is 5 / 13 of the way
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], [x / 40 for x in f[23:]],
                               rtol=1e-6)
    assert inv[15] == pytest.approx(f[15] * (8 / 13) + f[15] / 40 * (5 / 13),
                                    rel=1e-6)
    assert f[15] == pytest.approx(0.0133352, rel=1e-5)
    # m(a) = 0.1 a ln 40 + 1
    assert rope.yarn_mscale(40.0, 0.707) == pytest.approx(1.26080, abs=1e-5)
    assert rope.yarn_mscale(1.0, 0.707) == 1.0
    assert 192 ** -0.5 * rope.yarn_mscale(40.0, 0.707) ** 2 \
        == pytest.approx(0.114721, abs=1e-6)
    assert 192 ** -0.5 == pytest.approx(0.0721688, abs=1e-7)


def test_yarn_table_is_the_angles_and_does_not_depend_on_the_window():
    cos, sin = rope.yarn_table(64, 300, 10000.0, 40.0, 4096, 32, 1,
                               0.707, 0.707)
    inv = np.asarray(rope.yarn_inv_freq(64, 10000.0, 40.0, 4096))
    assert cos.shape == sin.shape == (300, 32)
    np.testing.assert_allclose(cos[17], np.cos(17 * inv), atol=1e-6)
    np.testing.assert_allclose(sin[17], np.sin(17 * inv), atol=1e-6)
    longer, _ = rope.yarn_table(64, 999, 10000.0, 40.0, 4096, 32, 1,
                                0.707, 0.707)
    np.testing.assert_array_equal(longer[:300], cos)
    # mscale over mscale_all_dim multiplies the table
    scaled, _ = rope.yarn_table(64, 300, 10000.0, 40.0, 4096, 32, 1,
                                1.0, 0.0)
    np.testing.assert_allclose(scaled, cos * (0.1 * math.log(40) + 1),
                               rtol=1e-6)
    # nothing stretched: the plain table
    plain, _ = rope.yarn_table(64, 300, 10000.0, 1.0, 4096)
    np.testing.assert_allclose(plain, rope.rope_table(64, 300)[0],
                               atol=1e-6)


# ------------------------------------------------------------ the router

def test_a_larger_score_outside_the_top_groups_is_not_chosen():
    """8 experts in 4 groups of 2, the best 2 groups, 3 experts a token.
    The router is the identity, so the logits are the hidden row."""
    logits = jnp.asarray([
        # groups: (0, 1) (2, 3) (4, 5) (6, 7)
        [5.0, 0.0, 4.0, 3.9, 4.5, -9.0, 1.0, 1.0],
        [0.0, 0.0, 2.0, 2.0, 0.5, 0.4, 3.0, -1.0]], F32)
    chosen, weights = softmax_group_limited_route(
        logits, jnp.eye(8, dtype=F32), 3, 4, 2, 16.0)
    scores = np.asarray(jax.nn.softmax(logits, -1))
    # row 0: groups 0 (5.0) and 2 (4.5) are kept; expert 2 (4.0) and 3
    # (3.9) beat expert 1 (0.0) and lie in a group left out: NOT chosen
    assert sorted(np.asarray(chosen[0])) == [0, 1, 4]
    # row 1: groups 3 (3.0) and 1 (2.0)
    assert sorted(np.asarray(chosen[1])) == [2, 3, 6]
    # the weights are the scores as they are, times 16: not renormalised
    for row in range(2):
        np.testing.assert_allclose(
            weights[row], 16.0 * scores[row, np.asarray(chosen[row])],
            rtol=1e-6)
    assert float(weights[0].sum()) < 16.0 * 0.75
    # the sigmoid router's callers keep theirs: plain top-k over all
    plain, _ = sigmoid_topk_route(logits, jnp.eye(8, dtype=F32),
                                  jnp.zeros(8), 3, 1.0)
    assert sorted(np.asarray(plain[0])) == [0, 2, 4]


# --------------------------------------------------- the attention forms

def _inputs(t, seed, b=2, h=4, d_n=16, d_r=8, d_v=16, rank=32,
            dtype=F32):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q_n = jax.random.normal(k[0], (b, t, h, d_n), dtype)
    q_r = jax.random.normal(k[1], (b, t, h, d_r), dtype)
    c = jax.random.normal(k[2], (b, t, rank), dtype)
    k_r = jax.random.normal(k[3], (b, t, d_r), dtype)
    w_kvb = (0.3 * jax.random.normal(k[4], (rank, h, d_n + d_v))
             ).astype(dtype)
    return q_n, q_r, c, k_r, w_kvb


def _rotated(q_r, k_r):
    cos, sin = rope.yarn_table(8, q_r.shape[1], 10000.0, 40.0, 16, 32, 1,
                               0.707, 0.707)
    return (rope.apply_rope(q_r, cos, sin),
            rope.apply_rope(k_r[:, :, None], cos, sin)[:, :, 0])


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["jax.numpy", "pallas-interpreted"])
@pytest.mark.parametrize("t,block", [(40, 8), (37, 8), (24, 16), (8, 8)],
                         ids=["whole-blocks", "ragged", "two-blocks",
                              "one-block"])
def test_the_prompt_form_is_the_expanded_form_unblocked(t, block, kernel):
    q_n, q_r, c, k_r, w_kvb = _inputs(t, seed=t)
    q_r, k_r = _rotated(q_r, k_r)
    want = mla.expanded_attention(q_n, q_r, c, k_r, w_kvb, 0.1147)
    dispatch.reset_kernel_choices()
    if kernel:
        with dispatch.pallas_interpret():
            got, blocks = mla.prompt_attention(q_n, q_r, c, k_r, w_kvb,
                                               0.1147, block)
    else:
        got, blocks = mla.prompt_attention(q_n, q_r, c, k_r, w_kvb,
                                           0.1147, block)
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=0)
    nb = -(-t // block)
    if nb == 1:
        assert blocks == 1 and not dispatch.kernel_choices("mla_prefill")
    else:
        # the kernel visits the lower triangle alone
        assert blocks == (nb * (nb + 1) // 2 if kernel else nb * nb)
        choice, = dispatch.kernel_choices("mla_prefill")
        assert choice["choice"] == ("pallas" if kernel else "reference")


def test_the_absorbed_form_with_rotated_parts_is_both():
    """The cache row holds the ROTATED key part: the absorbed form over
    the rows, one query at its own position a slot, gives what the
    expanded and the prompt form give that position."""
    t = 24
    q_n, q_r, c, k_r, w_kvb = _inputs(t, seed=3)
    q_r, k_r = _rotated(q_r, k_r)
    expanded = mla.expanded_attention(q_n, q_r, c, k_r, w_kvb, 0.1147)
    blocked, _ = mla.prompt_attention(q_n, q_r, c, k_r, w_kvb, 0.1147, 8)
    rows = mla.latent_row(c, k_r, 128, F32)
    assert rows.shape == (2, t, 128)
    rows = jnp.pad(rows, ((0, 0), (0, 9), (0, 0)))   # a slab longer than t
    at = jnp.asarray([[23], [10]], jnp.int32)
    take = jnp.arange(2)[:, None]
    got = mla.absorbed_attention(q_n[take, at], q_r[take, at], rows, at,
                                 w_kvb, 0.1147)
    for want in (expanded, blocked):
        np.testing.assert_allclose(got, want[take, at], atol=3e-6, rtol=0)


def test_the_first_callers_numbers_are_bit_for_bit_under_the_default_scale():
    """Kimi-Linear passes no scale: both forms divide by sqrt(d_n + d_r)
    as they did before they took one (bf16, as served)."""
    q_n, q_r, c, k_r, w_kvb = _inputs(20, seed=7, dtype=jnp.bfloat16)

    def expanded_before(q_n, q_r, c, k_r, w_kvb):
        t, d_n, d_r = q_n.shape[1], q_n.shape[-1], q_r.shape[-1]
        kv = jnp.einsum("bsc,chd->bshd", c, w_kvb,
                        preferred_element_type=F32).astype(q_n.dtype)
        k_n, v = kv[..., :d_n], kv[..., d_n:]
        scores = jnp.einsum("bthd,bshd->bhts", q_n, k_n,
                            preferred_element_type=F32) \
            + jnp.einsum("bthd,bsd->bhts", q_r, k_r,
                         preferred_element_type=F32)
        scores = scores / ((d_n + d_r) ** 0.5)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    def absorbed_before(q_n, q_r, rows, positions, w_kvb):
        rank, d_n, d_r = w_kvb.shape[0], q_n.shape[-1], q_r.shape[-1]
        w_uk, w_uv = w_kvb[..., :d_n], w_kvb[..., d_n:]
        q_c = jnp.einsum("bthd,chd->bthc", q_n, w_uk,
                         preferred_element_type=F32).astype(q_n.dtype)
        scores = jnp.einsum("bthw,bsw->bhts",
                            mla._padded([q_c, q_r], rows.shape[-1]), rows,
                            preferred_element_type=F32)
        scores = scores / ((d_n + d_r) ** 0.5)
        col = jnp.arange(rows.shape[1])[None, None, None, :]
        scores = jnp.where(col <= positions[:, None, :, None], scores,
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
        mixed = jnp.einsum("bhts,bsw->bthw", probs, rows)[..., :rank]
        return jnp.einsum("bthc,chd->bthd", mixed, w_uv,
                          preferred_element_type=F32).astype(q_n.dtype)

    np.testing.assert_array_equal(
        np.asarray(mla.expanded_attention(q_n, q_r, c, k_r, w_kvb), F32),
        np.asarray(expanded_before(q_n, q_r, c, k_r, w_kvb), F32))
    rows = mla.latent_row(c, k_r, 128, jnp.bfloat16)
    at = jnp.asarray([[19], [4]], jnp.int32)
    take = jnp.arange(2)[:, None]
    np.testing.assert_array_equal(
        np.asarray(mla.absorbed_attention(q_n[take, at], q_r[take, at],
                                          rows, at, w_kvb), F32),
        np.asarray(absorbed_before(q_n[take, at], q_r[take, at], rows, at,
                                   w_kvb), F32))
    # and a scale of the same value is the same attention, by a product
    np.testing.assert_allclose(
        np.asarray(mla.expanded_attention(q_n, q_r, c, k_r, w_kvb,
                                          24 ** -0.5), F32),
        np.asarray(expanded_before(q_n, q_r, c, k_r, w_kvb), F32),
        atol=0.02, rtol=0)


# ------------------------------------------------ the absorbed form's walk

@pytest.fixture()
def blocks_of_128(monkeypatch):
    """Toy rows are a few hundred bytes: the served block's bytes would
    make one block of the whole entry."""
    monkeypatch.setattr(swa, "_DECODE_BLOCK_BYTES", 1)


def _slab(seed, b, t, h, rows, dtype=jnp.bfloat16, rank=128, d_r=64,
          d_n=32, d_v=32):
    """A run of t queries a slot over a slab entry [b, rows, 256]: a
    latent of 128 and a shared key part of 64, as served in miniature."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q_n = jax.random.normal(k[0], (b, t, h, d_n), dtype)
    q_r = jax.random.normal(k[1], (b, t, h, d_r), dtype)
    slab = mla.latent_row(jax.random.normal(k[2], (b, rows, rank), dtype),
                          jax.random.normal(k[3], (b, rows, d_r), dtype),
                          mla.row_width(rank, d_r), dtype)
    w_kvb = (0.2 * jax.random.normal(k[4], (rank, h, d_n + d_v))
             ).astype(dtype)
    return q_n, q_r, slab, w_kvb


def _run_positions(base, t):
    return jnp.asarray(np.asarray(base)[:, None] + np.arange(t)[None],
                       jnp.int32)


# (rows of the entry, where each slot's run ENDS)
WALKS = {"ragged": (384, [5, 127, 128, 300]),
         "parked": (384, [0, 0, 383, 200]),
         "last-row": (384, [383, 383, 255, 256]),
         "no-whole-blocks": (300, [299, 171, 172, 40])}


@pytest.mark.parametrize("where", list(WALKS))
@pytest.mark.parametrize("t", [1, swa.DECODE_ROWS])
@pytest.mark.parametrize("heads,scale", [(32, None), (128, 0.1147)])
def test_the_walk_is_the_plain_absorbed_form(heads, scale, t, where,
                                             blocks_of_128):
    """bf16 rows and queries as served: the walk's numbers are the plain
    form's in another order of summation, to bf16's rounding."""
    rows, ends = WALKS[where]
    q_n, q_r, slab, w_kvb = _slab(heads + t, 4, t, heads, rows)
    pos = _run_positions(np.maximum(np.asarray(ends) - (t - 1), 0), t)
    dispatch.reset_kernel_choices()
    want = mla.absorbed_attention(q_n, q_r, slab, pos, w_kvb, scale)
    choice, = dispatch.kernel_choices("mla_decode")
    assert choice["choice"] == "reference" and "cpu" in choice["reason"]
    with dispatch.pallas_interpret():
        got = mla.absorbed_attention(q_n, q_r, slab, pos, w_kvb, scale)
    choice, = dispatch.kernel_choices("mla_decode")
    assert (choice["choice"], choice["block"], tuple(choice["shape"])) == (
        "pallas", 128, (4, t, heads, 256, 128, rows))
    want, got = np.asarray(want, F32), np.asarray(got, F32)
    # one bf16 step at the outputs' size, and the mean far under it
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    assert np.abs(got - want).mean() <= 2.0 ** -10 * np.abs(want).max()


def test_the_walk_never_reads_a_block_past_a_slots_last(blocks_of_128):
    """The blocks past the one that holds a slot's position hold NaN, as
    a slab's unwritten rows may hold anything: the plain form multiplies
    them by a probability of 0 and gives NaN, the walk does not."""
    q_n, q_r, slab, w_kvb = _slab(3, 3, 1, 32, 384, dtype=F32)
    pos = jnp.asarray([[0], [127], [200]], jnp.int32)
    last = np.asarray([128, 128, 256])
    clean = mla.absorbed_attention(q_n, q_r, slab, pos, w_kvb)
    dirty = jnp.where(np.arange(384)[None, :, None] >= last[:, None, None],
                      jnp.nan, slab)
    assert np.isnan(np.asarray(
        mla.absorbed_attention(q_n, q_r, dirty, pos, w_kvb))).all()
    with dispatch.pallas_interpret():
        got = mla.absorbed_attention(q_n, q_r, dirty, pos, w_kvb)
    np.testing.assert_allclose(got, clean, atol=3e-6, rtol=0)


@pytest.mark.parametrize("what,words", [
    ("visible", "`visible`"), ("long-run", "no tick's"),
    ("no-mosaic", "backend is cpu"), ("queries", "VMEM")])
def test_what_cannot_walk_takes_the_plain_form_and_says_why(what, words):
    t = swa.DECODE_ROWS + 1 if what == "long-run" else 2
    q_n, q_r, slab, w_kvb = _slab(5, 2, t, 4, 256, dtype=F32)
    pos = _run_positions([100, 7], t)
    want = mla.absorbed_attention(q_n, q_r, slab, pos, w_kvb, 0.2)
    visible = jnp.arange(256)[None, None] <= pos[..., None]
    dispatch.reset_kernel_choices()
    if what == "queries":
        # every slot's queries are resident: too many of them are not
        shapes = [jax.ShapeDtypeStruct((4096,) + x.shape[1:], x.dtype)
                  for x in (q_n, q_r, slab, pos)]
        with dispatch.pallas_interpret():
            jax.eval_shape(lambda a, b, c, d: mla.absorbed_attention(
                a, b, c, d, w_kvb, 0.2), *shapes)
    elif what == "no-mosaic":
        mla.absorbed_attention(q_n, q_r, slab, pos, w_kvb, 0.2)
    else:
        with dispatch.pallas_interpret():
            got = mla.absorbed_attention(
                q_n, q_r, slab, None if what == "visible" else pos, w_kvb,
                0.2, visible if what == "visible" else None)
        np.testing.assert_array_equal(got, want)
    choice, = dispatch.kernel_choices("mla_decode")
    assert choice["choice"] == "reference" and words in choice["reason"]


@pytest.mark.parametrize("b,heads,rows", [(128, 32, 2816), (16, 128, 8448)],
                         ids=["kimi-linear", "deepseek-v2"])
def test_the_walk_takes_the_served_entry_where_it_lies(b, heads, rows):
    """Lowered for a TPU at the served shapes (no chip and no TPU compiler
    needed to lower): the slab entry is the kernel's own operand, as the
    program's argument, and no other op makes an array of its shape (no
    copy, no pad, no transpose of 173 or 461 MB a layer)."""
    q = jax.ShapeDtypeStruct((b, 1, heads, 640), jnp.bfloat16)
    slab = jax.ShapeDtypeStruct((b, rows, 640), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    block = swa.decode_block(slab.shape, slab.dtype)
    assert block == 256
    text = jax.jit(lambda q, s, p: mla._decode_pallas(
        q, s, p, 512, 0.1147, block, False)).trace(q, slab, pos).lower(
        lowering_platforms=("tpu",)).as_text()
    entry = f"tensor<{b}x{rows}x640xbf16>"
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "mla_decode_t1" in call and entry in call
    made = [ln for ln in text.splitlines()
            if re.search(r"-> (\(.*)?" + re.escape(entry), ln)
            and "func.func" not in ln]
    assert not made, made
