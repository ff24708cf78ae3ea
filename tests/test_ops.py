"""Pallas kernel correctness: the hardware code path proven on CPU CI.

The test suite forces JAX_PLATFORMS=cpu (conftest.py), where
flash_attention normally dispatches to the jnp reference — so these tests
force the Pallas kernels through interpret mode (RAY_TPU_PALLAS_INTERPRET)
and check fwd AND grads against mha_reference: causal and not, odd
kv/q lengths (cross attention), bf16 and fp32, multiple block sizes.

Analog of the reference's kernel-less math tests; the reference has no
kernels of its own (SURVEY.md §5.7), so the model here is its numerical
test style (e.g. rllib/utils tests): explicit allclose vs a reference
implementation.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention, mha_reference


@pytest.fixture(autouse=True)
def _force_interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _rand_qkv(key, b, tq, tk, h, d, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, tq, h, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (b, tk, h, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (b, tk, h, d), dtype=jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_matches_reference(causal, dtype):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 256, 256, 2, 64, dtype)
    out = flash_attention(q, k, v, causal)
    ref = mha_reference(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_reference(causal):
    dtype = jnp.float32
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, 256, 256, 2, 64, dtype)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_flash_grads_bf16():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 128, 2, 64,
                        jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, True).astype(jnp.float32))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr, np.float32),
            atol=5e-2, rtol=5e-2, err_msg=f"d{name} mismatch")


def test_flash_cross_attention_decode_alignment():
    """kv longer than q (decode-style): queries align to the END of kv."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 384, 2, 64,
                        jnp.float32)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)

    g = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        mha_reference(a, b, c, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for gf, grr in zip(g, gr):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(grr),
                                   atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("block", [(64, 64), (128, 64), (64, 128)])
def test_flash_block_sizes(block):
    bq, bk = block
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 256, 256, 2, 64,
                        jnp.float32)
    out = flash_attention(q, k, v, True, None, bq, bk)
    ref = mha_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)
    g = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, True, None, bq, bk) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        mha_reference(a, b, c, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for gf, grr in zip(g, gr):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(grr),
                                   atol=1e-4, rtol=1e-3)


def test_flash_non_block_multiple_length():
    """T=640 is a multiple of 128 and of no larger block: the rule cuts
    its pairs to 128, so no pl.ds read is clamped (and corrupted)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 1, 640, 640, 2, 64,
                        jnp.float32)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)


def test_flash_odd_length_falls_back_to_reference():
    """Non-128-multiple sequence lengths use the XLA path and still work."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 100, 100, 2, 64,
                        jnp.float32)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)


def test_gpt2_loss_chunked_matches_unchunked():
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss)

    cfg = GPT2Config.tiny()
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                             cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0,
                             cfg.vocab_size)
    l1 = gpt2_loss(params, tok, tgt, cfg, loss_chunk_rows=1 << 30)
    l2 = gpt2_loss(params, tok, tgt, cfg, loss_chunk_rows=32)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-5, rtol=1e-5)
    # grads agree too, chunked + remat
    g1 = jax.grad(lambda p: gpt2_loss(p, tok, tgt, cfg,
                                      loss_chunk_rows=1 << 30))(params)
    g2 = jax.grad(lambda p: gpt2_loss(p, tok, tgt, cfg, remat=True,
                                      loss_chunk_rows=32))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-2, rtol=3e-2),
        g1, g2)


# ---------------------------------------------------------- fused CE


def test_fused_ce_fwd_matches_reference():
    from ray_tpu.ops.fused_ce import linear_cross_entropy, _ce_reference

    key = jax.random.PRNGKey(0)
    n, d, v, vocab = 256, 128, 640, 600  # _pick_block_v(640) -> 320
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (v, d), jnp.float32) * 0.1
    t = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, vocab)
    loss = linear_cross_entropy(x, w, t, vocab)
    ref, _ = _ce_reference(x, w, t, vocab)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_fused_ce_grads_match_reference():
    from ray_tpu.ops.fused_ce import linear_cross_entropy, _ce_reference

    key = jax.random.PRNGKey(3)
    n, d, v, vocab = 128, 128, 384, 380
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (v, d), jnp.float32) * 0.1
    t = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, vocab)

    def loss_fused(x, w):
        return jnp.mean(linear_cross_entropy(x, w, t, vocab))

    def loss_ref(x, w):
        return jnp.mean(_ce_reference(x, w, t, vocab)[0])

    gx, gw = jax.grad(loss_fused, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               atol=1e-5, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               atol=1e-5, rtol=1e-3)


def test_fused_ce_bf16():
    from ray_tpu.ops.fused_ce import linear_cross_entropy, _ce_reference

    n, d, v, vocab = 128, 128, 384, 384
    x = (jax.random.normal(jax.random.PRNGKey(6), (n, d), jnp.float32)
         ).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(7), (v, d), jnp.float32)
         * 0.1).astype(jnp.bfloat16)
    t = jax.random.randint(jax.random.PRNGKey(8), (n,), 0, vocab)
    loss = linear_cross_entropy(x, w, t, vocab)
    ref, _ = _ce_reference(x, w, t, vocab)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)
    gx, gw = jax.grad(lambda a, b: jnp.mean(
        linear_cross_entropy(a, b, t, vocab)), argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda a, b: jnp.mean(
        _ce_reference(a, b, t, vocab)[0]), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(rx, np.float32),
                               atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rw, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_fused_ce_padded_rows_masked_when_block_divides_vocab():
    """vocab_size a multiple of the chosen block must still mask padding
    rows (regression: mask was gated on vocab_size % block_v != 0)."""
    from ray_tpu.ops.fused_ce import linear_cross_entropy, _ce_reference

    n, d, v, vocab = 128, 128, 768, 384  # _pick_block_v(768)=384 divides
    x = jax.random.normal(jax.random.PRNGKey(9), (n, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(10), (v, d),
                          jnp.float32) * 0.1
    t = jax.random.randint(jax.random.PRNGKey(11), (n,), 0, vocab)
    loss = linear_cross_entropy(x, w, t, vocab)
    ref, _ = _ce_reference(x, w, t, vocab)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def _ce_case(dtype, vocab):
    """Four row blocks of 64 and two vocabulary blocks of 384."""
    n, d, v = 256, 128, 768
    key = jax.random.split(jax.random.PRNGKey(12), 4)
    x = jax.random.normal(key[0], (n, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(key[1], (v, d), jnp.float32) * 0.1).astype(dtype)
    t = jax.random.randint(key[2], (n,), 0, vocab)
    g = jax.random.uniform(key[3], (n,), jnp.float32) / n
    return x, w, t, g


@pytest.mark.parametrize("dtype,vocab,super_blocks", [
    (jnp.float32, 700, 1), (jnp.float32, 768, 1), (jnp.bfloat16, 700, 1),
    (jnp.bfloat16, 768, 1), (jnp.float32, 700, 2), (jnp.bfloat16, 700, 4)],
    ids=["f32-padded", "f32", "bf16-padded", "bf16", "f32-over-budget",
         "bf16-far-over-budget"])
def test_fused_ce_bwd_makes_p_once_and_matches_reference(dtype, vocab,
                                                         super_blocks):
    """dx and dW from ONE P, whole or a super-block of rows at a time
    under a budget its bytes pass, against the reference's gradients."""
    from ray_tpu.ops import fused_ce

    x, w, t, g = _ce_case(dtype, vocab)
    (n, _d), v = x.shape, w.shape[0]
    budget = n // super_blocks * v * x.dtype.itemsize
    assert fused_ce._super_rows(n, v, x.dtype.itemsize, 64,
                                budget) == n // super_blocks
    (_, lse), vjp = jax.vjp(
        lambda a, b: fused_ce._ce_reference(a, b, t, vocab), x, w)
    rx, rw = vjp((g, jnp.zeros_like(lse)))
    dx, dw = fused_ce._ce_bwd_pallas(x, w, t, lse, g, vocab, 64, 384, True,
                                     p_budget_bytes=budget)
    assert dx.dtype == dtype and dw.dtype == jnp.float32
    tol = dict(atol=5e-2 / n, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-5 / n, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(rx, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw, np.float32),
                               **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ce_dx_writes_the_tile_of_p(dtype):
    """The P^T that `fused_ce_dw` reads: nothing in the padded columns of
    P (no mask is left in dW's kernel), a distribution in every row."""
    from ray_tpu.ops import fused_ce

    vocab = 700
    x, w, t, _g = _ce_case(dtype, vocab)
    _, lse = fused_ce._ce_reference(x, w, t, vocab)
    lse_b = jnp.broadcast_to(lse[:, None], (x.shape[0], fused_ce._LANES))
    _dx, pt = fused_ce._ce_dx_pallas(x, w, lse_b, vocab, 64, 384, True)
    assert pt.shape == (w.shape[0], x.shape[0]) and pt.dtype == dtype
    p = np.asarray(pt, np.float32).T
    assert not p[:, vocab:].any() and (p[:, :vocab] > 0).all()
    # each entry is rounded to the dtype once: 8 bits of mantissa in bf16
    np.testing.assert_allclose(p.sum(axis=1), 1.0,
                               atol=2 ** -8 if dtype == jnp.bfloat16
                               else 1e-5)


def test_fused_ce_backward_form_in_kernel_choices():
    from ray_tpu.ops import dispatch, fused_ce

    x, w, t, _g = _ce_case(jnp.bfloat16, 700)
    dispatch.reset_kernel_choices()
    loss = lambda a, b: jnp.mean(fused_ce.linear_cross_entropy(a, b, t, 700))
    jax.eval_shape(loss, x, w)  # the forward alone: no backward to name
    (fwd,) = dispatch.kernel_choices("linear_cross_entropy")
    assert fwd["choice"] == "pallas" and "backward" not in fwd
    jax.eval_shape(jax.grad(loss, argnums=(0, 1)), x, w)
    (both,) = dispatch.kernel_choices("linear_cross_entropy")
    assert {k: both[k] for k in fwd} == fwd
    assert both["backward"] == {"p_bytes": 256 * 768 * 2, "super_blocks": 1}


def test_flash_fused_bwd_matches_two_pass(monkeypatch):
    """The fused single-pass backward (dq revisiting-accumulator) must
    match the two-pass backward and the XLA reference gradient."""
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 3, 64
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=128, block_k=128
                               ).astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=True
                             ).astype(jnp.float32).sum()

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("RAY_TPU_FLASH_FUSED_BWD", "0")
    g_two = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("RAY_TPU_FLASH_FUSED_BWD", "1")
    g_fused = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, c, name in zip(g_fused, g_two, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"fused vs two-pass d{name}")
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"fused vs reference d{name}")


def test_flash_fused_bwd_uneven_and_noncausal(monkeypatch):
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    rng = np.random.default_rng(1)
    B, H, D = 1, 2, 64
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    for fused in ("1", "0"):  # the tq<tk causal case was silently wrong
        monkeypatch.setenv("RAY_TPU_FLASH_FUSED_BWD", fused)
        _check_uneven_cases(rng, B, H, D)


def _check_uneven_cases(rng, B, H, D):
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    for tq, tk, causal in ((128, 384, True), (256, 256, False)):
        q = jnp.asarray(rng.standard_normal((B, tq, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, tk, H, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, tk, H, D)), jnp.float32)

        def loss_flash(q, k, v, causal=causal):
            return flash_attention(q, k, v, causal=causal,
                                   block_q=128, block_k=128
                                   ).astype(jnp.float32).sum()

        def loss_ref(q, k, v, causal=causal):
            return mha_reference(q, k, v, causal=causal
                                 ).astype(jnp.float32).sum()

        g_fused = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, c, name in zip(g_fused, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), rtol=2e-3, atol=2e-3,
                err_msg=f"tq={tq} tk={tk} causal={causal} d{name}")


def _share_by_elements(tq, tk, blocks, causal):
    """`executed_block_share` the slow way, from the mask's elements: a
    block of query rows visits the keys up to the last one any of its rows
    sees; a block of keys is visited from the first query row that sees
    any of it."""
    keep = np.tril(np.ones((tq, tk), bool), k=tk - tq) if causal \
        else np.ones((tq, tk), bool)
    visited = 0
    for bq in (blocks.fwd[0], blocks.dq[0]):
        for first in range(0, tq, bq):
            cols = np.flatnonzero(keep[first:first + bq].any(axis=0))
            visited += bq * (cols.max() + 1 if cols.size else 0)
    bk = blocks.dkv[1]
    for first in range(0, tk, bk):
        rows = np.flatnonzero(keep[:, first:first + bk].any(axis=1))
        visited += bk * (tq - rows.min() if rows.size else 0)
    return visited / (3.0 * tq * tk)


# (batch, tq, tk, heads, head_dim, causal): the training cell's shape, a
# decode-style tq != tk, heads of 128, a length only 128 divides, a
# non-causal call, a per-shard piece with an odd head count, float32
_RULE_SHAPES = [
    (32, 1024, 1024, 12, 64, True, jnp.bfloat16),
    (2, 512, 1024, 4, 64, True, jnp.bfloat16),
    (2, 2048, 2048, 4, 128, True, jnp.bfloat16),
    (1, 640, 640, 2, 64, True, jnp.bfloat16),
    (2, 1024, 1024, 2, 64, False, jnp.bfloat16),
    (8, 1024, 1024, 3, 64, True, jnp.bfloat16),
    (1, 1024, 1024, 2, 64, True, jnp.float32),
]


@pytest.mark.parametrize("b,tq,tk,h,d,causal,dtype", _RULE_SHAPES)
def test_flash_blocks_chosen_from_shape(b, tq, tk, h, d, causal, dtype):
    """`choose_blocks` gives every kernel a pair that divides its lengths,
    and the traced call records that pair and the share of the square it
    computes; nothing but the shape is read."""
    from ray_tpu.ops import attention, dispatch

    blocks = attention.choose_blocks(tq, tk, d, causal, dtype)
    assert set(blocks._fields) == {"fwd", "dq", "dkv"}
    for bq, bk in blocks:
        assert bq % 128 == 0 and bk % 128 == 0
        assert tq % bq == 0 and tk % bk == 0
    share = attention.executed_block_share(tq, tk, blocks, causal)
    assert share == pytest.approx(_share_by_elements(tq, tk, blocks, causal))
    if not causal:
        assert share == 1.0
    elif tq == tk and tq >= 1024:
        # the skipping engages where the training cell runs
        assert share < 1.0

    dispatch.reset_kernel_choices()
    q = jax.ShapeDtypeStruct((b, tq, h, d), dtype)
    kv = jax.ShapeDtypeStruct((b, tk, h, d), dtype)
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal),
                   q, kv, kv)
    (rec,) = dispatch.kernel_choices("flash_attention")
    assert rec["choice"] == "pallas" and rec["shape"] == (b, tq, h, d, tk)
    assert rec["blocks"] == blocks._asdict()
    assert rec["executed_block_share"] == pytest.approx(share)


def test_flash_pinned_blocks_are_recorded():
    """A test that pins block_q/block_k gives the pair to all three
    kernels, and the record says so."""
    from ray_tpu.ops import dispatch

    dispatch.reset_kernel_choices()
    q = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32)
    jax.eval_shape(lambda q: flash_attention(q, q, q, True, None, 128, 256),
                   q)
    (rec,) = dispatch.kernel_choices("flash_attention")
    assert rec["blocks"] == {"fwd": (128, 256), "dq": (128, 256),
                             "dkv": (128, 256)}
    # query blocks of 128 visit 128, 256, 384, 512 keys (fwd and dq: 5/8 of
    # the square); key blocks of 256 are visited by 512 and 256 rows (3/4)
    assert rec["executed_block_share"] == pytest.approx((5 / 8 + 5 / 8 + 3 / 4) / 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_rule_blocks_match_reference_at_1024(dtype):
    """Forward and the three gradients at the training cell's length with
    the rule's own blocks (none passed): the skipped blocks and the
    mask-free loop under the diagonal change no number."""
    from ray_tpu.ops import dispatch

    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 1, 1024, 1024, 2, 64, dtype)

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v, True)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    dispatch.reset_kernel_choices()
    (_, out), grads = loss(flash_attention)(q, k, v)
    (_, ref), grads_ref = loss(mha_reference)(q, k, v)
    (rec,) = dispatch.kernel_choices("flash_attention")
    assert rec["choice"] == "pallas" and rec["executed_block_share"] < 1.0
    tol = dict(atol=6e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    for g, gr, name in zip(grads, grads_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(gr, np.float32),
            err_msg=f"d{name}", **tol)
