"""Fused linear + cross-entropy Pallas kernel for TPU.

The LM-head loss `CE(x @ W^T, targets)` is the memory hog of LM training:
at GPT-2 vocab the fp32 logits are ~200KB *per token row*, so a
materialized [N, V] logits tensor plus log_softmax costs gigabytes of HBM
traffic per step. This kernel never materializes logits: the vocab axis
streams through VMEM in blocks while an online logsumexp (flash-attention
style, log2 domain) and the target-logit pick run in registers.

The backward makes P = exp(logits - lse) once, from the saved
row-logsumexp: `fused_ce_dx` (over row blocks) recomputes the logits,
multiplies P by W and writes each tile of P, transposed, in the dtype
that product reads it in; `fused_ce_dw` (over vocab blocks) reads those
tiles and is one product, P^T (g x). The kernels so execute 8 N V d for
the 6 N V d the loss needs. P is `local rows x padded vocab x itemsize`
bytes: 3.30 GB at GPT-2's training shape (32,768 x 50,304 bfloat16).
Rows whose P would pass `P_BUDGET_BYTES` are walked in super-blocks that
each fit (dx then dW a super-block, dW carried from one to the next), so
the buffer is bounded by the shape and by no knob. The one-hot terms (wte
gather / segment-sum scatter) are left to XLA where they are cheap single
passes.

New capability vs the reference (no kernels of its own — SURVEY.md §5.7);
the chunked-XLA fallback (`_ce_reference`) is the correctness oracle, and
interpret-mode tests drive the kernels on CPU CI. The choice per traced
shape is recorded in `ops.dispatch.kernel_choices()`; under an ambient
mesh the kernels run per shard over the batch axes
(`ops.dispatch.per_shard`).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import dispatch

_LANES = 8
_NEG_INF = -1e30
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

# token rows per program (env override for bench sweeps)
DEFAULT_BLOCK_N = int(os.environ.get("RAY_TPU_CE_BLOCK_N", "1024"))

# The most bytes of P the backward holds at once: a quarter of the 16 GB
# of a v5e, the smallest chip trained on. GPT-2's step at 32 x 1,024 holds
# 7.4 GB without P and 10.6 GB with its 3.30 GB whole; a shape whose P is
# larger is walked in super-blocks of rows (`_super_rows`).
P_BUDGET_BYTES = 4 << 30


def _ce_reference(x: jax.Array, w: jax.Array, targets: jax.Array,
                  vocab_size: int) -> Tuple[jax.Array, jax.Array]:
    """XLA reference: per-row loss and logsumexp. x [N,d], w [V,d]."""
    logits = jnp.dot(x, w.T, preferred_element_type=jnp.float32)
    if w.shape[0] != vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(col < vocab_size, logits, _NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return lse - tgt, lse


# --------------------------------------------------------------- forward


def _ce_fwd_kernel(x_ref, w_ref, t_ref, loss_ref, lse_ref,
                   m_scr, l_scr, tgt_scr, *, block_n: int, block_v: int,
                   n_v_blocks: int, vocab_size: int, padded: bool):
    """Grid (row_block, vocab_block), vocab minor. Scratch carries the
    online (m, l, target-logit) state across vocab steps; the final step
    writes loss and lse. All logits math is log2-domain."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full((block_n, 1), _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros((block_n, 1), jnp.float32)
        tgt_scr[...] = jnp.zeros((block_n, 1), jnp.float32)

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * _LOG2E  # [block_n, block_v]
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    if padded:  # mask vocab padding rows of w
        s = jnp.where(col < vocab_size, s, _NEG_INF)
    tgt = t_ref[...]  # [block_n, 1] int32
    tgt_here = jnp.sum(jnp.where(col == tgt, s, 0.0), axis=-1,
                       keepdims=True)
    tgt_scr[...] = tgt_scr[...] + tgt_here

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p_sum = jnp.sum(jnp.exp2(s - m_new), axis=-1, keepdims=True)
    l_new = jnp.exp2(m_prev - m_new) * l_prev + p_sum
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(vi == n_v_blocks - 1)
    def _finalize():
        lse2 = m_scr[...] + jnp.log2(jnp.maximum(l_scr[...], 1e-30))
        lse_nat = lse2 * _LN2
        loss = lse_nat - tgt_scr[...] * _LN2
        loss_ref[...] = jnp.broadcast_to(loss, (block_n, _LANES))
        lse_ref[...] = jnp.broadcast_to(lse_nat, (block_n, _LANES))


def _ce_fwd_pallas(x, w, targets, vocab_size: int, block_n: int,
                   block_v: int, interpret: bool):
    n, d = x.shape
    v = w.shape[0]
    block_n = min(block_n, n)
    grid = (pl.cdiv(n, block_n), v // block_v)
    t2 = targets.astype(jnp.int32).reshape(n, 1)
    kernel = functools.partial(
        _ce_fwd_kernel, block_n=block_n, block_v=block_v,
        n_v_blocks=v // block_v, vocab_size=vocab_size,
        padded=v > vocab_size)
    loss, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_fwd",
        cost_estimate=pl.CostEstimate(
            flops=2 * n * v * d,
            bytes_accessed=(x.size * x.dtype.itemsize
                            + pl.cdiv(n, block_n) * w.size
                            * w.dtype.itemsize),
            transcendentals=n * v),
    )(x, w, t2)
    return loss[:, 0], lse[:, 0]


# -------------------------------------------------------------- backward


def _ce_dx_kernel(x_ref, w_ref, lse_ref, dx_ref, pt_ref, *, block_v: int,
                  vocab_size: int, padded: bool):
    """dx_unscaled = P @ W, streamed over vocab blocks, and P itself: every
    grid step writes its tile of P = exp2(s - lse), in the dtype the
    product reads it in and transposed, as `_ce_dw_kernel`'s left operand
    (the transpose hides under the products here; a tile `[block_n,
    block_v]` is no legal block at a block_v of 320 or 448, and a product
    over the rows of both operands there costs 1 ms of 15). Grid
    (row_block, vocab_block), vocab minor; the f32 output block is the
    accumulator (a scratch beside it would pass the 16 MB of scoped
    VMEM)."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    w = w_ref[...]
    s = jax.lax.dot_general(
        x_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * _LOG2E
    if padded:  # a padded column's P is exp2(-1e30 - lse) = 0
        col = vi * block_v + jax.lax.broadcasted_iota(
            jnp.int32, (s.shape[0], block_v), 1)
        s = jnp.where(col < vocab_size, s, _NEG_INF)
    lse2 = lse_ref[:, :1] * _LOG2E
    p = jnp.exp2(s - lse2).astype(pt_ref.dtype)
    pt_ref[...] = p.T
    dx_ref[...] = dx_ref[...] + jax.lax.dot_general(
        p, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _ce_dw_kernel(xg_ref, pt_ref, carry_ref, dw_ref):
    """dW_unscaled[v_block] = carry + P^T @ (g*x) from the tiles of P^T
    that `_ce_dx_kernel` wrote, streamed over row blocks: no logits, no
    mask (a padded column's P is 0), no exp2. Grid (vocab_block,
    row_block), rows minor; the f32 output block is the accumulator."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dw_ref[...] = carry_ref[...]

    dw_ref[...] = dw_ref[...] + jax.lax.dot_general(
        pt_ref[...], xg_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _ce_dx_pallas(x, w, lse_b, vocab_size: int, block_n: int, block_v: int,
                  interpret: bool):
    """(dx_unscaled f32 [n, d], P^T [v, n] in x's dtype) of one
    super-block's rows."""
    n, d = x.shape
    v = w.shape[0]
    itemsize = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(
            _ce_dx_kernel, block_v=block_v, vocab_size=vocab_size,
            padded=v > vocab_size),
        grid=(n // block_n, v // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, block_n), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((v, n), x.dtype),
        ],
        interpret=interpret,
        name="fused_ce_dx",
        cost_estimate=pl.CostEstimate(
            flops=4 * n * v * d,
            bytes_accessed=(n * d * (itemsize + 4) + n * v * itemsize
                            + n // block_n * w.size * w.dtype.itemsize),
            transcendentals=n * v),
    )(x, w, lse_b)


def _ce_dw_pallas(xg, pt, dw, block_n: int, block_v: int, interpret: bool):
    """dw + P^T @ xg, in dw's buffer (f32 [v, d]). The rows' operand
    first: `chip_smoke.py` reads there that a shard got its own rows."""
    v, n = pt.shape
    d = xg.shape[1]
    return pl.pallas_call(
        _ce_dw_kernel,
        grid=(v // block_v, n // block_n),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, block_n), lambda j, i: (j, i)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="fused_ce_dw",
        cost_estimate=pl.CostEstimate(
            flops=2 * n * v * d,
            bytes_accessed=(pt.size * pt.dtype.itemsize + 2 * dw.size * 4
                            + v // block_v * xg.size * xg.dtype.itemsize),
            transcendentals=0),
    )(xg, pt, dw)


def _super_rows(n: int, v: int, itemsize: int, block_n: int,
                budget: int) -> int:
    """Rows of one super-block of the backward: the most whole row blocks
    that cut `n` evenly and whose P `[rows, v]` is within `budget` bytes
    (one row block where even that is over it)."""
    blocks = n // block_n
    for k in range(1, blocks + 1):
        if blocks % k == 0 and (n // k) * v * itemsize <= budget:
            return n // k
    return block_n


def _ce_bwd_pallas(x, w, targets, lse, g, vocab_size: int, block_n: int,
                   block_v: int, interpret: bool,
                   p_budget_bytes: int = P_BUDGET_BYTES):
    """Returns (dx in x's dtype, dW in f32: the caller may still have to
    sum it over row shards). P is made once, a super-block of rows at a
    time (`_super_rows`): dx writes it, dW reads it."""
    n, d = x.shape
    v = w.shape[0]
    block_n = min(block_n, n)
    rows = _super_rows(n, v, x.dtype.itemsize, block_n, p_budget_bytes)
    lse_b = jnp.broadcast_to(lse[:, None], (n, _LANES))
    xg = (x.astype(jnp.float32) * g[:, None]).astype(x.dtype)

    def super_block(dw, blk):
        x_, lse_, xg_ = blk
        dx_, pt = _ce_dx_pallas(x_, w, lse_, vocab_size, block_n, block_v,
                                interpret)
        # on the CPU, where interpret mode inlines a one-block grid, XLA
        # folds dx's transpose into dW's product, a bf16 form its DotThunk
        # lacks; on the chip the step is 2 ms shorter with it (PERF.md 6)
        pt = jax.lax.optimization_barrier(pt)
        return _ce_dw_pallas(xg_, pt, dw, block_n, block_v, interpret), dx_

    dw = jnp.zeros((v, d), jnp.float32)
    if rows == n:
        dw, dx_unscaled = super_block(dw, (x, lse_b, xg))
    else:  # one P at a time: the loop carries dW from one to the next
        dw, dx_unscaled = jax.lax.scan(
            super_block, dw, jax.tree.map(
                lambda a: a.reshape(n // rows, rows, a.shape[1]),
                (x, lse_b, xg)))
        dx_unscaled = dx_unscaled.reshape(n, d)
    # one-hot terms and upstream scaling in XLA (cheap single passes):
    # dx -= g * W[tgt]; scatter-add dW[tgt] -= g * x
    dx = (dx_unscaled - w[targets].astype(jnp.float32)) * g[:, None]
    dw = dw.at[targets].add(-xg.astype(jnp.float32))
    return dx.astype(x.dtype), dw


# ------------------------------------------------------------- dispatch


def _pick_block_v(v: int) -> Optional[int]:
    for bv in (512, 448, 384, 320, 256, 128):
        if v % bv == 0:
            return bv
    return None


def _reference_reason(n: int, d: int, v: int) -> str:
    """Why these shapes take an XLA loss; "" when the kernels run."""
    if os.environ.get("RAY_TPU_DISABLE_FUSED_CE") == "1":  # ablation/debug escape hatch
        return "RAY_TPU_DISABLE_FUSED_CE=1"
    if (reason := dispatch.backend_reason()):
        return reason
    if dispatch.live_axes((dispatch.HEAD_AXIS,)):
        # each tp shard holds a slice of the vocabulary: a per-shard
        # kernel would see a partial softmax, a gathered one repeats the
        # whole loss on every tp shard. The partitioner's vocab-parallel
        # CE on the caller's chunked XLA loss is the right program there.
        return f"vocabulary is split over the {dispatch.HEAD_AXIS!r} axis"
    local = n // dispatch.axes_size(
        dispatch.shard_axes(n, dispatch.DATA_AXES))
    if _pick_block_v(v) is None or local % min(DEFAULT_BLOCK_N, local) \
            or local % 128 or d % 128:
        return (f"rows {local}, width {d}, vocabulary {v} do not tile "
                "into the kernel's blocks")
    return ""


def fused_ce_supported(n: int, d: int, v: int) -> bool:
    """True iff the Pallas fused path will actually run for these shapes
    on this backend — callers (models.gpt2) dispatch on this so a shape
    miss falls back to *their* chunked path, never the unchunked
    full-logit reference. A "no" is recorded with its reason."""
    reason = _reference_reason(n, d, v)
    if reason:
        dispatch.record_choice("linear_cross_entropy", (n, d, v),
                               "reference", reason)
    return not reason


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def linear_cross_entropy(x: jax.Array, w: jax.Array, targets: jax.Array,
                         vocab_size: int) -> jax.Array:
    """Per-row CE loss of logits = x @ w.T without materializing logits.

    x [N, d], w [V, d] (rows >= vocab_size are padding and masked),
    targets [N] int. Returns f32 [N]. Pallas fused kernel on TPU; chunk-
    free XLA reference elsewhere.
    """
    return _lce_fwd(x, w, targets, vocab_size)[0]


def _row_axes(n: int):
    """Batch axes the token rows are split over (None: not split)."""
    return dispatch.shard_axes(n, dispatch.DATA_AXES) or None


def _lce_fwd(x, w, targets, vocab_size):
    n, d = x.shape
    v = w.shape[0]
    use = fused_ce_supported(n, d, v)
    if use:
        rows = _row_axes(n)
        dispatch.record_choice(
            "linear_cross_entropy", (n, d, v), "pallas",
            shards=dispatch.axes_size(rows or ()))
        fwd = functools.partial(
            _ce_fwd_pallas, vocab_size=vocab_size, block_n=DEFAULT_BLOCK_N,
            block_v=_pick_block_v(v), interpret=dispatch.interpret_forced())
        loss, lse = dispatch.per_shard(
            fwd, (x, w, targets), (P(rows, None), P(None, None), P(rows)),
            (P(rows), P(rows)))
    else:
        loss, lse = _ce_reference(x, w, targets, vocab_size)
    return loss, (x, w, targets, lse, use)


def _lce_bwd(vocab_size, res, g):
    x, w, targets, lse, used_pallas = res
    if used_pallas:
        (n, d), v = x.shape, w.shape[0]
        rows = _row_axes(n)
        shards = dispatch.axes_size(rows or ())
        local = n // shards
        p_rows = _super_rows(local, v, x.dtype.itemsize,
                             min(DEFAULT_BLOCK_N, local), P_BUDGET_BYTES)
        # the forward's entry again, with what the backward holds a shard
        dispatch.record_choice(
            "linear_cross_entropy", (n, d, v), "pallas", shards=shards,
            backward={"p_bytes": p_rows * v * x.dtype.itemsize,
                      "super_blocks": local // p_rows})

        def bwd(x_, w_, t_, lse_, g_):
            dx, dw = _ce_bwd_pallas(
                x_, w_, t_, lse_, g_, vocab_size, DEFAULT_BLOCK_N,
                _pick_block_v(w.shape[0]), dispatch.interpret_forced())
            # w is whole on every shard; its gradient sums over the rows
            if rows:
                dw = jax.lax.psum(dw, rows)
            return dx, dw.astype(w_.dtype)

        dx, dw = dispatch.per_shard(
            bwd, (x, w, targets, lse, g),
            (P(rows, None), P(None, None), P(rows), P(rows), P(rows)),
            (P(rows, None), P(None, None)))
        return dx, dw, None
    # XLA fallback: differentiate the reference
    def ref(x_, w_):
        return _ce_reference(x_, w_, targets, vocab_size)[0]

    _, vjp = jax.vjp(ref, x, w)
    dx, dw = vjp(g)
    return dx, dw, None


linear_cross_entropy.defvjp(_lce_fwd, _lce_bwd)
