"""Flash attention for TPU: Pallas kernels (MXU-tiled, online softmax),
forward AND backward.

New capability relative to the reference (which has no kernels of its own —
SURVEY.md §5.7); the design follows the standard blockwise-softmax flash
attention recipe mapped onto TPU constraints from the Pallas guide:
128-aligned q/kv blocks feeding the 128x128 MXU, fp32 accumulators, causal
masking via broadcasted_iota, and fully-masked-block skipping so causal
attention does about half the FLOPs.

The backward pass is two Pallas kernels (the FlashAttention-2 recipe):
- dq kernel: grid over q blocks, walk over kv windows;
- dkv kernel: grid over kv blocks, walk over windows of q rows;
both recompute P = exp(S - L) from the forward's saved logsumexp L (one
float32 a row, [B, H, T]) and make the row term D = rowsum(dO * O) from
the rows they hold.

What chooses the tiling (PR 36; nothing is read from the environment):
- Layout, from the heads: the kernels read the caller's `[B, T, H, D]`
  as `[B, T, H*D]`, two heads of 64 (or one of 128) a 128-lane block, so
  nothing is transposed or lane-padded around a call; a group's heads are
  stacked along the rows of one product (`_stack_heads`). Heads of 64 in
  odd number keep the transposed `[B*H, T, D]`.
- Blocks, from the lengths, the head width, causal and the dtype:
  `choose_blocks` gives each kernel its (block_q, block_k), the pairs a
  sweep on the chip chose (PERF.md section 6, PR 36), cut to what divides
  the lengths. A kernel's grid block and the most it visits of the other
  sequence at a time are that pair.
- The walk, from the block's place: a causal call lays each grid block's
  walk out in Python (`_per_block`, `_windows`), so every bound is static:
  windows the mask hides are not visited (`executed_block_share`, which
  the recorded choice carries beside the blocks), and only the lanes the
  diagonal crosses take the masked path (`_masked`).

`flash_attention` dispatches: Pallas kernel on TPU backends (or in
interpret mode, which is how CPU CI tests the hardware code path), jnp
reference otherwise. The choice per traced shape is recorded in
`ops.dispatch.kernel_choices()`; under an ambient mesh the kernels run per
shard over the batch and head axes (`ops.dispatch.per_shard`).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import dispatch

_LANES = 8  # the fused backward alone still takes LSE/D broadcast over a
#             small minor dim. It saves nothing in HBM: XLA tiles such an
#             array T(8,128), so the 8 is padded to 128 on the chip
#             (compiled HLO, PR 21), which is why the other kernels keep
#             one float32 a row, along the lanes
_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # kernels work in log2 domain: exp2 is the
_LN2 = 0.6931471805599453    # cheap VPU transcendental; scale*log2(e) is
#                              folded into q so softmax needs only exp2.


def _grid_params(interpret: bool, *semantics: str):
    """Mosaic dimension_semantics for a grid, one name an axis. An axis
    is "parallel" when no program carries state to the next, which lets
    the compiler software-pipeline block DMA against compute; the fused
    backward accumulates dq across its block axis, which is therefore
    "arbitrary" (run in order). None in interpret mode."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  sm_scale: Optional[float] = None) -> jax.Array:
    """Plain XLA multi-head attention. q,k,v: [B, T, H, D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), k=tk - tq)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ------------------------------------------------------- kernel layout
#
# The kernels read `[B', T, G*W]`: a grid cell is one batch row, one
# 128-lane (or narrower) group of heads and one block of rows. Where the
# heads allow it that is the caller's `[B, T, H, D]` itself, reshaped for
# free to `[B, T, H*D]`: heads of 128 (or a multiple) are a lane group
# each, heads of 64 go two a group, so that no array is transposed or
# lane-padded on its way in or out. Any other shape (heads of 64 in odd
# number) is transposed to `[B*H, T, D]`, one head a group, as every shape
# was before PR 36. The row statistic (the logsumexp) is `[B', G, heads, T]`
# float32: a row of lanes a head, which the dkv kernel reads as it lies.


def _kernel_layout(num_heads: int, head_dim: int) -> Tuple[bool, int]:
    """(packed, heads a lane group): whether `[B, T, H, D]` is read as it
    lies, and how many heads then share a group (1 in the transposed
    layout)."""
    if head_dim % 128 == 0:
        return True, 1
    if head_dim == 64 and num_heads % 2 == 0:
        return True, 2
    return False, 1


def _to_kernel(x, packed: bool):
    """[B, T, H, D] -> the kernels' `[B', T, G*W]` (see above)."""
    b, t, h, d = x.shape
    if packed:
        return x.reshape(b, t, h * d)
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_kernel(x, shape, packed: bool):
    """The inverse of `_to_kernel` for an array of `shape` [B, T, H, D]."""
    b, t, h, d = shape
    if packed:
        return x.reshape(b, t, h, d)
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _own_lanes(width: int, heads: int, h: int):
    """[1, width] bool: the lanes of the group that belong to its head h."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    d = width // heads
    return (lane >= h * d) & (lane < (h + 1) * d)


def _stack_heads(x, heads: int):
    """[rows, W] float32 -> [heads*rows, W]: row group h keeps head h's
    lanes and is zero elsewhere, so a contraction over all W lanes is head
    h's alone, and one product serves the group's heads with `heads` times
    the rows a weight tile is streamed against."""
    if heads == 1:
        return x
    return jnp.concatenate(
        [jnp.where(_own_lanes(x.shape[-1], heads, h), x, 0.0)
         for h in range(heads)], axis=0)


def _unstack_heads(x, heads: int):
    """[heads*rows, W] -> [rows, W]: head h's lanes from row group h."""
    if heads == 1:
        return x
    rows = x.shape[0] // heads
    out = x[:rows]
    for h in range(1, heads):
        out = jnp.where(_own_lanes(x.shape[-1], heads, h),
                        x[h * rows:(h + 1) * rows], out)
    return out


def _stacked_rows(ref, heads: int):
    """A row statistic `ref` [heads, rows] -> [heads*rows, 1], in the row
    order of `_stack_heads`."""
    return jnp.concatenate([ref[h:h + 1, :].T for h in range(heads)], axis=0)


# A program's walk over the other sequence is laid out in Python, per
# block of the grid (`_per_block`): which windows of it the block's rows
# see at all, and which part of a window the diagonal crosses. Every
# bound is then static: a window wholly hidden by the mask is never
# visited, one wholly visible takes no mask, and only the lanes the
# diagonal crosses pay the iota, the compare and the select.


class _Window(NamedTuple):
    start: int      # first position of the other sequence
    width: int
    mask: Optional[Tuple[int, int]]  # [lo, hi) of the width the mask cuts


def _windows(visible: Tuple[int, int], crossed: Tuple[int, int],
             step: int) -> Tuple[_Window, ...]:
    """`visible` [lo, hi) cut into windows of at most `step`; `crossed`
    is the part of it the diagonal crosses (elsewhere all is seen)."""
    out = []
    for start in range(visible[0], visible[1], step):
        width = min(step, visible[1] - start)
        lo = max(crossed[0], start) - start
        hi = min(crossed[1], start + width) - start
        out.append(_Window(start, width, (lo, hi) if hi > lo else None))
    return tuple(out)


def _visit(windows: Tuple[_Window, ...], body, carry):
    """carry = body(carry, start, width, mask) over the windows in order;
    a run of unmasked windows of one width becomes ONE loop (its start
    traced), so that a long sequence does not unroll into the program."""
    i = 0
    while i < len(windows):
        w = windows[i]
        run = 1
        while (w.mask is None and i + run < len(windows)
               and windows[i + run] == w._replace(
                   start=w.start + run * w.width)):
            run += 1
        if run > 2:
            carry = jax.lax.fori_loop(
                0, run,
                lambda j, c, w=w: body(c, w.start + j * w.width, w.width,
                                       None),
                carry)
        else:
            run = 1
            carry = body(carry, w.start, w.width, w.mask)
        i += run
    return carry


def _masked(x, mask: Optional[Tuple[int, int]], origin, sign: int,
            heads: int):
    """x [heads*R, width] with the lanes [lo, hi) of `mask` cut by the
    causal mask; the other lanes as they are. `origin` is lane 0's
    position less row 0's (rows within their head's R): with sign +1 a
    row sees the lanes at or before its own position, with -1 a lane
    sees the rows at or before its own."""
    if mask is None:
        return x
    lo, hi = mask
    slab = (x.shape[0] // heads, hi - lo)
    r = jax.lax.broadcasted_iota(jnp.int32, slab, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, slab, 1)
    keep = sign * (r - c - (origin + lo)) >= 0
    if heads > 1:
        keep = jnp.concatenate([keep] * heads, axis=0)
    parts = [x[:, :lo]] if lo else []
    parts.append(jnp.where(keep, x[:, lo:hi], _NEG_INF))
    if hi < x.shape[1]:
        parts.append(x[:, hi:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _key_windows(qi, causal: bool, block_q: int, block_k: int, q_len: int,
                 kv_len: int):
    """(first, windows) for q block `qi`: the key position its first row
    sees last (queries are aligned to the END of the keys, decode-style,
    matching mha_reference's tril(k=tk-tq)) and the windows of at most
    block_k keys its rows see."""
    if not causal:
        return 0, _windows((0, kv_len), (0, 0), block_k)
    first = kv_len - q_len + qi * block_q
    seen = max(0, min(kv_len, first + block_q))
    return first, _windows((0, seen), (max(first, 0), seen), block_k)


def _per_block(axis: int, blocks: int, causal: bool, program):
    """Run `program(i)` for this grid cell's block `i` along `axis`, with
    `i` a Python int when the walk depends on it (a causal call)."""
    if not causal or blocks == 1:
        program(0 if blocks == 1 else None)
        return
    for i in range(blocks):
        pl.when(pl.program_id(axis) == i)(functools.partial(program, i))


# --------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, q_len: int, kv_len: int, heads: int):
    """One (batch row, head group, q block) program; walks the KV windows
    its rows see with online softmax. Refs: q/o [block_q, W], k/v [kv_len,
    W], lse [heads, block_q] (the logsumexp, a row of lanes a head). The
    group's heads are stacked along the rows (`_stack_heads`)."""
    cd = q_ref.dtype

    def program(qi):
        # log2-domain: fold sm_scale*log2(e) into q; softmax uses exp2
        # only. Matmul operands stay in the input dtype (bf16 on the fast
        # path — f32 MXU passes are ~6x slower); accumulation is f32.
        q = _stack_heads(
            q_ref[...].astype(jnp.float32) * (sm_scale * _LOG2E),
            heads).astype(cd)
        rows, width = q.shape
        first, windows = _key_windows(qi, causal, block_q, block_k, q_len,
                                      kv_len)

        def body(carry, start, width_k, mask):
            m_prev, l_prev, acc = carry
            k_blk = k_ref[pl.ds(start, width_k), :]
            v_blk = v_ref[pl.ds(start, width_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [rows, width_k]
            # row r sees key first + r and those before it
            s = _masked(s, mask, start - first, 1, heads)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(cd), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        m, l, acc = _visit(windows, body, (
            jnp.full((rows, 1), _NEG_INF, dtype=jnp.float32),
            jnp.zeros((rows, 1), dtype=jnp.float32),
            jnp.zeros((rows, width), dtype=jnp.float32)))
        # Fully-masked rows (l == 0) only occur with kv_len < q_len.
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[...] = _unstack_heads(acc / l_safe, heads).astype(o_ref.dtype)
        # natural-log LSE for the API: ln(sum exp(s_nat - 0)) recovered
        # from the log2-domain running (m, l).
        lse = (m + jnp.log2(l_safe)) * _LN2  # [heads*block_q, 1]
        for h in range(heads):
            lse_ref[h:h + 1, :] = lse[h * block_q:(h + 1) * block_q].T

    _per_block(2, q_len // block_q, causal, program)


def _specs(width: int, heads: int):
    """BlockSpec makers for a (batch row, head group, block) grid over the
    kernel layout: an operand cut into blocks of rows along the grid's last
    axis, or held whole; `stat` for the row statistics."""
    def data(rows: int, blocked: bool):
        return pl.BlockSpec(
            (None, rows, width),
            (lambda b, g, i: (b, i, g)) if blocked
            else (lambda b, g, i: (b, 0, g)))

    def stat(rows: int, blocked: bool):
        return pl.BlockSpec(
            (None, None, heads, rows),
            (lambda b, g, i: (b, g, 0, i)) if blocked
            else (lambda b, g, i: (b, g, 0, 0)))

    return data, stat


def _fwd_pallas(qf, kf, vf, *, heads: int, width: int, causal: bool,
                sm_scale: float, block_q: int, block_k: int, interpret: bool):
    """(out, lse) over operands in the kernel layout: `heads` heads share
    each `width`-lane group."""
    bsz, tq, lanes = qf.shape
    tk = kf.shape[1]
    groups = lanes // width
    data, stat = _specs(width, heads)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, q_len=tq, kv_len=tk, heads=heads)
    return pl.pallas_call(
        kernel,
        grid=(bsz, groups, pl.cdiv(tq, block_q)),
        in_specs=[data(block_q, True), data(tk, False), data(tk, False)],
        out_specs=[data(block_q, True), stat(block_q, True)],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, qf.dtype),
            jax.ShapeDtypeStruct((bsz, groups, heads, tq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        compiler_params=_grid_params(interpret, *("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=4 * bsz * tq * tk * lanes,
            bytes_accessed=(qf.size + kf.size + vf.size) * qf.dtype.itemsize,
            transcendentals=bsz * groups * heads * tq * tk),
    )(qf, kf, vf)


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool):
    b, tq, h, d = q.shape
    packed, heads = _kernel_layout(h, d)
    out, lse = _fwd_pallas(
        *(_to_kernel(x, packed) for x in (q, k, v)), heads=heads,
        width=heads * d, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    # lse leaves as [B, H, T] so per_shard can split batch and heads
    return _from_kernel(out, q.shape, packed), lse.reshape(b, h, tq)


# -------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, q_len: int,
                         kv_len: int, heads: int):
    """dQ for one q block: walks the kv windows its rows see.
    Refs: q/o/do/dq [block_q, W], k/v [kv_len, W], lse [heads, block_q];
    the group's heads stacked along the rows as in the forward.
    The softmax correction term rowsum(dO * O) is made here, from the
    block's own rows."""
    cd = q_ref.dtype

    def program(qi):
        q = _stack_heads(
            q_ref[...].astype(jnp.float32) * (sm_scale * _LOG2E),
            heads).astype(cd)
        do = _stack_heads(do_ref[...].astype(jnp.float32), heads)
        o = o_ref[...].astype(jnp.float32)
        dcor = jnp.sum(  # each row group's own lanes alone are not zero
            do * (o if heads == 1 else jnp.concatenate([o] * heads, axis=0)),
            axis=-1, keepdims=True)                    # [rows, 1]
        do = do.astype(cd)
        lse2 = _stacked_rows(lse_ref, heads) * _LOG2E  # [rows, 1], log2
        rows, width = q.shape
        first, windows = _key_windows(qi, causal, block_q, block_k, q_len,
                                      kv_len)

        def body(dq_acc, start, width_k, mask):
            k_blk = k_ref[pl.ds(start, width_k), :]
            v_blk = v_ref[pl.ds(start, width_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # row r sees key first + r and those before it
            s = _masked(s, mask, start - first, 1, heads)
            p = jnp.exp2(s - lse2)                    # [rows, width_k]
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [rows, width_k]
            ds = (p * (dp - dcor)).astype(cd)
            return dq_acc + jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq = _visit(windows, body, jnp.zeros((rows, width), jnp.float32))
        dq_ref[...] = (_unstack_heads(dq, heads)
                       * sm_scale).astype(dq_ref.dtype)

    _per_block(2, q_len // block_q, causal, program)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                          block_q: int, block_k: int, q_len: int,
                          kv_len: int, heads: int):
    """dK/dV for one kv block: walks the windows of q rows that see it.
    Refs: k/v/dk/dv [block_k, W], q/o/do [q_len, W], lse [heads, q_len];
    the group's heads stacked along the KEY rows; rowsum(dO * O) made per
    window of q rows.
    """
    cd = k_ref.dtype

    def program(ki):
        k_scaled = _stack_heads(
            k_ref[...].astype(jnp.float32) * (sm_scale * _LOG2E),
            heads).astype(cd)
        v_blk = _stack_heads(v_ref[...].astype(jnp.float32),
                             heads).astype(cd)
        rows, width = k_scaled.shape
        first = 0  # the first q row that sees this block's first key
        windows = _windows((0, q_len), (0, 0), block_q)
        if causal:
            first = ki * block_k - (kv_len - q_len)
            lo = min(q_len, max(0, first))
            # rows from first + block_k on see the whole block
            windows = _windows(
                (lo, q_len), (lo, min(q_len, max(lo, first + block_k))),
                block_q)

        def per_head(x, stat_t):
            """x [heads*block_k, width_q] less each head's own row
            statistic (`stat_t[h]` is [1, width_q])."""
            return [x[h * block_k:(h + 1) * block_k] - stat_t[h]
                    for h in range(heads)]

        def body(carry, start, width_q, mask):
            dk_acc, dv_acc = carry
            sl = pl.ds(start, width_q)
            q_blk = q_ref[sl, :]
            do_blk = do_ref[sl, :]
            lse2_t = [lse_ref[h:h + 1, sl] * _LOG2E for h in range(heads)]
            doo = do_blk.astype(jnp.float32) * o_ref[sl, :].astype(jnp.float32)
            dcor_t = [jnp.sum(
                doo if heads == 1 else jnp.where(
                    _own_lanes(width, heads, h), doo, 0.0),
                axis=-1, keepdims=True).T for h in range(heads)]
            # s^T: [rows, width_q] = (K*scale*log2e) Q^T, log2 domain
            st = jax.lax.dot_general(
                k_scaled, q_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # key row r is seen by q row first + r and those after it
            st = _masked(st, mask, start - first, -1, heads)
            pt = [jnp.exp2(x) for x in per_head(st, lse2_t)]
            # dp^T = V dO^T : [rows, width_q]
            dpt = jax.lax.dot_general(
                v_blk, do_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = [p * x for p, x in zip(pt, per_head(dpt, dcor_t))]
            # dv += P^T dO
            dv_acc = dv_acc + jax.lax.dot_general(
                jnp.concatenate(pt, axis=0).astype(cd), do_blk,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # dk += dS^T (Q*scale)  (the sm_scale factor rides on
            # k_scaled's partner: dK = scale * dS^T Q, q_blk is unscaled)
            dk_acc = dk_acc + jax.lax.dot_general(
                jnp.concatenate(dst, axis=0).astype(cd), q_blk,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        dk, dv = _visit(windows, body, (
            jnp.zeros((rows, width), jnp.float32),
            jnp.zeros((rows, width), jnp.float32)))
        dk_ref[...] = (_unstack_heads(dk, heads)
                       * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = _unstack_heads(dv, heads).astype(dv_ref.dtype)

    _per_block(2, kv_len // block_k, causal, program)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcor_ref,
                            dk_ref, dv_ref, dq_ref, *, sm_scale: float,
                            causal: bool, block_q: int, block_k: int,
                            q_len: int, q_offset: int):
    """Single-pass backward: one grid cell = one kv block, computing its
    dK/dV AND this block's dQ contributions. The two-pass backward
    recomputes S twice (7 dots per q-kv pair); this computes it once
    (5 dots) and halves the Q/dO HBM traffic. dq is a REVISITED output
    ([q_len, D] f32, index ignoring ki): the ki grid axis is "arbitrary"
    (run in order), so cell (g, ki) accumulates onto what (g, ki-1)
    wrote — the standard TPU revisiting-accumulator pattern.
    Refs: k/v/dk/dv [block_k, D]; q/do [q_len, D]; dq [q_len, D] f32;
    lse/dcor [q_len, LANES]."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    cd = k_ref.dtype
    k_scaled = (k_ref[...].astype(jnp.float32)
                * (sm_scale * _LOG2E)).astype(cd)
    k_raw = k_ref[...]
    v_blk = v_ref[...]
    d = k_scaled.shape[-1]

    num_q_blocks = pl.cdiv(q_len, block_q)
    start_q = 0
    first_full_q = 0
    if causal:
        start_q = jnp.maximum(0, (ki * block_k - q_offset) // block_q)
        # clamp below at 0 — see _flash_bwd_dkv_kernel: negative numerator
        # (tq < tk) must not start the unmasked loop at qi=-1
        first_full_q = jnp.minimum(
            num_q_blocks,
            jnp.maximum(0, (ki * block_k + block_k - 1 - q_offset
                            + block_q - 1) // block_q))

    def body(qi, carry, apply_mask):
        dk_acc, dv_acc = carry
        sl = pl.ds(qi * block_q, block_q)
        q_blk = q_ref[sl, :]
        do_blk = do_ref[sl, :]
        lse2 = lse_ref[sl, :1] * _LOG2E
        dcor = dcor_ref[sl, :1]
        st = jax.lax.dot_general(
            k_scaled, q_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # [block_k, block_q]
        if apply_mask:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp2(st - lse2.T)                # [block_k, block_q]
        dv_acc = dv_acc + jax.lax.dot_general(
            pt.astype(cd), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v_blk, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # [block_k, block_q]
        dst = (pt * (dpt - dcor.T)).astype(cd)    # dS^T
        dk_acc = dk_acc + jax.lax.dot_general(
            dst, q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dQ[q_blk] += scale * dS K  (dst^T @ K via contracting dim 0)
        dq_contrib = jax.lax.dot_general(
            dst, k_raw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # [block_q, D]
        dq_ref[sl, :] = dq_ref[sl, :] + dq_contrib * sm_scale
        return dk_acc, dv_acc

    carry = jax.lax.fori_loop(
        start_q, first_full_q, functools.partial(body, apply_mask=True),
        (jnp.zeros((k_scaled.shape[0], d), jnp.float32),
         jnp.zeros((k_scaled.shape[0], d), jnp.float32)))
    dk, dv = jax.lax.fori_loop(
        first_full_q, num_q_blocks,
        functools.partial(body, apply_mask=False), carry)
    dk_ref[...] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _fused_bwd_enabled() -> bool:
    """Opt-in until profiled on real chips (RAY_TPU_FLASH_FUSED_BWD=1);
    interpret-mode tests pin its numerics against the two-pass path."""
    return os.environ.get("RAY_TPU_FLASH_FUSED_BWD", "0") == "1"


def _flash_bwd_fused_pallas(q, k, v, o, lse, do, causal: bool,
                            sm_scale: float, block_q: int, block_k: int,
                            interpret: bool):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    lse = jnp.broadcast_to(lse.reshape(b * h, tq, 1), (b * h, tq, _LANES))
    qf, kf, vf, of, dof = (_to_kernel(x, False) for x in (q, k, v, o, do))
    dcor = jnp.broadcast_to(
        jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1,
                keepdims=True),
        (b * h, tq, _LANES))
    kernel = functools.partial(
        _flash_bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_len=tq, q_offset=tk - tq)
    dkf, dvf, dqf = pl.pallas_call(
        kernel,
        grid=(b * h, pl.cdiv(tk, block_k)),
        in_specs=[
            pl.BlockSpec((None, tq, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, tq, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, tq, _LANES), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, tq, _LANES), lambda g, i: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda g, i: (g, i, 0)),
            # dq: revisited across ki (index ignores i) — accumulator
            pl.BlockSpec((None, tq, d), lambda g, i: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
            jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
        compiler_params=_grid_params(interpret, "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=10 * b * h * tq * tk * d,
            bytes_accessed=(qf.size + kf.size + vf.size + dof.size)
            * qf.dtype.itemsize,
            transcendentals=b * h * tq * tk),
    )(qf, kf, vf, dof, lse, dcor)
    dq = dqf.astype(q.dtype).reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    dk = dkf.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    dv = dvf.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


def _dq_pallas(qf, kf, vf, of, dof, lse, *, heads: int, width: int,
               causal: bool, sm_scale: float, block_q: int, block_k: int,
               interpret: bool):
    """dQ over operands in the kernel layout, as `_fwd_pallas`; lse
    [B', G, heads, T]."""
    bsz, tq, lanes = qf.shape
    tk = kf.shape[1]
    data, stat = _specs(width, heads)
    kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_len=tq, kv_len=tk, heads=heads)
    return pl.pallas_call(
        kernel,
        grid=(bsz, lanes // width, pl.cdiv(tq, block_q)),
        in_specs=[data(block_q, True), data(tk, False), data(tk, False),
                  data(block_q, True), data(block_q, True),
                  stat(block_q, True)],
        out_specs=data(block_q, True),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        compiler_params=_grid_params(interpret, *("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=4 * bsz * tq * tk * lanes,
            bytes_accessed=(3 * qf.size + kf.size + vf.size)
            * qf.dtype.itemsize,
            transcendentals=lse.size * tk),
    )(qf, kf, vf, of, dof, lse)


def _dkv_pallas(qf, kf, vf, of, dof, lse, *, heads: int, width: int,
                causal: bool, sm_scale: float, block_q: int, block_k: int,
                interpret: bool):
    """(dK, dV) over operands in the kernel layout, as `_dq_pallas`."""
    bsz, tq, lanes = qf.shape
    tk = kf.shape[1]
    data, stat = _specs(width, heads)
    kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_len=tq, kv_len=tk, heads=heads)
    return pl.pallas_call(
        kernel,
        grid=(bsz, lanes // width, pl.cdiv(tk, block_k)),
        in_specs=[data(tq, False), data(block_k, True), data(block_k, True),
                  data(tq, False), data(tq, False), stat(tq, False)],
        out_specs=[data(block_k, True), data(block_k, True)],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        interpret=interpret,
        name="flash_bwd_dkv",
        compiler_params=_grid_params(interpret, *("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=6 * bsz * tq * tk * lanes,
            bytes_accessed=(3 * qf.size + kf.size + vf.size)
            * qf.dtype.itemsize,
            transcendentals=lse.size * tk),
    )(qf, kf, vf, of, dof, lse)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                      blocks: "FlashBlocks", interpret: bool):
    if _fused_bwd_enabled():
        # its grid runs over key blocks, as the dkv kernel's does
        return _flash_bwd_fused_pallas(q, k, v, o, lse, do, causal,
                                       sm_scale, *blocks.dkv, interpret)
    b, tq, h, d = q.shape
    packed, heads = _kernel_layout(h, d)
    # [B, H, T] -> [B', G, heads, T]
    lse = (lse.reshape(b, h // heads, heads, tq) if packed
           else lse.reshape(b * h, 1, 1, tq))
    args = (*(_to_kernel(x, packed) for x in (q, k, v, o, do)), lse)
    common = dict(heads=heads, width=heads * d, causal=causal,
                  sm_scale=sm_scale, interpret=interpret)
    dqf = _dq_pallas(*args, block_q=blocks.dq[0], block_k=blocks.dq[1],
                     **common)
    dkf, dvf = _dkv_pallas(*args, block_q=blocks.dkv[0],
                           block_k=blocks.dkv[1], **common)
    return (_from_kernel(dqf, q.shape, packed),
            _from_kernel(dkf, k.shape, packed),
            _from_kernel(dvf, v.shape, packed))


# ------------------------------------------------------------- dispatch


class FlashBlocks(NamedTuple):
    """The (block_q, block_k) pair of each kernel. The forward's and the
    dq kernel's grids run over query blocks of block_q and they visit at
    most block_k keys at a time; the dkv kernel's grid runs over key
    blocks of block_k and it visits at most block_q query rows at a
    time."""
    fwd: Tuple[int, int]
    dq: Tuple[int, int]
    dkv: Tuple[int, int]


_WIDEST_FLOAT32 = 512  # twice the bytes a score: no block over this


def _fit(length: int, preferred: int) -> int:
    """`preferred`, halved until it divides `length` (not under 128);
    `length` itself where nothing does or it is shorter than 128
    (`_reference_reason` then refuses the call)."""
    block = preferred
    while block >= 128:
        if length % block == 0:
            return block
        block //= 2
    return length


def choose_blocks(tq: int, tk: int, head_dim: int, causal: bool,
                  dtype) -> FlashBlocks:
    """Each kernel's (block_q, block_k) for a call of these lengths.

    A causal call visits only what its grid block's rows see, so a grid
    block smaller than the sequence skips part of what the mask would
    throw away (3/4 of the square is visited at half the length, 5/8 at a
    quarter); a smaller block also pays more programs and more turns of
    the row statistics, and a wider visit more VMEM. The pairs are the
    chip's own answer (TPU v5 lite; the sweep's tables are in PERF.md
    section 6, PR 36) at the training shape `[32, 1024, 12, 64]` bf16, at
    heads of 128 over 2,048 tokens, non-causal, float32 and `tq != tk`:
    a grid block of 512 and a visit of 1,024 wherever the mask can hide
    something, the reverse where it cannot. Heads of 64 go two a block,
    so their stacked rows are twice the block's and the dkv kernel keeps
    the smaller grid block; it also holds q, o and dO of the whole
    sequence, so past 1,024 rows its visit is halved (the wider one does
    not fit VMEM at 2,048). Another length gets the nearest pair that
    divides it."""
    wide = head_dim > 64
    by_rows = (512, 1024) if causal or wide else (1024, 512)
    dkv = (512, 1024) if wide else (1024 if tq <= 1024 else 512, 512)
    most = _WIDEST_FLOAT32 if jnp.dtype(dtype).itemsize > 2 else max(tq, tk)
    return FlashBlocks(*(
        (_fit(tq, min(bq, most)), _fit(tk, min(bk, most)))
        for bq, bk in (by_rows, by_rows, dkv)))


def executed_block_share(tq: int, tk: int, blocks: FlashBlocks,
                         causal: bool) -> float:
    """The share of the [tq, tk] square the three kernels visit, mean
    over the three (1.0: the whole square, what a non-causal call needs
    and what a causal call computes when one grid block spans the
    sequence). Queries are end-aligned to the keys."""
    if not causal:
        return 1.0
    offset = tk - tq
    (fwd_q, _), (dq_q, _), (_, dkv_k) = blocks
    by_rows = [sum(min(tk, max(0, offset + first + bq)) * bq
                   for first in range(0, tq, bq)) for bq in (fwd_q, dq_q)]
    by_keys = sum((tq - min(tq, max(0, first - offset))) * dkv_k
                  for first in range(0, tk, dkv_k))
    return (sum(by_rows) + by_keys) / (3.0 * tq * tk)


def _reference_reason(q, k, blocks: FlashBlocks) -> str:
    """Why this call takes the XLA reference; "" when the kernels run.

    Sequence lengths must divide every kernel's blocks; otherwise the
    in-kernel pl.ds reads would silently clamp out-of-bounds starts and
    corrupt the causal indexing."""
    if os.environ.get("RAY_TPU_DISABLE_FLASH") == "1":  # ablation/debug escape hatch
        return "RAY_TPU_DISABLE_FLASH=1"
    if (reason := dispatch.backend_reason()):
        return reason
    tq, tk = q.shape[1], k.shape[1]
    if tq % 128 or tk % 128 or any(
            tq % bq or tk % bk for bq, bk in blocks):
        return (f"sequence lengths ({tq}, {tk}) are not multiples of 128 "
                f"and of the blocks {sorted(set(blocks))}")
    if q.shape[-1] % 128 and q.shape[-1] != 64:
        return f"head_dim {q.shape[-1]} is neither 64 nor a multiple of 128"
    return ""


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Fused attention. q,k,v: [batch, time, heads, head_dim] (kv time may
    differ). Pallas on TPU (fwd and bwd kernels); XLA reference elsewhere.
    Left None, block_q/block_k are chosen per kernel from the traced shape
    (`choose_blocks`); a test that pins one gives it to all three kernels.
    """
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)[0]


def _shard_specs(q):
    """(q/k/v spec, lse spec, shard count) for running the kernels per
    shard: attention is independent per batch row and per head."""
    b, _, h, _ = q.shape
    batch = dispatch.shard_axes(b, dispatch.DATA_AXES) or None
    head = dispatch.shard_axes(h, (dispatch.HEAD_AXIS,)) or None
    return (P(batch, None, head, None), P(batch, head, None),
            dispatch.axes_size((batch or ()) + (head or ())))


def _blocks_of(q, k, causal, block_q, block_k) -> FlashBlocks:
    tq, tk = q.shape[1], k.shape[1]
    blocks = choose_blocks(tq, tk, q.shape[-1], causal, q.dtype)
    if block_q is None and block_k is None:
        return blocks
    return FlashBlocks(*(
        (bq if block_q is None else min(block_q, tq),
         bk if block_k is None else min(block_k, tk)) for bq, bk in blocks))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    blocks = _blocks_of(q, k, causal, block_q, block_k)
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    shape = (*q.shape, k.shape[1])
    reason = _reference_reason(q, k, blocks)
    if reason:
        dispatch.record_choice("flash_attention", shape, "reference", reason)
        out = mha_reference(q, k, v, causal, scale)
        return out, (q, k, v, None, None)
    qkv_spec, lse_spec, n_shards = _shard_specs(q)
    dispatch.record_choice(
        "flash_attention", shape, "pallas", shards=n_shards,
        blocks=blocks._asdict(),
        executed_block_share=executed_block_share(
            q.shape[1], k.shape[1], blocks, causal))
    fwd = functools.partial(
        _flash_fwd_pallas, causal=causal, sm_scale=scale,
        block_q=blocks.fwd[0], block_k=blocks.fwd[1],
        interpret=dispatch.interpret_forced())
    out, lse = dispatch.per_shard(fwd, (q, k, v), (qkv_spec,) * 3,
                                  (qkv_spec, lse_spec))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, o, lse = res
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    if lse is not None:
        qkv_spec, lse_spec, _ = _shard_specs(q)
        bwd = functools.partial(
            _flash_bwd_pallas, causal=causal, sm_scale=scale,
            blocks=_blocks_of(q, k, causal, block_q, block_k),
            interpret=dispatch.interpret_forced())
        return dispatch.per_shard(
            bwd, (q, k, v, o, lse, g),
            (qkv_spec, qkv_spec, qkv_spec, qkv_spec, lse_spec, qkv_spec),
            (qkv_spec,) * 3)

    def ref(q_, k_, v_):
        return mha_reference(q_, k_, v_, causal, scale)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
