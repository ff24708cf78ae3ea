"""Flash attention for TPU: Pallas kernels (MXU-tiled, online softmax),
forward AND backward.

New capability relative to the reference (which has no kernels of its own —
SURVEY.md §5.7); the design follows the standard blockwise-softmax flash
attention recipe mapped onto TPU constraints from the Pallas guide:
128-aligned q/kv blocks feeding the 128x128 MXU, fp32 accumulators, causal
masking via broadcasted_iota, and fully-masked-block skipping so causal
attention does ~half the FLOPs.

The backward pass is two Pallas kernels (the FlashAttention-2 recipe):
- dq kernel: grid over q blocks, inner loop over kv blocks;
- dkv kernel: grid over kv blocks, inner loop over q blocks;
both recompute P = exp(S - L) from the forward's saved logsumexp L (stored
broadcast over a minor dim of `_LANES` = 8 as [B*H, T, 8] f32) and the
precomputed row term D = rowsum(dO * O).

`flash_attention` dispatches: Pallas kernel on TPU backends (or in
interpret mode, which is how CPU CI tests the hardware code path), jnp
reference otherwise. The choice per traced shape is recorded in
`ops.dispatch.kernel_choices()`; under an ambient mesh the kernels run per
shard over the batch and head axes (`ops.dispatch.per_shard`).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import dispatch

# 1024 x 1024 compiles and fits on a v5e at the 32 x 1024 training shape
# (chip run, PR 21); choosing among block sizes on the chip is ROADMAP S5.
# Env overrides for bench sweeps.
DEFAULT_BLOCK_Q = int(os.environ.get("RAY_TPU_FLASH_BLOCK_Q", "1024"))
DEFAULT_BLOCK_K = int(os.environ.get("RAY_TPU_FLASH_BLOCK_K", "1024"))
_LANES = 8  # LSE/D are broadcast over a small minor dim. It saves nothing
#             in HBM: XLA tiles these arrays T(8,128), so the 8 is padded
#             to 128 on the chip (compiled HLO, PR 21; PERF.md section 5)
_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # kernels work in log2 domain: exp2 is the
_LN2 = 0.6931471805599453    # cheap VPU transcendental; scale*log2(e) is
#                              folded into q so softmax needs only exp2.


def _grid_params(interpret: bool, minor: str = "parallel"):
    """Mosaic dimension_semantics for a (batch*head, block) grid. An axis
    is "parallel" when no program carries state to the next, which lets
    the compiler software-pipeline block DMA against compute; the fused
    backward accumulates dq across its block axis, which is therefore
    "arbitrary" (run in order). None in interpret mode."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", minor))


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


# --------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, kv_len: int, q_offset: int):
    """One (batch*head, q_block) program; loops KV blocks with online
    softmax. Refs: q [block_q, D], k/v [kv_len, D], o [block_q, D],
    lse [block_q, LANES] (logsumexp broadcast over lanes).
    q_offset = kv_len - q_len aligns queries to the END of the kv sequence
    (decode-style), matching mha_reference's tril(k=tk-tq)."""
    qi = pl.program_id(1)
    # log2-domain: fold sm_scale*log2(e) into q; softmax uses exp2 only.
    # Matmul operands stay in the input dtype (bf16 on the fast path —
    # f32 MXU passes are ~6x slower); accumulation is always f32.
    cd = q_ref.dtype
    q = (q_ref[...].astype(jnp.float32) * (sm_scale * _LOG2E)).astype(cd)
    d = q.shape[-1]

    m0 = jnp.full((block_q, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((block_q, d), dtype=jnp.float32)

    num_kv_blocks = pl.cdiv(kv_len, block_k)
    num_full_blocks = num_kv_blocks
    if causal:
        # KV blocks strictly after this q block's diagonal are fully masked.
        num_kv_blocks = jnp.minimum(
            num_kv_blocks,
            (q_offset + qi * block_q + block_q + block_k - 1) // block_k)
        # Blocks entirely below the diagonal need no mask compute at all;
        # two loops (full, then diagonal-straddling) keep the hot loop free
        # of iota/select VPU work.
        num_full_blocks = jnp.maximum(
            0, (q_offset + qi * block_q + 1 - block_k) // block_k + 1)

    def body(ki, carry, apply_mask):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, block_k]
        if apply_mask:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(cd), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    carry = jax.lax.fori_loop(
        0, num_full_blocks, functools.partial(body, apply_mask=False),
        (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(
        num_full_blocks, num_kv_blocks,
        functools.partial(body, apply_mask=True), carry)
    # Fully-masked rows (l == 0) only occur with kv_len < block alignment.
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    # natural-log LSE for the API: ln(sum exp(s_nat - 0)) recovered from
    # the log2-domain running (m, l).
    lse = (m + jnp.log2(l_safe)) * _LN2  # [block_q, 1]
    lse_ref[...] = jnp.broadcast_to(lse, (block_q, _LANES))


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    # flatten batch*heads into the grid's first axis; time-major per head
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)

    grid = (b * h, pl.cdiv(tq, block_q))
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=tk, q_offset=tk - tq)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, tk, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, tk, d), lambda g, i: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda g, i: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        compiler_params=_grid_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * tq * tk * d,
            bytes_accessed=(qf.size + kf.size + vf.size) * qf.dtype.itemsize,
            transcendentals=b * h * tq * tk),
    )(qf, kf, vf)
    # lse leaves as [B, H, T, LANES] so per_shard can split batch and heads
    return (out.reshape(b, h, tq, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, tq, _LANES))


# -------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcor_ref,
                         dq_ref, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, kv_len: int,
                         q_offset: int):
    """dQ for one q block: loop over kv blocks.
    Refs: q/do/dq [block_q, D], k/v [kv_len, D], lse/dcor [block_q, LANES]
    (dcor = rowsum(dO * O), the softmax correction term)."""
    qi = pl.program_id(1)
    cd = q_ref.dtype
    q = (q_ref[...].astype(jnp.float32) * (sm_scale * _LOG2E)).astype(cd)
    do = do_ref[...]
    lse2 = lse_ref[:, :1] * _LOG2E   # [block_q, 1], log2 domain
    dcor = dcor_ref[:, :1]
    d = q.shape[-1]

    num_kv_blocks = pl.cdiv(kv_len, block_k)
    num_full_blocks = num_kv_blocks
    if causal:
        num_kv_blocks = jnp.minimum(
            num_kv_blocks,
            (q_offset + qi * block_q + block_q + block_k - 1) // block_k)
        num_full_blocks = jnp.maximum(
            0, (q_offset + qi * block_q + 1 - block_k) // block_k + 1)

    def body(ki, dq_acc, apply_mask):
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if apply_mask:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp2(s - lse2)                    # [block_q, block_k]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # [block_q, block_k]
        ds = (p * (dp - dcor)).astype(cd)
        return dq_acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_full_blocks, functools.partial(body, apply_mask=False),
        jnp.zeros((block_q, d), jnp.float32))
    dq = jax.lax.fori_loop(
        num_full_blocks, num_kv_blocks,
        functools.partial(body, apply_mask=True), dq)
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcor_ref,
                          dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                          block_q: int, block_k: int, q_len: int,
                          q_offset: int):
    """dK/dV for one kv block: loop over q blocks.
    Refs: k/v/dk/dv [block_k, D], q/do [q_len, D], lse/dcor [q_len, LANES].
    """
    ki = pl.program_id(1)
    cd = k_ref.dtype
    k_scaled = (k_ref[...].astype(jnp.float32)
                * (sm_scale * _LOG2E)).astype(cd)
    v_blk = v_ref[...]
    d = k_scaled.shape[-1]

    num_q_blocks = pl.cdiv(q_len, block_q)
    start_q = 0
    first_full_q = 0
    if causal:
        # q blocks strictly before this kv block's diagonal see nothing;
        # blocks at/after first_full_q are entirely below the diagonal and
        # skip mask compute.
        start_q = jnp.maximum(
            0, (ki * block_k - q_offset) // block_q)
        # clamp below at 0: for tq < tk (decode-style) the numerator goes
        # negative and python floor division would yield -1, starting the
        # UNMASKED loop at a phantom qi=-1 block
        first_full_q = jnp.minimum(
            num_q_blocks,
            jnp.maximum(0, (ki * block_k + block_k - 1 - q_offset
                            + block_q - 1) // block_q))

    def body(qi, carry, apply_mask):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.ds(qi * block_q, block_q), :]
        do_blk = do_ref[pl.ds(qi * block_q, block_q), :]
        lse2 = lse_ref[pl.ds(qi * block_q, block_q), :1] * _LOG2E
        dcor = dcor_ref[pl.ds(qi * block_q, block_q), :1]
        # s^T: [block_k, block_q] = (K*scale*log2e) Q^T, log2 domain
        st = jax.lax.dot_general(
            k_scaled, q_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if apply_mask:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp2(st - lse2.T)                # [block_k, block_q]
        # dv += P^T dO
        dv_acc = dv_acc + jax.lax.dot_general(
            pt.astype(cd), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp^T = V dO^T : [block_k, block_q]
        dpt = jax.lax.dot_general(
            v_blk, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = (pt * (dpt - dcor.T)).astype(cd)
        # dk += dS^T (Q*scale)  (the sm_scale factor rides on k_scaled's
        # partner: dK = scale * dS^T Q, and q_blk here is unscaled)
        dk_acc = dk_acc + jax.lax.dot_general(
            dst, q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
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
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    lse = lse.reshape(b * h, tq, _LANES)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    of = o.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    dof = do.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
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
        compiler_params=_grid_params(interpret, minor="arbitrary"),
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


def _flash_bwd_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool):
    if _fused_bwd_enabled():
        return _flash_bwd_fused_pallas(q, k, v, o, lse, do, causal,
                                       sm_scale, block_q, block_k,
                                       interpret)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    lse = lse.reshape(b * h, tq, _LANES)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    of = o.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    dof = do.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    # softmax correction term D = rowsum(dO * O), lane-broadcast like lse
    dcor = jnp.broadcast_to(
        jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1,
                keepdims=True),
        (b * h, tq, _LANES))

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=tk, q_offset=tk - tq)
    dqf = pl.pallas_call(
        dq_kernel,
        grid=(b * h, pl.cdiv(tq, block_q)),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, tk, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, tk, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda g, i: (g, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        compiler_params=_grid_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * tq * tk * d,
            bytes_accessed=(qf.size + kf.size + vf.size + dof.size)
            * qf.dtype.itemsize,
            transcendentals=b * h * tq * tk),
    )(qf, kf, vf, dof, lse, dcor)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_len=tq, q_offset=tk - tq)
    dkf, dvf = pl.pallas_call(
        dkv_kernel,
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
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        compiler_params=_grid_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * h * tq * tk * d,
            bytes_accessed=(qf.size + kf.size + vf.size + dof.size)
            * qf.dtype.itemsize,
            transcendentals=b * h * tq * tk),
    )(qf, kf, vf, dof, lse, dcor)

    dq = dqf.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    dk = dkf.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    dv = dvf.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ------------------------------------------------------------- dispatch


def _reference_reason(q, k, block_q: int, block_k: int) -> str:
    """Why this call takes the XLA reference; "" when the kernels run.

    Sequence lengths must divide the *effective* block size (after
    clamping to the sequence length); otherwise the in-kernel pl.ds
    reads would silently clamp out-of-bounds starts and corrupt the
    causal indexing."""
    if os.environ.get("RAY_TPU_DISABLE_FLASH") == "1":  # ablation/debug escape hatch
        return "RAY_TPU_DISABLE_FLASH=1"
    if (reason := dispatch.backend_reason()):
        return reason
    tq, tk = q.shape[1], k.shape[1]
    if tq % min(block_q, tq) or tk % min(block_k, tk) \
            or tq % 128 or tk % 128:
        return (f"sequence lengths ({tq}, {tk}) are not multiples of 128 "
                f"and of the blocks ({block_q}, {block_k})")
    if q.shape[-1] % 128 and q.shape[-1] != 64:
        return f"head_dim {q.shape[-1]} is neither 64 nor a multiple of 128"
    return ""


def set_default_blocks(block_q: Optional[int] = None,
                       block_k: Optional[int] = None) -> None:
    """Runtime override of the default flash block sizes — calls that
    did not pin block_q/block_k pick the new values up on their next
    trace (autotuning hook; bench.py sweeps these on chip)."""
    global DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    if block_q is not None:
        DEFAULT_BLOCK_Q = int(block_q)
    if block_k is not None:
        DEFAULT_BLOCK_K = int(block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Fused attention. q,k,v: [batch, time, heads, head_dim] (kv time may
    differ). Pallas on TPU (fwd and bwd kernels); XLA reference elsewhere.
    block_q/block_k default to the module-level (env/autotune-settable)
    values at trace time.
    """
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)[0]


def _shard_specs(q):
    """(q/k/v spec, lse spec, shard count) for running the kernels per
    shard: attention is independent per batch row and per head."""
    b, _, h, _ = q.shape
    batch = dispatch.shard_axes(b, dispatch.DATA_AXES) or None
    head = dispatch.shard_axes(h, (dispatch.HEAD_AXIS,)) or None
    return (P(batch, None, head, None), P(batch, head, None, None),
            dispatch.axes_size((batch or ()) + (head or ())))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    block_q = DEFAULT_BLOCK_Q if block_q is None else block_q
    block_k = DEFAULT_BLOCK_K if block_k is None else block_k
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    shape = (*q.shape, k.shape[1])
    reason = _reference_reason(q, k, block_q, block_k)
    if reason:
        dispatch.record_choice("flash_attention", shape, "reference", reason)
        out = mha_reference(q, k, v, causal, scale)
        return out, (q, k, v, None, None)
    qkv_spec, lse_spec, n_shards = _shard_specs(q)
    dispatch.record_choice("flash_attention", shape, "pallas",
                           shards=n_shards)
    fwd = functools.partial(
        _flash_fwd_pallas, causal=causal, sm_scale=scale, block_q=block_q,
        block_k=block_k, interpret=dispatch.interpret_forced())
    out, lse = dispatch.per_shard(fwd, (q, k, v), (qkv_spec,) * 3,
                                  (qkv_spec, lse_spec))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, o, lse = res
    block_q = DEFAULT_BLOCK_Q if block_q is None else block_q
    block_k = DEFAULT_BLOCK_K if block_k is None else block_k
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    if lse is not None:
        qkv_spec, lse_spec, _ = _shard_specs(q)
        bwd = functools.partial(
            _flash_bwd_pallas, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k,
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
