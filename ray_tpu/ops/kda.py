"""Kimi Delta Attention (Kimi Linear, 2025): the gated delta rule with a
decay PER CHANNEL, as functional ops: the chunked form a prefill runs and
the one-step form a decode tick runs for the slots that are live.

Per head, with a float32 state S [d_k, d_v], log-decay g_t [d_k] <= 0
(alpha_t = exp(g_t)), beta_t in (0, 1), q_t and k_t L2-normalised:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The update reads the state it writes (k^T S), which `ops/mamba2.py`'s
rank-1 add under a scalar decay does not. With the pseudo-value
u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t) the step is
S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T, and over a chunk of C tokens from
the carried state S_0, with G_t the running sum of g inside the chunk:

    (I + A) U = beta (V - (K . exp(G)) S_0)       the WY/UT form
    A[t, i]   = beta_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c]),  i <  t
    P[t, i]   =        sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c]),  i <= t
    O         = (Q . exp(G)) S_0 + P U
    S_C       = Diag(exp(G_C)) S_0 + (K . exp(G_C - G))^T U

`kda_scan` solves the unit lower-triangular system for every chunk at
once (U = U0 - W S_0, with U0 and W independent of the state) and carries
only the state between chunks. A and P are matrix products: the decay
between two tokens is factored through a reference row at the head of
each block of `_SUB` columns, exp(G_t - R) <= 1 on the rows' side and
exp(R - G_i) >= 1 on the columns', so nothing overflows while a channel
decays by less than e^88 inside one block. A ragged last chunk is padded
with g = 0, beta = 0 and k = 0, which neither decays nor feeds the state
(as `ssd_scan` pads dt = 0). Everything here is float32 at the highest
matmul precision, and the scan and the step over EVERY row (`_step_all`)
are what XLA fuses.

A decode tick knows which of its slots decode for somebody (`live` [B],
the engine's device vector), and the state is the largest thing a tick of
such a model moves: 2 MB a slot and layer at the published widths, which
XLA's fusion of the step passes over three times (a read for the two
sums against the state as it arrives, then a read and a write in place).
With `live`, on a backend with Mosaic, `kda_step` is the Pallas kernel
`kda_step_live`, built as `ops/mamba2.py`'s `ssd_step_live` is: a grid
over (visit, block of heads) whose length is counted on the chip, the
live slots' indices first in a map that is prefetched as scalars
(`mamba2.live_first`), a visit one slot's `[heads_block, d_k, d_v]` block
of state, held in VMEM for both sums and the update, so read ONCE, and
written back WHERE IT LAY (the state is aliased to the output: the slab
is updated in place and never copied). A dead slot's state is neither
read nor written, and its `o` is 0. Both sums run over d_k, the sublane
axis of a head's tile: vector adds and one fold, no lane reduction. The
small operands come a visit at a time, blocked by the same map: what
spreads along d_k (alpha k, alpha q, alpha, k) as columns, a head a lane,
in one array; what spreads along d_v (v, beta, k . q) as rows in another.
Which form a traced shape took: `dispatch.kernel_choices("state_step")`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .mamba2 import live_first, step_heads_block

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_SUB = 16    # columns to a reference row of the decay's factoring
# two blocks of state in and two out (the next visit's arrive under this
# one's: 2 MB each at the published widths), the small operands twice,
# and the step's own temporaries
_STEP_VMEM_LIMIT = 32 << 20


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _step_all(q, k, v, g, beta, state):
    """The step for EVERY row, as XLA fuses it: the reference. Both
    reductions are taken against the state as it arrives (S'^T k = S^T
    (alpha k), o = S^T (alpha q) + u (k . q)), so the state is read by
    one pass for the sums and read and written in place by another."""
    q, k, v, g = (x.astype(F32) for x in (q, k, v, g))
    beta = beta.astype(F32)[..., None]
    s = state.astype(F32)
    alpha = jnp.exp(g)
    ak, aq = alpha * k, alpha * q
    sk = jnp.sum(s * ak[..., None], axis=-2)          # S'^T k  [B,H,dv]
    sq = jnp.sum(s * aq[..., None], axis=-2)          # S'^T q
    u = beta * (v - sk)
    o = sq + u * jnp.sum(k * q, -1, keepdims=True)
    s = s * alpha[..., None] + k[..., None] * u[..., None, :]
    return o, s.astype(state.dtype)


def _step_kernel(slots_ref, count_ref, cols_ref, rows_ref, s_ref, o_ref,
                 new_ref):
    """One visit: a block of `hb` heads of slot `slots[v]`. Refs: slots
    [B], count [1] in SMEM; cols [d_k, 4 hb] (alpha k, alpha q, alpha and
    k, each a head a lane), rows [3, hb, d_v] (v, beta, k . q, the last
    two spread along d_v), o [hb, d_v]: the slot's own; s, new [hb, d_k,
    d_v], the slot's block of the state, new aliased to s in HBM. A visit
    past the live slots (there is one only when none is live) hands its
    block back as it found it."""
    hb = s_ref.shape[0]

    @pl.when(pl.program_id(0) >= count_ref[0])
    def _():
        new_ref[...] = s_ref[...]

    @pl.when(pl.program_id(0) < count_ref[0])
    def _():
        cols = cols_ref[...]
        col = lambda kind, i: cols[:, kind * hb + i:kind * hb + i + 1]
        for i in range(hb):
            s = s_ref[i]
            row = lambda kind: rows_ref[kind, i:i + 1, :]
            # over d_k, the tile's sublanes: S'^T k and S'^T q, [1, d_v]
            sk = jnp.sum(s * col(0, i), axis=0, keepdims=True)
            sq = jnp.sum(s * col(1, i), axis=0, keepdims=True)
            u = row(1) * (row(0) - sk)
            o_ref[i:i + 1, :] = sq + u * row(2)
            new_ref[i] = s * col(2, i) + col(3, i) * u


@functools.partial(jax.jit, static_argnums=(7, 8))
def _step_pallas(q, k, v, g, beta, state, live, heads_block: int,
                 interpret: bool):
    """Jitted on its own so that the layers of a tick share ONE lowering
    of the kernel. Shapes as `kda_step`'s, the state float32, `live` [B];
    `heads_block` divides the heads."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    hb, nj = heads_block, h // heads_block
    q, k, v, g = (x.astype(F32) for x in (q, k, v, g))
    alpha = jnp.exp(g)
    # a head a lane, the four kinds side by side: 4 x [B, H, d_k] ->
    # [B, H/hb, d_k, 4 hb]
    cols = jnp.stack([x.reshape(b, nj, hb, dk)
                      for x in (alpha * k, alpha * q, alpha, k)], 2)
    cols = cols.reshape(b, nj, 4 * hb, dk).swapaxes(2, 3)
    along_dv = lambda x: jnp.broadcast_to(x[..., None], (b, h, dv))
    rows = jnp.stack([x.reshape(b, nj, hb, dv) for x in (
        v, along_dv(beta.astype(F32)), along_dv(jnp.sum(k * q, -1)))], 2)
    slots, count = live_first(live)
    of_the_slot = lambda *shape: pl.BlockSpec(
        (None, None) + shape, lambda v, j, slots, count:
        (slots[v], j) + (0,) * len(shape))
    where_it_lies = pl.BlockSpec(
        (None, hb, dk, dv), lambda v, j, slots, count: (slots[v], j, 0, 0))
    o, new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # the visits are counted on the chip; with no slot live, one
            # visit that changes nothing
            grid=(jnp.maximum(count[0], 1), nj),
            in_specs=[of_the_slot(dk, 4 * hb), of_the_slot(3, hb, dv),
                      where_it_lies],
            out_specs=[of_the_slot(hb, dv), where_it_lies]),
        out_shape=[jax.ShapeDtypeStruct((b, nj, hb, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (operand 4, the two maps counted) IS the second
        # output: updated in place, a slot not visited not touched
        input_output_aliases={4: 1},
        interpret=interpret,
        name="kda_step_live",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_LIMIT),
        # with every slot live; how many are is known on the chip alone
        # (two products and two adds for the sums, three for the update)
        cost_estimate=pl.CostEstimate(
            flops=7 * state.size, transcendentals=0,
            bytes_accessed=8 * state.size),
    )(slots, count, cols, rows, state)
    # a slot not visited left nothing in its rows of o
    return jnp.where((live != 0)[:, None, None], o.reshape(b, h, dv),
                     0.0), new


def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, state: jax.Array,
             live: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence: q, k, g [B, H, d_k], v [B, H, d_v],
    beta [B, H], state [B, H, d_k, d_v]; `live` [B] (0: a dead slot),
    None for every row. Returns (o [B, H, d_v] float32, the new state in
    the state's dtype).

    With `live` on a backend with Mosaic the kernel `kda_step_live`
    visits the live rows alone (module docstring): a visit reads its
    slot's state once and writes it once where it lay, a dead row's state
    is not read and not written, its o is 0. Elsewhere the XLA expression
    over every row (`_step_all`), which passes over the whole state three
    times; a dead row's state is then stepped too, which nothing reads (a
    slot's state is written whole before it lives again). The choice is
    recorded under `state_step` in `ops/dispatch`, shape (B, H, d_k,
    d_v)."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    interpret = dispatch.interpret_forced()
    reason = dispatch.backend_reason()
    if live is None:
        reason = "no liveness given: every row is stepped"
    if not reason and state.dtype != F32:
        reason = f"a {state.dtype.name} state, not float32"
    if not reason and not interpret and (dv % 128 or dk % 8):
        reason = (f"a head's state of {dk} x {dv} does not fill the "
                  "kernel's tiles")
    if reason:
        dispatch.record_choice("state_step", (b, h, dk, dv), "reference",
                               reason)
        return _step_all(q, k, v, g, beta, state)
    hb = step_heads_block(h, 1, dk, dv)
    dispatch.record_choice("state_step", (b, h, dk, dv), "pallas",
                           heads_block=hb)
    return _step_pallas(q, k, v, g, beta, state, live, hb, interpret)


def _decayed_products(rows: jax.Array, k: jax.Array, gc: jax.Array,
                      sub: int) -> jax.Array:
    """sum_c rows[t, c] k[i, c] exp(gc[t, c] - gc[i, c]) as [.., C, C]
    (meaningful for i <= t only), the decay factored per block of `sub`
    columns through the block's first row of gc (module docstring).
    rows, k, gc [.., C, d_k]."""
    c, dk = k.shape[-2:]
    lead = k.shape[:-2]
    nb = c // sub
    ref = gc.reshape(lead + (nb, sub, dk))[..., :1, :]       # [.., nb,1,dk]
    cols = k.reshape(lead + (nb, sub, dk)) * jnp.exp(
        ref - gc.reshape(lead + (nb, sub, dk)))
    # rows before a block never meet it (i <= t): their exponent is
    # clamped and the product masked by the caller
    left = rows[..., None, :, :] * jnp.exp(
        jnp.minimum(gc[..., None, :, :] - ref, 0.0))         # [.., nb,C,dk]
    out = jnp.einsum("...ntc,...njc->...tnj", left, cols, precision=_HI)
    return out.reshape(lead + (c, c))


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, state: jax.Array, chunk: int
             ) -> Tuple[jax.Array, jax.Array]:
    """T steps of the recurrence, a chunk of `chunk` at a time: q, k, g
    [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H], state [B, H, d_k,
    d_v] (what came before position 0). Returns (o [B, T, H, d_v]
    float32, the state after the last token in the state's dtype)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    c = int(chunk)
    sub = math.gcd(c, _SUB)
    pad = (-t) % c
    nc = (t + pad) // c

    def chunks(arr):
        """[B, T, H, ..] -> [B, nc, H, C, ..], zero-padded."""
        arr = arr.astype(F32)
        if pad:
            arr = jnp.pad(arr, [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2))
        arr = arr.reshape((b, nc, c) + arr.shape[2:])
        return jnp.moveaxis(arr, 3, 2)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta)[..., None]                          # [B,nc,H,C,1]
    gc = jnp.cumsum(gc, axis=-2)                          # G_t
    lower = jnp.tril(jnp.ones((c, c), bool))
    a = jnp.where(lower & ~jnp.eye(c, dtype=bool),
                  _decayed_products(kc, kc, gc, sub), 0.0) * bc
    p = jnp.where(lower, _decayed_products(qc, kc, gc, sub), 0.0)
    decay = jnp.exp(gc)                                   # exp(G_t) <= 1
    # (I + A) [U0 | W] = beta [V | K exp(G)], every chunk at once
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=F32),
        jnp.concatenate([bc * vc, bc * kc * decay], -1),
        lower=True, unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]
    q_in = qc * decay                                     # reads S_0
    to_end = decay[..., -1:, :]                           # exp(G_C)
    k_out = kc * jnp.exp(gc[..., -1:, :] - gc)            # feeds S_C

    def one_chunk(s, inp):
        u0_c, w_c, p_c, q_c, k_c, end_c = inp
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s, precision=_HI)
        o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s, precision=_HI) \
            + jnp.einsum("bhti,bhiv->bhtv", p_c, u, precision=_HI)
        s = s * jnp.swapaxes(end_c, -1, -2) \
            + jnp.einsum("bhtk,bhtv->bhkv", k_c, u, precision=_HI)
        return s, o

    per_chunk = tuple(jnp.moveaxis(x, 1, 0)
                      for x in (u0, w, p, q_in, k_out, to_end))
    s, os_ = jax.lax.scan(one_chunk, state.astype(F32), per_chunk)
    o = jnp.moveaxis(os_, 0, 1)                           # [B,nc,H,C,dv]
    o = jnp.moveaxis(o, 2, 3).reshape(b, nc * c, h, dv)[:, :t]
    return o, s.astype(state.dtype)

