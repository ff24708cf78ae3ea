"""Mamba-2 (state-space duality; Dao & Gu 2024) as functional ops: the
causal depthwise convolution with its tail, the chunked scan a prefill
runs, and the one-step recurrence a decode tick runs for the slots that
are live.

Per head h (head size P, state size N; head h reads group h // (H // G)
of B and C), with A < 0 and dt > 0 per head:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (S is P x N)
    y_t = S_t C_t + D x_t

`ssd_scan` computes the same thing a chunk at a time: inside a chunk the
products C_t . B_s weighted by the decay between s and t (a Q x Q matrix
product per head), between chunks the carried state. A ragged last chunk
is padded with dt = 0, which neither decays nor feeds the state, so the
state handed back is the one after the last real token. The state is kept
in float32 whatever the activations are (a bf16 state rounds the
recurrence at every step); the einsums that touch it run at the highest
matmul precision, which costs nothing next to the projections around
them. The convolution, the scan and the norm are fusions XLA finds on
its own, and so is the step over EVERY row (`_step_all`: elementwise
over the state, which it reads once and writes once, all of it).

A decode tick knows which of its slots decode for somebody (`live` [B],
the engine's device vector), and the state is the largest thing a tick
of such a model moves: 4 MB a slot and layer at the published widths.
With `live`, on a backend with Mosaic, `ssd_step` is the Pallas kernel
`ssd_step_live`: a grid over (visit, block of heads) whose length is
counted on the chip, the live slots' indices first in a map that is
prefetched as scalars (`live_first`), a visit one slot's `[heads_block,
P, N]` block of state, read, stepped by the expression above and written
back WHERE IT LAY (the state is aliased to the output: the slab is
updated in place and never copied). A dead slot's state is neither read
nor written, and its `y` is 0. The small operands (x dt, the decay, B
and C: 48 KB a slot) stay resident for the whole call, transposed by XLA
so that a head's column of them spreads along the lanes of its state.
Which form a traced shape took: `dispatch.kernel_choices("state_step")`.

Two callers, two shapes. `models/nemotron_h.py`: 8 groups of 16 heads, a
chunk of 128, the whole run of a prompt in one call (192 to 1,024
tokens). `models/granite_hybrid.py`: ONE group (all 128 heads read the
same B and C: `rep = heads`, a visit of the step kernel takes one block
of B and C a slot), a chunk of 256, a prompt of thousands of tokens
walked in blocks of 2,048 with the state and the tail carried from call
to call, so a call's last chunk is ragged wherever a prompt is no whole
number of chunks. Both go through the same code: nothing here reads the
number of groups but as a shape.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# the most one visit's block of state may hold: a whole slot's 4 MB at the
# published widths, so that a visit is one long copy in and one out
_STEP_BLOCK_BYTES = 4 << 20
# two blocks of state in and two out (the next visit's arrive under this
# one's), the resident operands twice, and the step's own temporaries
_STEP_VMEM_LIMIT = 64 << 20


def causal_conv(xbc: jax.Array, tail: jax.Array, w: jax.Array,
                b: jax.Array, activation=jax.nn.silu
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution, then SiLU (or `activation`; None:
    the taps' sum as it is, `ops/cca.py`). xbc [B, T, C] are the new
    inputs, tail [B, K-1, C] the K-1 inputs before them (zeros at the
    start of a sequence), w [K, C], b [C]. Returns (out [B, T, C] in
    xbc's dtype, the new tail [B, K-1, C] in the tail's dtype)."""
    k, t = w.shape[0], xbc.shape[1]
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w32 = w.astype(F32)
    acc = b.astype(F32)
    for i in range(k):
        acc = acc + window[:, i:i + t].astype(F32) * w32[i]
    if activation is not None:
        acc = activation(acc)
    return acc.astype(xbc.dtype), window[:, t:].astype(tail.dtype)


def live_first(live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The map of a walk over the slots that are live, made on the chip:
    (the slots' indices with the live ones first, each kind in slot
    order, int32 [B]; how many are live, int32 [1]). `live` [B] is 0 for
    a dead slot. A grid of `count` visits whose v-th block index is
    `slots[v]` visits every live slot once and no dead one."""
    dead = live == 0
    return (jnp.argsort(dead, stable=True).astype(jnp.int32),
            jnp.sum(~dead, dtype=jnp.int32)[None])


def step_heads_block(heads: int, rep: int, p: int, n: int) -> int:
    """The heads of one visit's block of the state [B, heads, p, n]
    float32: all of them if a slot's state fits `_STEP_BLOCK_BYTES`, else
    the most whole groups of `rep` heads (a group shares B and C) that
    divide `heads` and fit; one group at least."""
    fits = [hb for hb in range(rep, heads + 1, rep)
            if heads % hb == 0 and 4 * hb * p * n <= _STEP_BLOCK_BYTES]
    return max(fits, default=rep)


def _step_kernel(slots_ref, count_ref, fed_ref, decay_ref, bm_ref, cm_ref,
                 s_ref, y_ref, o_ref, *, rep: int):
    """One visit: a block of `hb` heads of slot `slots[v]`. Refs: slots
    [B], count [1] in SMEM; fed [B, H/hb, P, hb] (x dt, a head a lane),
    decay [B, H/hb, 1, hb], bm, cm [B, H/hb, hb/rep, N], y as fed: all
    resident; s, o [hb, P, N], the slot's block of the state, o aliased to
    s in HBM. A visit past the live slots (there is one only when none is
    live) hands its block back as it found it."""
    v, j = pl.program_id(0), pl.program_id(1)
    hb, p, n = s_ref.shape

    @pl.when(v >= count_ref[0])
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(v < count_ref[0])
    def _():
        slot = slots_ref[v]
        fed = fed_ref[slot, j]
        decay = jnp.broadcast_to(decay_ref[slot, j], (p, hb))
        bm, cm = bm_ref[slot, j], cm_ref[slot, j]
        lane = jax.lax.broadcasted_iota(jnp.int32, (p, hb), 1)
        ones = jnp.ones((n, hb), F32)
        y = jnp.zeros((p, hb), F32)
        for i in range(hb):
            g = i // rep
            s = s_ref[i] * decay[:, i:i + 1] \
                + fed[:, i:i + 1] * bm[g:g + 1, :]
            o_ref[i] = s
            # the float32 products summed along the lanes by the matrix
            # unit, which is idle (three exact bf16 parts of each, float32
            # accumulators: a float32 sum in another order). As a lane
            # reduction on the vector unit the sums are a fifth of the
            # call and it falls behind XLA's fusion with every slot live
            # (PERF.md section 6, PR 47); every lane of `col` holds the sum
            col = jnp.dot(s * cm[g:g + 1, :], ones, precision=_HI,
                          preferred_element_type=F32)
            y = jnp.where(lane == i, col, y)
        y_ref[slot, j] = y


@functools.partial(jax.jit, static_argnums=(8, 9))
def _step_pallas(x, dt, a, bm, cm, d, state, live, heads_block: int,
                 interpret: bool):
    """Jitted on its own so that the layers of a tick share ONE lowering
    of the kernel. Shapes as `ssd_step`'s, the state float32, `live` [B];
    `heads_block` a whole number of groups that divides the heads."""
    b, h, p = x.shape
    g, n = bm.shape[1:]
    hb, rep = heads_block, h // g
    nj = h // hb
    x32, dt32 = x.astype(F32), dt.astype(F32)
    # a head a lane: [B, H, P] -> [B, H/hb, P, hb]
    lanes = lambda arr: arr.reshape(b, nj, hb, -1).swapaxes(2, 3)
    fed = lanes(x32 * dt32[..., None])
    decay = lanes(jnp.exp(dt32 * a.astype(F32)))
    groups = lambda arr: arr.astype(F32).reshape(b, nj, hb // rep, n)
    slots, count = live_first(live)
    resident = lambda *shape: pl.BlockSpec(
        shape, lambda v, j, slots, count: (0,) * len(shape))
    where_it_lies = pl.BlockSpec(
        (None, hb, p, n), lambda v, j, slots, count: (slots[v], j, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_step_kernel, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # the visits are counted on the chip; with no slot live, one
            # visit that changes nothing
            grid=(jnp.maximum(count[0], 1), nj),
            in_specs=[resident(b, nj, p, hb), resident(b, nj, 1, hb),
                      resident(b, nj, hb // rep, n),
                      resident(b, nj, hb // rep, n), where_it_lies],
            out_specs=[resident(b, nj, p, hb), where_it_lies]),
        out_shape=[jax.ShapeDtypeStruct((b, nj, p, hb), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (operand 6, the two maps counted) IS the second
        # output: updated in place, a slot not visited not touched
        input_output_aliases={6: 1},
        interpret=interpret,
        name="ssd_step_live",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_LIMIT),
        # with every slot live; how many are is known on the chip alone
        cost_estimate=pl.CostEstimate(
            flops=6 * state.size, transcendentals=0,
            bytes_accessed=8 * state.size),
    )(slots, count, fed, decay, groups(bm), groups(cm), state)
    # a slot not visited left nothing in its rows of y
    y = jnp.where((live != 0)[:, None, None],
                  y.swapaxes(2, 3).reshape(b, h, p), 0.0)
    return y + d.astype(F32)[:, None] * x32, new


def _step_all(x, dt, a, bm, cm, d, state):
    """The step for EVERY row, as XLA fuses it: the reference."""
    b, h, p = x.shape
    g, n = bm.shape[1:]
    r = h // g
    x32, dt32 = x.astype(F32), dt.astype(F32)
    s = state.astype(F32).reshape(b, g, r, p, n)
    decay = jnp.exp(dt32 * a.astype(F32)).reshape(b, g, r, 1, 1)
    fed = (x32 * dt32[..., None]).reshape(b, g, r, p, 1)
    s = s * decay + fed * bm.astype(F32)[:, :, None, None, :]
    y = jnp.sum(s * cm.astype(F32)[:, :, None, None, :], axis=-1)
    y = y.reshape(b, h, p) + d.astype(F32)[:, None] * x32
    return y, s.reshape(b, h, p, n).astype(state.dtype)


def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, d: jax.Array, state: jax.Array,
             live: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence: x [B, H, P], dt [B, H] (after
    softplus), a [H] (negative), bm, cm [B, G, N], d [H], state [B, H, P,
    N]; `live` [B] (0: a dead slot), None for every row. Returns (y [B,
    H, P] float32, the new state in the state's dtype).

    With `live` on a backend with Mosaic the kernel `ssd_step_live`
    visits the live rows alone (module docstring): a dead row's state is
    not read and not written, its y is 0 before D x. Elsewhere the XLA
    expression over every row, elementwise over the state, so with the
    state donated it is read once and written once in place; a dead
    row's state is then stepped too, which nothing reads (a slot's state
    is written whole before it lives again). The choice is recorded
    under `state_step` in `ops/dispatch`, shape (B, H, P, G, N)."""
    b, h, p = x.shape
    g, n = bm.shape[1:]
    interpret = dispatch.interpret_forced()
    reason = dispatch.backend_reason()
    if live is None:
        reason = "no liveness given: every row is stepped"
    if not reason and state.dtype != F32:
        reason = f"a {state.dtype.name} state, not float32"
    if not reason and not interpret and (n % 128 or p % 8):
        reason = (f"a head's state of {p} x {n} does not fill the "
                  "kernel's tiles")
    if reason:
        dispatch.record_choice("state_step", (b, h, p, g, n), "reference",
                               reason)
        return _step_all(x, dt, a, bm, cm, d, state)
    hb = step_heads_block(h, h // g, p, n)
    dispatch.record_choice("state_step", (b, h, p, g, n), "pallas",
                           heads_block=hb)
    return _step_pallas(x, dt, a, bm, cm, d, state, live, hb, interpret)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, d: jax.Array, state: jax.Array, chunk: int
             ) -> Tuple[jax.Array, jax.Array]:
    """T steps of the recurrence, a chunk of `chunk` at a time: x [B, T,
    H, P], dt [B, T, H], bm, cm [B, T, G, N], state [B, H, P, N] (what
    came before position 0). Returns (y [B, T, H, P] float32, the state
    after the last token in the state's dtype)."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g
    q = int(chunk)
    pad = (-t) % q
    nc = (t + pad) // q

    def chunks(arr):
        arr = arr.astype(F32)
        if pad:
            arr = jnp.pad(arr, [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2))
        return jnp.moveaxis(arr.reshape((b, nc, q) + arr.shape[2:]), 1, 0)

    a32, d32 = a.astype(F32), d.astype(F32)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(s, inp):
        xc, dtc, bc, cc = inp       # [B,Q,H,P] [B,Q,H] [B,Q,G,N] x2
        cs = jnp.cumsum(dtc * a32, axis=1)                    # [B,Q,H]
        fed = (xc * dtc[..., None]).reshape(b, q, g, r, p)
        # inside the chunk: (C_t . B_s) exp(cs_t - cs_s), s <= t
        gram = jnp.einsum("bqgn,bsgn->bgqs", cc, bc, precision=_HI)
        diff = cs[:, :, None, :] - cs[:, None, :, :]          # [B,Q,S,H]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], diff,
                                  -jnp.inf))
        decay = jnp.moveaxis(decay, 3, 1).reshape(b, g, r, q, q)
        y = jnp.einsum("bgrqs,bsgrp->bqgrp", gram[:, :, None] * decay,
                       fed, precision=_HI)
        # what the chunks before left behind
        s5 = s.reshape(b, g, r, p, n)
        carried = jnp.einsum("bqgn,bgrpn->bqgrp", cc, s5, precision=_HI)
        y = y + carried * jnp.exp(cs).reshape(b, q, g, r, 1)
        # the state at the chunk's end
        to_end = jnp.exp(cs[:, -1:, :] - cs).reshape(b, q, g, r, 1)
        s5 = s5 * jnp.exp(cs[:, -1]).reshape(b, g, r, 1, 1) + jnp.einsum(
            "bsgrp,bsgn->bgrpn", fed * to_end, bc, precision=_HI)
        y = y.reshape(b, q, h, p) + d32[:, None] * xc
        return s5.reshape(b, h, p, n), y

    s, ys = jax.lax.scan(one_chunk, state.astype(F32),
                         (chunks(x), chunks(dt), chunks(bm), chunks(cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * q, h, p)[:, :t]
    return y, s.astype(state.dtype)


def gated_group_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """RMSNorm over `groups` equal runs of the channels of y * silu(z),
    times the per-channel scale: the mixer's norm before its output
    projection. y, z [..., C]; float32 statistics, y's dtype out."""
    shape = y.shape
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps)
    return (v.reshape(shape) * scale.astype(F32)).astype(y.dtype)
