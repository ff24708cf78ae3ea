"""Mamba-1 (the selective state space of Gu & Dao 2023, as the Jamba
family runs it) as functional ops: the selective scan a prefill runs over
a block of tokens, and the one-step recurrence a decode tick runs for
every slot. The causal convolution ahead of both is `ops/mamba2.py`'s.

Per channel c of C and state n of N, with A[c, n] < 0 and dt_t[c] > 0:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = (sum_n S_t[c, n] C_t[n] + D[c] u_t[c]) * silu(z_t[c])

The decay differs for every (channel, state) pair, so unlike Mamba-2
(`ops/mamba2.ssd_scan`: one scalar decay a head, a chunk is a matrix
product) a run of tokens has no matrix form: it is T dependent steps of
C x N multiply-adds and exponentials on the vector unit. In plain XLA
each step is a loop iteration of its own; here, on a TPU, it is the
Pallas kernel `selective_scan_t<T>`.

Layout: the state and A are held [N, C], the CHANNELS last: they lie
along the lanes and the 16 states along the sublanes (a [C, 16] array
would fill an eighth of every vector register and of every tile in
memory). The kernel folds the channels once more, [C] as [C / 128, 128],
and takes blocks of 8 x 128 of them: one state n of a block is then ONE
vector register, a step is 16 of them updated in place, and B_t[n] and
C_t[n] are scalars read from SMEM and broadcast. The time axis is the
last axis of the grid: the state stays in VMEM across it (the output
block of the final state is the accumulator) and goes back to memory
once. A step with dt = 0 neither decays nor feeds the state, which is
how a ragged run is padded (`models/jamba.py`) and how this module pads
a run to its own time blocks.

The state is float32 whatever the activations are (a bf16 state rounds
the recurrence at every step).
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
_LANES = 128
_ROWS = 8            # sublanes of a float32 register: 1,024 channels a block
_TIME_BLOCK = 256    # steps a program; 10 KB a step and block of channels


def selective_step(u: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                   cm: jax.Array, d: jax.Array, z: jax.Array,
                   state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every row: u, z [B, C], dt [B, C]
    float32 (after softplus), a [N, C] (negative), bm, cm [B, N], d [C],
    state [B, N, C]. Elementwise over the state, so with the state donated
    it is read once and written once in place, for EVERY row whatever is
    live. Returns (y [B, C] in u's dtype, the new state in the state's
    dtype)."""
    u32, dt32 = u.astype(F32), dt.astype(F32)
    s = (jnp.exp(dt32[:, None, :] * a.astype(F32)) * state.astype(F32)
         + (dt32 * u32)[:, None, :] * bm.astype(F32)[:, :, None])
    y = jnp.sum(s * cm.astype(F32)[:, :, None], axis=1) \
        + d.astype(F32) * u32
    return ((y * jax.nn.silu(z.astype(F32))).astype(u.dtype),
            s.astype(state.dtype))


def _scan_steps(u, dt, a, bm, cm, d, z, state):
    """The recurrence token by token as a `lax.scan` of `selective_step`:
    what a backend without Mosaic runs, and the kernel's reference."""
    def step(s, inp):
        u_t, dt_t, b_t, c_t, z_t = inp
        y, s = selective_step(u_t, dt_t, a, b_t, c_t, d, z_t, s)
        return s, y

    s, ys = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, dt, bm, cm, z)))
    return jnp.moveaxis(ys, 0, 1), s


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, z_ref, a_ref, d_ref, s0_ref,
                 y_ref, s_ref, *, steps: int, states: int):
    """One (batch row, block of channels, block of time) program. Refs: b,
    c [steps x N] float32 in SMEM, this block's B_t[n] and C_t[n] at `t N
    + n`; u, z, y [steps, rows, 128]; dt [steps, rows, 128] float32; a, s0,
    s [N, rows, 128] float32; d [rows, 128]. `s_ref`, the final state's
    block, is the same for every time block of a (row, channels) pair: it
    holds the state from one to the next."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    a = [a_ref[n] for n in range(states)]
    d = d_ref[...]

    def step(t, s):
        dt = dt_ref[t]
        u = u_ref[t].astype(F32)
        fed = dt * u
        acc = d * u
        new = []
        for n in range(states):
            s_n = jnp.exp(dt * a[n]) * s[n] + fed * b_ref[t * states + n]
            acc = acc + s_n * c_ref[t * states + n]
            new.append(s_n)
        z = z_ref[t].astype(F32)
        y_ref[t] = (acc * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)
        return tuple(new)

    s = jax.lax.fori_loop(0, steps, step,
                          tuple(s_ref[n] for n in range(states)))
    for n in range(states):
        s_ref[n] = s[n]


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _scan_pallas(u, dt, a, bm, cm, d, z, state, steps: int, tokens: int,
                 interpret: bool):
    """Jitted on its own so that the layers of a program, which call it
    at one shape, share ONE lowering of the kernel. u, z [B, T, C]; dt [B,
    T, C] float32; bm, cm [B, T, N] float32; a, state [.., N, C] float32;
    T a whole number of `steps`, C of 128."""
    b, t, c = u.shape
    n = a.shape[0]
    lines = c // _LANES
    rows = min(_ROWS, lines)
    nt = t // steps
    fold = lambda x: x.reshape(x.shape[:-1] + (lines, _LANES))
    scalars = pl.BlockSpec((steps * n,), lambda i, j, k: (i * nt + k,),
                           memory_space=pltpu.SMEM)
    by_time = pl.BlockSpec((None, steps, rows, _LANES),
                           lambda i, j, k: (i, k, j, 0))
    by_state = pl.BlockSpec((None, n, rows, _LANES),
                            lambda i, j, k: (i, 0, j, 0))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, steps=steps, states=n),
        grid=(b, pl.cdiv(lines, rows), nt),
        in_specs=[scalars, scalars, by_time, by_time, by_time,
                  pl.BlockSpec((n, rows, _LANES), lambda i, j, k: (0, j, 0)),
                  pl.BlockSpec((rows, _LANES), lambda i, j, k: (j, 0)),
                  by_state],
        out_specs=[by_time, by_state],
        out_shape=[jax.ShapeDtypeStruct((b, t, lines, _LANES), u.dtype),
                   jax.ShapeDtypeStruct((b, n, lines, _LANES), F32)],
        input_output_aliases={7: 1},
        interpret=interpret,
        # the PROMPT's length is in the name (the call's own is a block
        # of it), so that a trace says what prefill each event belongs
        # to (benchmarks: selective_scan_roofline.tput)
        name=f"selective_scan_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * t * c * n,
            bytes_accessed=b * t * (c * (3 * u.dtype.itemsize + 4) + 8 * n)
            + 4 * c * n * (2 * b + 1),
            transcendentals=b * t * c * (n + 1)),
    )(bm.reshape(-1), cm.reshape(-1), fold(u), fold(dt), fold(z),
      fold(a.astype(F32)), fold(d.astype(F32)), fold(state))
    return y.reshape(b, t, c), s.reshape(b, n, c)


def selective_scan(u: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                   cm: jax.Array, d: jax.Array, z: jax.Array,
                   state: jax.Array, tokens: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """T steps of the recurrence on top of `state` (what came before the
    run): u, z [B, T, C], dt [B, T, C] float32 (after softplus; 0 where a
    step is padding), a [N, C] (negative), bm, cm [B, T, N], d [C], state
    [B, N, C] float32. `tokens` is the length of the prompt this run is a
    block of (the run's own when None): it names the kernel and shapes
    nothing. Returns (y [B, T, C] in u's dtype, the state after the last
    step). On a TPU the Pallas kernel `selective_scan_t<tokens>`, which
    wants the channels a whole number of 128; elsewhere, and for other
    channel counts, the same steps as a `lax.scan`
    (`dispatch.kernel_choices("selective_scan")` says which)."""
    b, t, c = u.shape
    n = a.shape[0]
    tokens = int(tokens or t)
    dt, bm, cm = dt.astype(F32), bm.astype(F32), cm.astype(F32)
    state = state.astype(F32)
    shape = (b, t, c, n, tokens)
    reason = dispatch.backend_reason() or (
        "" if c % _LANES == 0 else f"{c} channels are not a whole number "
        f"of {_LANES}")
    if reason:
        dispatch.record_choice("selective_scan", shape, "reference", reason)
        return _scan_steps(u, dt, a, bm, cm, d, z, state)
    # the kernel's own time blocks: padded steps (dt = 0) leave the state
    # as it is
    steps = min(_TIME_BLOCK, -(-t // 8) * 8)
    pad = -t % steps
    if pad:
        u, dt, bm, cm, z = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (u, dt, bm, cm, z))
    dispatch.record_choice("selective_scan", shape, "pallas",
                           time_block=steps,
                           channel_block=min(_ROWS, c // _LANES) * _LANES)
    y, s = _scan_pallas(u, dt, a, bm, cm, d, z, state, steps, tokens,
                        dispatch.interpret_forced())
    return y[:, :t], s
