"""Mamba-2 (state-space duality; Dao & Gu 2024) as functional ops: the
causal depthwise convolution with its tail, the chunked scan a prefill
runs, and the one-step recurrence a decode tick runs for every slot.

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
them. No Pallas kernel: every piece is a fusion XLA finds on its own.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def causal_conv(xbc: jax.Array, tail: jax.Array, w: jax.Array,
                b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution, then SiLU. xbc [B, T, C] are the new
    inputs, tail [B, K-1, C] the K-1 inputs before them (zeros at the
    start of a sequence), w [K, C], b [C]. Returns (out [B, T, C] in
    xbc's dtype, the new tail [B, K-1, C] in the tail's dtype)."""
    k, t = w.shape[0], xbc.shape[1]
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w32 = w.astype(F32)
    acc = b.astype(F32)
    for i in range(k):
        acc = acc + window[:, i:i + t].astype(F32) * w32[i]
    return (jax.nn.silu(acc).astype(xbc.dtype),
            window[:, t:].astype(tail.dtype))


def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, d: jax.Array, state: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every row: x [B, H, P], dt [B, H]
    (after softplus), a [H] (negative), bm, cm [B, G, N], d [H], state
    [B, H, P, N]. Elementwise over the state, so with the state donated
    it is read once and written once in place. Returns (y [B, H, P]
    float32, the new state in the state's dtype)."""
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
