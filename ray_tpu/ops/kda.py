"""Kimi Delta Attention (Kimi Linear, 2025): the gated delta rule with a
decay PER CHANNEL, as functional ops: the chunked form a prefill runs and
the one-step form a decode tick runs for every slot.

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
matmul precision; no Pallas kernel: each piece is what XLA fuses.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_SUB = 16    # columns to a reference row of the decay's factoring


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, state: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every row: q, k, g [B, H, d_k],
    v [B, H, d_v], beta [B, H], state [B, H, d_k, d_v]. Both reductions
    are taken against the state as it arrives (S'^T k = S^T (alpha k),
    o = S^T (alpha q) + u (k . q)), so the state is read by one pass and
    read and written in place by a second. Returns (o [B, H, d_v]
    float32, the new state in the state's dtype)."""
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

