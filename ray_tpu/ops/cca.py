"""Compressed convolutional attention (CCA; arXiv:2510.04476, the
attention of the ZAYA1 family, `models/zaya.py`): queries and keys are
made in a LATENT narrower than the hidden size, mixed by two causal
convolutions over the sequence, and only then normalised, rotated and
attended, so the cache holds `kv_heads x head_dim` keys and values a
token (an eighth of full attention's at 8 over 2 heads of 128 under a
hidden size of 2,048) and the products that make them are as much
smaller. With `x` the sublayer's normed input [B, T, D], `H` query heads
over `G` key-value heads of `d` numbers, `g = H / G`:

  1  `[q~ | k~ | v1 | v2] = x W_in`: the query latent (`H d` wide), the
     key latent (`G d`), and two half-heads of values (`G d / 2` each);
     `u = [q~ | k~]`, `H + G` heads of `d` channels.
  2  convolution 0, depthwise and causal over `time0` taps (the tap
     `ops/mamba2.causal_conv`, with no activation): `a_t[c] = sum_j
     w0[j, c] u_{t - time0 + 1 + j}[c] + b0[c]`; convolution 1, causal
     over `time1` taps, GROUPED BY HEAD: `b_t[h] = sum_j a_{t - time1 +
     1 + j}[h] W1[h, j] + b1[h]`, `W1[h, j]` a `d x d` matrix (one
     batched product over the taps side by side, `[.., time1 d] x
     [time1 d, d]` a head). `b` splits into `q^c` and `k^c`.
  3  the query-key mean, of the latents BEFORE the convolutions: `q_i =
     q^c_i + (q~_i + k~_{i // g}) / 2`; `k_j = k^c_j + (mean over the g
     heads i of group j of q~_i + k~_j) / 2`.
  4  values: head j's first `d / 2` channels are `v1_t`'s, THIS token's,
     its last `d / 2` are `v2_{t-1}`'s, the token's before it.
  5  `q <- sqrt(d) q / |q|`, `k <- sqrt(d) k / |k| * tau_j` a head, `tau`
     learned, one a key-value head.
  6  rotary positions over the first `rotary` channels of each head
     (split-half within them), by position.
  7  causal softmax attention at scale `d^-1/2`, `H` over `G`
     (`ops/swa.py`: the prompt form over a run from position 0, the
     decode walk for a tick, the slab form for a suffix), and `W_o`.

A token's QUERY depends on the token before it (steps 2 and 4), so the
sublayer carries STATE with no sequence axis beside its rows of keys and
values: the last `time0 - 1` rows of `u`, the last `time1 - 1` rows of
`a`, and the last `v2` (`cca_state`; at two taps each 2 x 1,280 + 128
numbers, 5.4 KB in bf16, against 1 KB of keys and values a token). Both
forms are ONE function over a run of T tokens on top of a state
(`cca_qkv`): a prompt in one block, a prompt's next block with the
tails carried (`cca_prompt`), and a tick's one token a slot (`cca_tick`)
give the same numbers, because what the convolutions read of the past
is what the state holds, in the state's type: `u` and `a` are rounded to
it BEFORE they are convolved, wherever they come from.

Everything here is XLA's: the products are small beside the expert
layer's (11 MB of weights a layer against 400), and steps 2 to 6 are
elementwise work over `[B, T, (H + G) d]` that XLA fuses around them
(PERF.md section 6, PR 56, says what share of a tick they are).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import mm
from .mamba2 import causal_conv
from .swa import cache_attention, prompt_attention

F32 = jnp.float32
Params = Dict[str, Any]
_TINY = 1e-12       # under the squared norm of step 5


def cca_state(batch: int, heads: int, kv_heads: int, head_dim: int,
              time0: int, time1: int, dtype: Any) -> Params:
    """An empty sequence's state for `batch` slots: zeros (`u_{-1} =
    a_{-1} = v2_{-1} = 0`)."""
    width = (heads + kv_heads) * head_dim
    return {"conv0": jnp.zeros((batch, time0 - 1, width), dtype),
            "conv1": jnp.zeros((batch, time1 - 1, width), dtype),
            "v2": jnp.zeros((batch, 1, kv_heads * head_dim // 2), dtype)}


def _rotate(x: jax.Array, positions: jax.Array, rotary: int, theta: float
            ) -> jax.Array:
    """x [B, T, heads, d] float32 at positions [B, T]: the first `rotary`
    channels of every head rotated (split-half), the rest as they are.
    The angles are made for these positions alone: no table."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    ang = positions.astype(F32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = jnp.split(x, [rotary // 2, rotary], axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _unit(x: jax.Array) -> jax.Array:
    """Each head's numbers at length sqrt(d)."""
    d = x.shape[-1]
    return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _TINY)
                * math.sqrt(d))


def cca_qkv(x: jax.Array, w: Params, state: Params, positions: jax.Array,
            *, heads: int, kv_heads: int, head_dim: int, rotary: int,
            theta: float) -> Tuple[jax.Array, jax.Array, jax.Array, Params]:
    """Steps 1 to 6 over a run: x [B, T, D] in the weights' type at
    `positions` [B, T], on top of `state` (`cca_state`'s entries, the
    tails BEFORE the run's first token). Returns (q [B, T, H, d], k, v
    [B, T, G, d] in x's type, rotated, and the state after the run's
    last token)."""
    b, t, _ = x.shape
    d, g = head_dim, heads // kv_heads
    lat_q, lat_k, half = heads * d, kv_heads * d, kv_heads * d // 2
    u, v1, v2 = jnp.split(mm(x, w["w_in"]), [lat_q + lat_k,
                                             lat_q + lat_k + half], -1)
    a, tail0 = causal_conv(u, state["conv0"], w["conv0_w"], w["conv0_b"],
                           activation=None)
    taps = state["conv1"].shape[1] + 1
    window = jnp.concatenate([state["conv1"].astype(a.dtype), a], axis=1)
    side = jnp.concatenate(
        [window[:, j:j + t].reshape(b * t, heads + kv_heads, d)
         for j in range(taps)], axis=-1)
    # the heads lead both operands: the one batched form every backend
    # has with float32 sums of bf16 products
    mixed = jnp.einsum("hnc,hcd->hnd", jnp.swapaxes(side, 0, 1),
                       w["conv1_w"], preferred_element_type=F32)
    mixed = jnp.swapaxes(mixed, 0, 1).reshape(b, t, heads + kv_heads, d) \
        + w["conv1_b"].astype(F32).reshape(heads + kv_heads, d)
    q_lat = u[..., :lat_q].astype(F32).reshape(b, t, heads, d)
    k_lat = u[..., lat_q:].astype(F32).reshape(b, t, kv_heads, d)
    q = mixed[:, :, :heads] + 0.5 * (q_lat + jnp.repeat(k_lat, g, axis=2))
    k = mixed[:, :, heads:] + 0.5 * (
        q_lat.reshape(b, t, kv_heads, g, d).mean(axis=3) + k_lat)
    q = _unit(q)
    k = _unit(k) * w["tau"].astype(F32)[:, None]
    q = _rotate(q, positions, rotary, theta).astype(x.dtype)
    k = _rotate(k, positions, rotary, theta).astype(x.dtype)
    before = jnp.concatenate([state["v2"].astype(v2.dtype), v2[:, :-1]], 1)
    v = jnp.concatenate([v1.reshape(b, t, kv_heads, d // 2),
                         before.reshape(b, t, kv_heads, d // 2)], axis=-1)
    new = {"conv0": tail0,
           "conv1": window[:, t:].astype(state["conv1"].dtype),
           "v2": v2[:, -1:].astype(state["v2"].dtype)}
    return q, k, v, new


def _from_zero(pos: Any) -> bool:
    """Whether `pos` is a 0 known while tracing: a run of tokens from it
    attends over itself alone."""
    try:
        return int(pos) == 0
    except TypeError:       # a tracer
        return False


def cca_prompt(x: jax.Array, w: Params, state: Params,
               cache: Optional[Params], pos: Any, **geometry
               ) -> Tuple[jax.Array, Params, Optional[Params]]:
    """The sublayer over a run of tokens x [B, T, D] at scalar position
    `pos`, on top of `state`: from a concrete 0 the prompt form over the
    run alone, else (a prompt's next block, a suffix) over the cache as
    it lies. The run's rows land in `cache` {"k", "v"} [B, S, G, d], if
    there is one. Returns (the output [B, T, D] float32, the state after
    the run, the cache)."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(pos + jnp.arange(t)[None, :], (b, t))
    q, k, v, state = cca_qkv(x, w, state, positions, **geometry)
    if cache is not None:
        cache = {n: jax.lax.dynamic_update_slice(
            cache[n], new.astype(cache[n].dtype), (0, pos, 0, 0))
            for n, new in (("k", k), ("v", v))}
    if cache is None or _from_zero(pos):
        a, _ = prompt_attention(q, k, v)
        a = a.reshape(b, t, -1)
    else:
        a = cache_attention(q, cache["k"], cache["v"], positions)
    return jnp.dot(a, w["wo"], preferred_element_type=F32), state, cache


def cca_tick(x: jax.Array, w: Params, state: Params, cache: Params,
             positions: jax.Array, **geometry
             ) -> Tuple[jax.Array, Params, Params]:
    """The sublayer for one token a slot: x [B, 1, D] at `positions` [B,
    1]. Every slot's tails are stepped, its key and value written at its
    position, and its walk reads the rows up to there
    (`ops/swa.decode_attention`). Returns (the output [B, 1, D] float32,
    the state, the cache)."""
    q, k, v, state = cca_qkv(x, w, state, positions, **geometry)
    at = (jnp.arange(x.shape[0])[:, None], positions)
    ck = cache["k"].at[at].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[at].set(v.astype(cache["v"].dtype))
    a = cache_attention(q, ck, cv, positions)
    return (jnp.dot(a, w["wo"], preferred_element_type=F32), state,
            {"k": ck, "v": cv})
