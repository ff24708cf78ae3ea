"""Rotary position embeddings (RoPE) — split-half (GPT-NeoX) convention.

TPU notes: cos/sin tables are precomputed fp32 and broadcast (tiny HBM
cost); the rotation is pure elementwise work that XLA fuses into the
surrounding QK projections, so no Pallas kernel is warranted here."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rope_table(head_dim: int, max_seq_len: int,
               theta: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin), each [max_seq_len, head_dim // 2], fp32."""
    if head_dim % 2:
        raise ValueError("RoPE needs an even head_dim")
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [T, hd/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_table(head_dim: int, max_seq_len: int, theta: float, factor: float,
               original_max: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0, mscale: float = 1.0,
               mscale_all_dim: float = 0.0
               ) -> Tuple[jax.Array, jax.Array]:
    """`rope_table` under YaRN (`rope_scaling.type` yarn, as DeepSeek-V2
    publishes it): frequency i of the `head_dim // 2` turns `r_i =
    original_max f_i / (2 pi)` times over the original window; those that
    turn more than `beta_fast` times keep `f_i`, those that turn fewer
    than `beta_slow` times are slowed to `f_i / factor`, and a linear ramp
    over the index, from `low = floor(cd(beta_fast))` to `high =
    ceil(cd(beta_slow))`, `cd(r) = head_dim ln(original_max / (2 pi r)) /
    (2 ln theta)`, blends the rest. cos and sin are multiplied by
    `yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)`.
    A position's row does not depend on `max_seq_len`."""
    inv_freq = yarn_inv_freq(head_dim, theta, factor, original_max,
                             beta_fast, beta_slow)
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def yarn_correction_range(head_dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float
                          ) -> Tuple[int, int]:
    """(low, high) of the ramp, cut to the indices there are."""
    def cd(rotations: float) -> float:
        return head_dim * math.log(
            original_max / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    return (max(math.floor(cd(beta_fast)), 0),
            min(math.ceil(cd(beta_slow)), head_dim - 1))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """[head_dim // 2] float32: `f_i (1 - ramp_i) + (f_i / factor)
    ramp_i`, `ramp_i = clip((i - low) / (high - low), 0, 1)`."""
    if head_dim % 2:
        raise ValueError("RoPE needs an even head_dim")
    f = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim))
    low, high = yarn_correction_range(head_dim, theta, original_max,
                                      beta_fast, beta_slow)
    span = max(high - low, 0.001)       # low == high: a step, as published
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / span, 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def yarn_mscale(factor: float, mscale: float) -> float:
    """`0.1 mscale ln(factor) + 1`; 1 where nothing is stretched. The
    softmax scale of a YaRN model is `d^-0.5 yarn_mscale(factor,
    mscale_all_dim)^2`."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotate q or k. x: [B, T, H, hd]; cos/sin: [>=T, hd/2];
    positions: optional [B, T] int32 (defaults to arange — use for
    decode-time offsets)."""
    t = x.shape[1]
    if positions is None:
        c = cos[:t][None, :, None, :]  # [1, T, 1, hd/2]
        s = sin[:t][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]  # [B, T, 1, hd/2]
        s = sin[positions][:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)
