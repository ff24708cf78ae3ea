"""Normalization layers as functional TPU-friendly ops.

fp32 statistics regardless of input dtype (bf16 activations on TPU), output
cast back — XLA fuses the whole thing into surrounding elementwise work, so
there is no Pallas kernel here on purpose: a hand-written layernorm would
only deny XLA the fusion with its neighbors.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array,
             eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """`x @ w` accumulated in float32, handed back in x's dtype: the
    projection every served family's block makes."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def lora_delta(h: jax.Array, a: jax.Array, b: jax.Array,
               scale: jax.Array) -> jax.Array:
    """Per-slot scatter-gathered LoRA contribution for a ragged decode
    batch: ``scale * (h @ A) @ B`` with a DIFFERENT adapter per batch
    row (the cross-tenant batched-decode matmul; serve/lora.py gathers
    A/B out of the adapter pool by each slot's adapter index before the
    call).

    ``h [B, t, d]``, ``a [B, d, r]``, ``b [B, r, o]``, ``scale [B]`` ->
    ``[B, o or t, o]`` in ``h.dtype``. fp32 accumulation like the base
    matmuls; rows whose adapter is the null slot (A == B == 0,
    scale == 0) contribute an exact-zero delta, so adding it to the base
    projection leaves those rows' values unchanged. Structured as the
    Pallas ragged-matmul kernel candidate (grouped by adapter index) the
    autotuner item will sweep — today it lowers to two batched einsums.
    """
    z = jnp.einsum("btd,bdr->btr", h, a,
                   preferred_element_type=jnp.float32)
    d = jnp.einsum("btr,bro->bto", z, b,
                   preferred_element_type=jnp.float32)
    return (d * scale[:, None, None]).astype(h.dtype)
