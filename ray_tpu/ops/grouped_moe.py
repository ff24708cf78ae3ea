"""A dropless expert layer for one share of an expert-parallel
deployment: the router scores ALL experts, this share is told which run
of them it holds, and it computes the part of the result its own experts
give, for every token routed to them. No capacity factor, no token
dropped, nothing that stands in for the experts held elsewhere (on one
chip the layer runs without its exchange; the partial sum is what goes
on). `ops/moe.py` is the older GShard path (one-hot dispatch, a
capacity that drops), which lives on under `models/moe_transformer.py`.

The token-expert pairs are sorted by expert (pairs of experts held
elsewhere last), the rows gathered in that order, and the two expert
products are `jax.lax.ragged_dot` over the groups: on a TPU XLA's own
grouped-matmul kernel, which reads each held expert's weights once and
visits only the rows that exist; elsewhere its reference lowering.

Three callers, three expert shapes: `models/nemotron_h.py` (experts in
a latent space, `w1` [held, L, I] under relu squared),
`models/kimi_linear.py` (SwiGLU experts at full hidden width: gate and up
packed in ONE `w1` [held, D, 2 I], and an `activation` that maps the
[rows, 2 I] product to `silu(gate) * up` [rows, I], which `w2` [held, I, D]
takes) and `models/deepseek_v2.py` (the same packing at `w1` [40, 5120,
3072], `w2` [40, 1536, 5120], and over a prompt hundreds of rows an
expert, in token blocks: the buffers here are sized for ALL T x k pairs,
held or not). `held_experts` asks nothing of the activation but that it
keeps the rows. Two routers over sigmoid scores share
`sigmoid_topk_route` (the bias chooses); the third,
`softmax_group_limited_route`, scores by softmax and lets only the best
GROUPS of experts compete, as a deployment that keeps a group on a chip
does.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sigmoid_topk_route(h: jax.Array, w_router: jax.Array, bias: jax.Array,
                       k: int, scale: float, normalize: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores in float32 over every expert; the k largest of
    score + bias are chosen (the bias chooses, it does not weigh); the
    chosen scores, normalised over all k if `normalize`, times `scale`.
    h [T, D], w_router [D, E], bias [E] -> (experts [T, k] int32,
    weights [T, k] float32)."""
    scores = jax.nn.sigmoid(jnp.dot(h, w_router,
                                    preferred_element_type=F32))
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def softmax_group_limited_route(h: jax.Array, w_router: jax.Array, k: int,
                                n_group: int, topk_group: int, scale: float
                                ) -> Tuple[jax.Array, jax.Array]:
    """`topk_method` group_limited_greedy: softmax scores in float32 over
    every expert; the experts stand in `n_group` equal runs, a group's
    score is its largest, only the `topk_group` best groups compete, and
    the k largest scores among them are chosen (a larger score in a group
    left out is NOT). The weights are the chosen scores as they are, not
    renormalised, times `scale`. h [T, D], w_router [D, E] -> (experts
    [T, k] int32, weights [T, k] float32)."""
    scores = jax.nn.softmax(
        jnp.dot(h.astype(F32), w_router.astype(F32),
                precision=jax.lax.Precision.HIGHEST), axis=-1)
    t, e = scores.shape
    grouped = scores.reshape(t, n_group, e // n_group)
    _, groups = jax.lax.top_k(grouped.max(-1), topk_group)
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], groups].set(True)
    # a softmax score is positive, so a zero never wins a place
    masked = jnp.where(keep[:, :, None], grouped, 0.0).reshape(t, e)
    weights, chosen = jax.lax.top_k(masked, k)
    return chosen.astype(jnp.int32), weights * scale


def held_experts(u: jax.Array, chosen: jax.Array, weights: jax.Array,
                 w1: jax.Array, w2: jax.Array, first: int,
                 activation: Callable[[jax.Array], jax.Array]
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """sum over a token's chosen experts HELD HERE of weight_e *
    (activation(u W1_e) W2_e). u [T, L]; chosen, weights [T, k] over all
    experts; w1 [H, L, I] (or [H, L, 2 I] under an activation that
    halves the width), w2 [H, I, L] are experts first .. first+H-1.
    Returns ([T, L] float32, {"pairs_held": pairs that fell on held
    experts, "rows_max": the most rows one held expert got, "sizes":
    the rows each held expert got [H]}, int32)."""
    t, k = chosen.shape
    held = w1.shape[0]
    local = chosen.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)          # elsewhere: sorted last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    rows = u[order // k]
    mid = jax.lax.ragged_dot(rows, w1, sizes, preferred_element_type=F32)
    mid = activation(mid).astype(u.dtype)
    out = jax.lax.ragged_dot(mid, w2, sizes, preferred_element_type=F32)
    # rows past the last group belong to no expert here: whatever the
    # grouped product left there is not a number to scale
    out = jnp.where(here[order][:, None], out, 0.0) \
        * weights.reshape(-1)[order][:, None]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    result = out[back].reshape(t, k, -1).sum(axis=1)
    return result, {"pairs_held": sizes.sum(), "rows_max": sizes.max(),
                    "sizes": sizes}
