"""Multi-head latent attention (DeepSeek-V2's MLA) as functional ops, in
the two forms a served model needs. A token leaves ONE row in the cache,
`[c | k_r | 0]`: the normed latent c (`kv_lora_rank` wide), the key part
every head shares (`qk_rope_head_dim` wide; it carries no rotary embedding
in a family that applies none), zero-padded to whole tiles of 128 lanes so
that the slab is read and updated where it lies. Keys and values per head
are never stored: `W_kvb` [rank, H, d_n + d_v] makes them from c.

  expanded  over a prompt: k_n, v per head from c, causal softmax of
            (q_n . k_n + q_r . k_r) / sqrt(d_n + d_r) over the prompt
            alone. The scores are [H, T, T] float32.
  absorbed  over the cache in a decode tick: W_kvb's key half is folded
            into the query (q_n W_uk^T, rank wide), the scores are taken
            against the rows as they lie (H query heads to one shared
            row), the weighted sum of rows goes through W_uv. Same
            numbers, no per-head key or value formed over the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
LANES = 128


def row_width(rank: int, shared: int) -> int:
    """The cache row: rank + shared, up to whole tiles of lanes."""
    return -(-(rank + shared) // LANES) * LANES


def _padded(parts, width: int) -> jax.Array:
    """The parts side by side, zero-padded to `width` lanes."""
    pad = width - sum(x.shape[-1] for x in parts)
    if pad:
        parts = list(parts) + [jnp.zeros(parts[0].shape[:-1] + (pad,),
                                         parts[0].dtype)]
    return jnp.concatenate(parts, -1)


def latent_row(c: jax.Array, k_r: jax.Array, width: int, dtype
               ) -> jax.Array:
    """[c | k_r | 0] [.., width] in the cache's dtype."""
    return _padded([c, k_r], width).astype(dtype)


def expanded_attention(q_n: jax.Array, q_r: jax.Array, c: jax.Array,
                       k_r: jax.Array, w_kvb: jax.Array) -> jax.Array:
    """Causal attention over a prompt from position 0. q_n [B, T, H, d_n],
    q_r [B, T, H, d_r], c [B, T, rank], k_r [B, T, d_r], w_kvb [rank, H,
    d_n + d_v]. Returns [B, T, H, d_v] in q's dtype."""
    t, d_n, d_r = q_n.shape[1], q_n.shape[-1], q_r.shape[-1]
    kv = jnp.einsum("bsc,chd->bshd", c, w_kvb,
                    preferred_element_type=F32).astype(q_n.dtype)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scores = jnp.einsum("bthd,bshd->bhts", q_n, k_n,
                        preferred_element_type=F32) \
        + jnp.einsum("bthd,bsd->bhts", q_r, k_r,
                     preferred_element_type=F32)
    scores = scores / ((d_n + d_r) ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def absorbed_attention(q_n: jax.Array, q_r: jax.Array, rows: jax.Array,
                       positions: jax.Array, w_kvb: jax.Array) -> jax.Array:
    """Attention of q over the cache as it lies: rows [B, S, width] are
    `latent_row`s, query (b, j) sees rows <= positions[b, j]. q_n [B, t,
    H, d_n], q_r [B, t, H, d_r], w_kvb [rank, H, d_n + d_v]. Returns
    [B, t, H, d_v] in q's dtype."""
    rank, d_n, d_r = w_kvb.shape[0], q_n.shape[-1], q_r.shape[-1]
    w_uk, w_uv = w_kvb[..., :d_n], w_kvb[..., d_n:]
    q_c = jnp.einsum("bthd,chd->bthc", q_n, w_uk,
                     preferred_element_type=F32).astype(q_n.dtype)
    scores = jnp.einsum("bthw,bsw->bhts",
                        _padded([q_c, q_r], rows.shape[-1]), rows,
                        preferred_element_type=F32)
    scores = scores / ((d_n + d_r) ** 0.5)
    col = jnp.arange(rows.shape[1])[None, None, None, :]
    scores = jnp.where(col <= positions[:, None, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
    # the values are the first `rank` lanes of the key row: the sum is
    # taken over the whole row where it lies and cut afterwards
    mixed = jnp.einsum("bhts,bsw->bthw", probs, rows)[..., :rank]
    return jnp.einsum("bthc,chd->bthd", mixed, w_uv,
                      preferred_element_type=F32).astype(q_n.dtype)
