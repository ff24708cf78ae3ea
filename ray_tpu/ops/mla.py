"""Multi-head latent attention (DeepSeek-V2's MLA) as functional ops, in
the forms a served model needs. A token leaves ONE row in the cache,
`[c | k_r | 0]`: the normed latent c (`kv_lora_rank` wide), the key part
every head shares (`qk_rope_head_dim` wide: rotated by the caller in a
family that has rotary positions, `models/deepseek_v2.py`, and plain in
one that applies none, `models/kimi_linear.py`), zero-padded to whole
tiles of 128 lanes so that the slab is read and updated where it lies.
Keys and values per head are never stored: `W_kvb` [rank, H, d_n + d_v]
makes them from c.

  expanded  over a prompt: k_n, v per head from c, causal softmax of
            (q_n . k_n + q_r . k_r) * scale over the prompt alone. The
            scores are [H, T, T] float32: 1.1 GB at 32 heads and 2,048
            tokens, 34 GB at 128 heads and 8,192.
  prompt    the same numbers without ever holding [H, T, T]
            (`prompt_attention`): square blocks of `block` tokens under a
            running softmax, the blocks above the diagonal never visited.
            On a TPU it is the Pallas kernel `mla_prefill_t<T>` (one
            program a head and block of queries, the head's keys and
            values resident, two products a block: q_n . k_n over d_n and
            q_r . k_r over d_r against the ONE shared key part); elsewhere
            the same blocks in `jax.numpy`, which is also the kernel's
            reference. A prompt of one block takes the expanded form.
  absorbed  over the cache in a decode tick: W_kvb's key half is folded
            into the query (q_n W_uk^T, rank wide), the scores are taken
            against the rows as they lie (H query heads to one shared
            row), the weighted sum of rows goes through W_uv. Same
            numbers, no per-head key or value formed over the cache. At
            128 heads it does 128 x (576 + 512) x 2 FLOPs for each
            1,280-byte row it reads, 218 FLOP/B: at the ridge of a v5e,
            where Kimi-Linear's 32 heads (54 FLOP/B) are bandwidth-bound.

Every form takes the softmax `scale`; left None it is (d_n + d_r)^-1/2,
by the division the first caller's numbers were made with. A YaRN model
passes its own (`ops/rope.yarn_mscale`).
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
LANES = 128
_NEG = -1e30
_LOG2E = 1.4426950408889634
# the kernel holds one head's keys and values in VMEM, twice (the next
# head's arrive under the compute): 6 MB at 8,192 tokens, of a v5e's 128
_VMEM_LIMIT = 96 << 20


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


def _scaled(scores: jax.Array, width: int, scale: Optional[float]
            ) -> jax.Array:
    if scale is None:
        return scores / (width ** 0.5)
    return scores * scale


def _expand(c: jax.Array, w_kvb: jax.Array, d_n: int, dtype
            ) -> Tuple[jax.Array, jax.Array]:
    """The per-head keys' first part and the values from the latent."""
    kv = jnp.einsum("bsc,chd->bshd", c, w_kvb,
                    preferred_element_type=F32).astype(dtype)
    return kv[..., :d_n], kv[..., d_n:]


def expanded_attention(q_n: jax.Array, q_r: jax.Array, c: jax.Array,
                       k_r: jax.Array, w_kvb: jax.Array,
                       scale: Optional[float] = None) -> jax.Array:
    """Causal attention over a prompt from position 0. q_n [B, T, H, d_n],
    q_r [B, T, H, d_r], c [B, T, rank], k_r [B, T, d_r], w_kvb [rank, H,
    d_n + d_v]. Returns [B, T, H, d_v] in q's dtype."""
    t, d_n, d_r = q_n.shape[1], q_n.shape[-1], q_r.shape[-1]
    k_n, v = _expand(c, w_kvb, d_n, q_n.dtype)
    scores = jnp.einsum("bthd,bshd->bhts", q_n, k_n,
                        preferred_element_type=F32) \
        + jnp.einsum("bthd,bsd->bhts", q_r, k_r,
                     preferred_element_type=F32)
    scores = _scaled(scores, d_n + d_r, scale)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def absorbed_attention(q_n: jax.Array, q_r: jax.Array, rows: jax.Array,
                       positions: jax.Array, w_kvb: jax.Array,
                       scale: Optional[float] = None) -> jax.Array:
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
    scores = _scaled(scores, d_n + d_r, scale)
    col = jnp.arange(rows.shape[1])[None, None, None, :]
    scores = jnp.where(col <= positions[:, None, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
    # the values are the first `rank` lanes of the key row: the sum is
    # taken over the whole row where it lies and cut afterwards
    mixed = jnp.einsum("bhts,bsw->bthw", probs, rows)[..., :rank]
    return jnp.einsum("bthc,chd->bthd", mixed, w_uv,
                      preferred_element_type=F32).astype(q_n.dtype)


# ------------------------------------------------------ the prompt form

def _blocked(q_n, q_r, k_n, k_r, v, scale: float, block: int) -> jax.Array:
    """The prompt form in `jax.numpy`: q_n [B, T, H, d_n], q_r [B, T, H,
    d_r], k_n [B, T, H, d_n], k_r [B, T, d_r], v [B, T, H, d_v], T a
    whole number of blocks. Every block of keys is visited and the ones
    above the diagonal masked whole, so that the trip counts are static
    (it can be differentiated, and is what a backend without Mosaic
    runs); the kernel visits the lower triangle alone."""
    b, t, h, d_v = v.shape
    nb = t // block

    def cut(x):                       # [B, T, ...] -> [nb, B, block, ...]
        return jnp.moveaxis(x.reshape((b, nb, block) + x.shape[2:]), 1, 0)

    kn_b, kr_b, v_b = cut(k_n), cut(k_r), cut(v)
    at = jnp.arange(block)

    def q_block(args):
        i, qn_i, qr_i = args

        def k_block(carry, inp):
            m, l, acc = carry
            j, kn_j, kr_j, v_j = inp
            s = (jnp.einsum("bthd,bshd->bhts", qn_i, kn_j,
                            preferred_element_type=F32)
                 + jnp.einsum("bthd,bsd->bhts", qr_i, kr_j,
                              preferred_element_type=F32)) * scale
            seen = (i * block + at)[:, None] >= (j * block + at)[None, :]
            s = jnp.where(seen[None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            # block 0 comes first and every query sees a key of it, so
            # the maximum is a real score and a masked one weighs 0
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhts,bshd->bhtd", p.astype(v.dtype), v_j,
                preferred_element_type=F32)
            return (m_new, alpha * l + p.sum(-1), acc), None

        init = (jnp.full((b, h, block), _NEG, F32),
                jnp.zeros((b, h, block), F32),
                jnp.zeros((b, h, block, d_v), F32))
        (_, l, acc), _ = jax.lax.scan(
            k_block, init, (jnp.arange(nb), kn_b, kr_b, v_b))
        return (acc / l[..., None]).astype(v.dtype)      # [B, H, blk, dv]

    out = jax.lax.map(q_block, (jnp.arange(nb), cut(q_n), cut(q_r)))
    return jnp.moveaxis(out, (0, 3), (1, 2)).reshape(b, t, h, d_v)


def _prefill_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, *,
                    scale: float, block: int):
    """One (batch x head, block of queries) program. Refs: qn [block,
    d_n], qr [block, d_r], kn [T, d_n] and v [T, d_v] of this head, kr [T,
    d_r] of this sequence, o [block, d_v]. The blocks of keys below the
    diagonal need no mask; the diagonal one closes the loop. The scale
    and log2(e) are folded into the queries, so the softmax is exp2
    alone; products take the inputs' dtype and accumulate in float32."""
    qi = pl.program_id(1)
    cd = qn_ref.dtype
    fold = scale * _LOG2E
    qn = (qn_ref[...].astype(F32) * fold).astype(cd)
    qr = (qr_ref[...].astype(F32) * fold).astype(cd)
    last = (((1,), (1,)), ((), ()))

    def step(ki, carry, diagonal):
        m_prev, l_prev, acc = carry
        rows = pl.ds(pl.multiple_of(ki * block, block), block)
        s = jax.lax.dot_general(qn, kn_ref[rows, :], last,
                                preferred_element_type=F32) \
            + jax.lax.dot_general(qr, kr_ref[rows, :], last,
                                  preferred_element_type=F32)
        if diagonal:
            q_at = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            k_at = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(q_at >= k_at, s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(cd), v_ref[rows, :], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        return m_new, alpha * l_prev + jnp.sum(p, -1, keepdims=True), acc

    carry = (jnp.full((block, 1), _NEG, F32), jnp.zeros((block, 1), F32),
             jnp.zeros((block, o_ref.shape[-1]), F32))
    carry = jax.lax.fori_loop(
        0, qi, functools.partial(step, diagonal=False), carry)
    _, l, acc = step(qi, carry, True)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _prefill_pallas(q_n, q_r, k_n, k_r, v, scale: float, block: int,
                    tokens: int, interpret: bool) -> jax.Array:
    b, t, h, d_n = q_n.shape
    d_r, d_v = q_r.shape[-1], v.shape[-1]

    def heads_first(x):               # [B, T, H, d] -> [B H, T, d]
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])

    per_head = lambda d: pl.BlockSpec((None, t, d), lambda g, i: (g, 0, 0))
    per_block = lambda d: pl.BlockSpec((None, block, d),
                                       lambda g, i: (g, i, 0))
    pairs = t // block * (t // block + 1) // 2
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block=block),
        grid=(b * h, t // block),
        in_specs=[per_block(d_n), per_block(d_r), per_head(d_n),
                  pl.BlockSpec((None, t, d_r), lambda g, i: (g // h, 0, 0)),
                  per_head(d_v)],
        out_specs=per_block(d_v),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d_v), q_n.dtype),
        interpret=interpret,
        # the prompt's length is in the name, so that a trace says what
        # work each event did (benchmarks: mla_prefill_roofline.tput)
        name=f"mla_prefill_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * pairs * block * block * (d_n + d_r + d_v),
            bytes_accessed=(q_n.size + q_r.size + k_n.size + k_r.size
                            + 2 * v.size) * q_n.dtype.itemsize,
            transcendentals=b * h * pairs * block * block),
    )(heads_first(q_n), heads_first(q_r), heads_first(k_n), k_r,
      heads_first(v))
    return out.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)


def prompt_attention(q_n: jax.Array, q_r: jax.Array, c: jax.Array,
                     k_r: jax.Array, w_kvb: jax.Array,
                     scale: Optional[float] = None, block: int = 512
                     ) -> Tuple[jax.Array, int]:
    """`expanded_attention`'s numbers over a prompt of any length without
    its [H, T, T] scores (module docstring); the arguments are the same.
    Returns ([B, T, H, d_v] in q's dtype, the blocks of scores that were
    computed). A prompt that is not a whole number of blocks is padded
    with rows no real query sees."""
    b, t, h, d_n = q_n.shape
    d_r = q_r.shape[-1]
    if t <= block:
        return expanded_attention(q_n, q_r, c, k_r, w_kvb, scale), 1
    if scale is None:
        scale = (d_n + d_r) ** -0.5
    pad = -t % block
    if pad:
        q_n, q_r, c, k_r = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q_n, q_r, c, k_r))
    k_n, v = _expand(c, w_kvb, d_n, q_n.dtype)
    nb = (t + pad) // block
    shape = (b, t, h, d_n + d_r, v.shape[-1])
    resident = 2 * (t + pad) * 3 * LANES * q_n.dtype.itemsize
    reason = dispatch.backend_reason() or (
        "" if resident < _VMEM_LIMIT // 2 else
        f"{resident} bytes of keys and values a head exceed the kernel's "
        "VMEM")
    if reason:
        dispatch.record_choice("mla_prefill", shape, "reference", reason)
        out, blocks = _blocked(q_n, q_r, k_n, k_r, v, scale, block), nb * nb
    else:
        dispatch.record_choice("mla_prefill", shape, "pallas")
        out = _prefill_pallas(q_n, q_r, k_n, k_r, v, scale, block, t,
                              dispatch.interpret_forced())
        blocks = nb * (nb + 1) // 2
    return out[:, :t], blocks
