"""Prompt-form attention for grouped-query heads, over the whole prompt
or under a sliding window: what a served model's prefill needs where
`rep` query heads share one key-value head and a layer may see only the
last `window` positions (`models/smallthinker.py`).

A module of its own beside `ops/mla.py`, whose pattern it follows, and
not a third family in `ops/attention.py`: the flash kernels there are the
training step's (a custom VJP, equal head counts, blocks chosen for a
batch of 1,024-token rows), and nothing of them is shared with a
forward-only kernel whose unit of work is a key-value head with its
query heads stacked, and whose walk is a band.

  plain     a prompt of at most one block: the masked softmax over
            [H, T, T] as it stands.
  prompt    the same numbers without ever holding [H, T, T]
            (`prompt_attention`): square blocks of `block` tokens under a
            running softmax. A block of queries walks the key blocks from
            the first one the window lets it see to the diagonal: a block
            the causal mask or the band hides wholly is never visited,
            and only the blocks the band's edge or the diagonal crosses
            are masked. On a TPU it is the Pallas kernel
            `gqa_prefill_w<window>_t<T>` (`w0`: no window): one program a
            key-value head and block of queries, the head's keys and
            values resident, its `rep` query heads stacked along the rows
            of ONE product ([rep x block, d] against a key block: the
            matrix unit is fed rep x block rows and a key block is read
            once for all of them). Elsewhere the same blocks in
            `jax.numpy`, which is also the kernel's reference.

The window counts the query's own position: query i sees keys j with
`i - window < j <= i`, so a ring of `window` rows holds exactly what a
decode tick may see.
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
_NEG = -1e30
_LOG2E = 1.4426950408889634
# what the kernel holds of a v5e's 128 MB of VMEM: one head's keys and
# values twice (the next head's arrive under the compute), 16 MB at 16,384
# tokens of 128 numbers and 34 MB at 32,768; a block of queries and its
# output twice, the float32 accumulator, and the block's scores
_VMEM_LIMIT = 100 << 20
# the float32 scores of one block of queries against one key block, `rep x
# block` rows of `block`: 7 MB for seven stacked heads at 512, the most
# measured (PERF.md, PR 37); `_fit_block` keeps a wider group under it
_SCORE_BYTES = 8 << 20


def _fit_block(block: int, rep: int) -> int:
    """The caller's block, halved (not under 128) until the scores of
    `rep` stacked query heads fit `_SCORE_BYTES`: 512 stays 512 for a
    group of 4 or 7 (and 8), a group of 20 takes 256."""
    while block > 128 and 4 * rep * block * block > _SCORE_BYTES:
        block //= 2
    return block


def _first_block(i, block: int, window: Optional[int]):
    """The first key block the query block `i` visits: the one that holds
    key `i * block - window + 1`, the oldest its first query sees."""
    if window is None:
        return 0 * i
    return jnp.maximum(i * block - window + 1, 0) // block


def _first_whole_block(i, block: int, window: Optional[int]):
    """The first key block EVERY query of block `i` sees whole (the last
    query sees keys from `(i + 1) * block - window` on), held to `i`."""
    if window is None:
        return 0 * i
    return jnp.minimum(
        (jnp.maximum((i + 1) * block - window, 0) + block - 1) // block, i)


def visited_blocks(tokens: int, block: int, window: Optional[int]
                   ) -> Tuple[int, int]:
    """(the blocks of scores one head's walk over a prompt visits, what a
    causal walk with no window would visit), by hand: the prompt form
    returns the first."""
    nb = -(-tokens // block)
    causal = nb * (nb + 1) // 2
    if window is None or nb == 1:
        return causal, causal
    return sum(i - max(i * block - window + 1, 0) // block + 1
               for i in range(nb)), causal


def _seen(q_at, k_at, window: Optional[int]):
    seen = k_at <= q_at
    if window is not None:
        seen &= k_at > q_at - window
    return seen


def plain_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    window: Optional[int] = None) -> jax.Array:
    """The masked softmax as it stands: q [B, T, H, d], k, v [B, T, G, d],
    H = rep x G, head h of group h // rep. Returns [B, T, H, d]."""
    b, t, h, d = q.shape
    g = k.shape[2]
    qg = q.reshape(b, t, g, h // g, d)
    s = jnp.einsum("btgrd,bsgd->bgrts", qg, k,
                   preferred_element_type=F32) / (d ** 0.5)
    at = jnp.arange(t)
    s = jnp.where(_seen(at[:, None], at[None, :], window), s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrts,bsgd->btgrd", p, v).reshape(b, t, h, d)


def _stacked(q: jax.Array, groups: int, block: int) -> jax.Array:
    """q [B, T, H, d] -> [B G, T / block, rep x block, d]: a key-value
    head's query heads one under another, block of queries by block."""
    b, t, h, d = q.shape
    rep = h // groups
    x = q.reshape(b, t // block, block, groups, rep, d)
    return x.transpose(0, 3, 1, 4, 2, 5).reshape(
        b * groups, t // block, rep * block, d)


def _unstacked(o: jax.Array, batch: int, block: int) -> jax.Array:
    """`_stacked`'s inverse on the output."""
    bg, nb, rows, d = o.shape
    groups, rep = bg // batch, rows // block
    x = o.reshape(batch, groups, nb, rep, block, d)
    return x.transpose(0, 2, 4, 1, 3, 5).reshape(
        batch, nb * block, groups * rep, d)


def _blocked(q, k, v, block: int, window: Optional[int]) -> jax.Array:
    """The prompt form in `jax.numpy` on the kernel's own layout: q [B G,
    nb, rep x block, d], k, v [B G, T, d]. A block of queries takes the
    same walk as the kernel's, as a scan of the most steps any block
    needs; a step that falls before the block's first key block leaves
    the carry as it was (static trip counts: it can be differentiated,
    and is what a backend without Mosaic runs)."""
    bg, nb, rows, d = q.shape
    steps = nb if window is None else min(nb, -(-window // block) + 1)
    scale = d ** -0.5
    q_in = jnp.arange(rows) % block
    k_in = jnp.arange(block)

    def q_block(args):
        i, q_i = args                                   # [B G, rows, d]
        lo = _first_block(i, block, window)

        def k_block(carry, back):
            m, l, acc = carry
            j = i - back
            at = jnp.maximum(j, 0) * block
            k_j = jax.lax.dynamic_slice_in_dim(k, at, block, 1)
            v_j = jax.lax.dynamic_slice_in_dim(v, at, block, 1)
            s = jnp.einsum("gtd,gsd->gts", q_i, k_j,
                           preferred_element_type=F32) * scale
            seen = _seen((i * block + q_in)[:, None],
                         (j * block + k_in)[None, :], window)
            s = jnp.where(seen[None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            new = (m_new, alpha * l + p.sum(-1),
                   acc * alpha[..., None] + jnp.einsum(
                       "gts,gsd->gtd", p.astype(v.dtype), v_j,
                       preferred_element_type=F32))
            return jax.tree.map(
                lambda a, b: jnp.where(j >= lo, a, b), new, carry), None

        init = (jnp.full((bg, rows), _NEG, F32), jnp.zeros((bg, rows), F32),
                jnp.zeros((bg, rows, d), F32))
        # oldest block first, the diagonal last: a query whose first
        # visited block holds none of its keys is put right by the next
        (_, l, acc), _ = jax.lax.scan(
            k_block, init, jnp.arange(steps - 1, -1, -1))
        return (acc / l[..., None]).astype(q.dtype)

    out = jax.lax.map(q_block, (jnp.arange(nb), jnp.moveaxis(q, 1, 0)))
    return jnp.moveaxis(out, 0, 1)


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, *, block: int,
                    window: Optional[int]):
    """One (batch x key-value head, block of queries) program. Refs: q
    [rep x block, d], the head's query heads stacked; k, v [T, d] of this
    head; o [rep x block, d]. The key blocks the band's edge crosses come
    first and are masked, those every query sees whole need no mask, the
    diagonal one closes the walk. The scale and log2(e) are folded into
    the queries, so the softmax is exp2 alone; products take the inputs'
    dtype and accumulate in float32."""
    qi = pl.program_id(1)
    cd = q_ref.dtype
    rows, d = q_ref.shape
    q = (q_ref[...].astype(F32) * (d ** -0.5 * _LOG2E)).astype(cd)

    def step(ki, carry, masked):
        m_prev, l_prev, acc = carry
        at = pl.ds(pl.multiple_of(ki * block, block), block)
        s = jax.lax.dot_general(q, k_ref[at, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if masked:
            q_at = qi * block + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0),
                block)
            k_at = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block), 1)
            s = jnp.where(_seen(q_at, k_at, window), s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(cd), v_ref[at, :], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        return m_new, alpha * l_prev + jnp.sum(p, -1, keepdims=True), acc

    carry = (jnp.full((rows, 1), _NEG, F32), jnp.zeros((rows, 1), F32),
             jnp.zeros((rows, d), F32))
    whole = _first_whole_block(qi, block, window)
    carry = jax.lax.fori_loop(
        _first_block(qi, block, window), whole,
        functools.partial(step, masked=True), carry)
    carry = jax.lax.fori_loop(
        whole, qi, functools.partial(step, masked=False), carry)
    _, l, acc = step(qi, carry, True)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _prefill_pallas(q, k, v, block: int, window: Optional[int],
                    tokens: int, visited: int, interpret: bool
                    ) -> jax.Array:
    """Jitted on its own so that the layers of a program, which call it
    at one shape, share ONE lowering of the kernel (each costs a model's
    set-up some 30 ms, a cache hit or not: PERF.md, PR 38)."""
    bg, nb, rows, d = q.shape
    t = k.shape[1]
    per_head = pl.BlockSpec((None, t, d), lambda g, i: (g, 0, 0))
    per_block = pl.BlockSpec((None, None, rows, d),
                             lambda g, i: (g, i, 0, 0))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, block=block, window=window),
        grid=(bg, nb),
        in_specs=[per_block, per_head, per_head],
        out_specs=per_block,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        # the window and the prompt's length are in the name, so that a
        # trace says what work each event did (benchmarks:
        # gqa_prefill_roofline.tput)
        name=f"gqa_prefill_w{window or 0}_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * bg * visited * rows * block * d,
            bytes_accessed=(2 * q.size + k.size + v.size)
            * q.dtype.itemsize,
            transcendentals=bg * visited * rows * block),
    )(q, k, v)


def prompt_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     window: Optional[int] = None, block: int = 512
                     ) -> Tuple[jax.Array, int]:
    """Causal attention over a prompt from position 0, query i seeing the
    keys `i - window < j <= i` (all of `j <= i` with no window), without
    the [H, T, T] scores (module docstring). q [B, T, H, d]; k, v [B, T,
    G, d], head h of key-value head h // (H / G). Returns ([B, T, H, d] in
    q's dtype, the blocks of scores one head's walk visited). A prompt
    that is not a whole number of blocks is padded with rows no real
    query sees; a window no shorter than the prompt is no window. `block`
    is the most a block of tokens may be: a group too wide for it takes a
    smaller one (`_fit_block`)."""
    b, t, h, d = q.shape
    groups = k.shape[2]
    block = _fit_block(block, h // groups)
    if window is not None and window >= t:
        window = None
    if t <= block:
        return plain_attention(q, k, v, window), 1
    pad = -t % block
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    visited, _ = visited_blocks(t, block, window)
    heads_first = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * groups, t + pad, d)
    stacked = _stacked(q, groups, block)
    shape = (b, t, h, groups, d, window or 0)
    resident = 2 * 2 * (t + pad) * d * q.dtype.itemsize
    reason = dispatch.backend_reason() or (
        "" if resident < _VMEM_LIMIT // 2 else
        f"{resident} bytes of keys and values a head exceed the kernel's "
        "VMEM")
    if reason:
        dispatch.record_choice("gqa_prefill", shape, "reference", reason)
        out = _blocked(stacked, heads_first(k), heads_first(v), block,
                       window)
    else:
        dispatch.record_choice("gqa_prefill", shape, "pallas")
        out = _prefill_pallas(stacked, heads_first(k), heads_first(v),
                              block, window, t, visited,
                              dispatch.interpret_forced())
    return _unstacked(out, b, block)[:, :t], visited
