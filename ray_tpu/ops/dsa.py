"""Learned sparse attention: an INDEXER scores every visible row for a
query, the `topk` best rows are kept, and attention runs over the kept
rows alone: latent attention (`ops/mla.py`, one latent row a token that
all heads share) or GROUPED-QUERY attention (keys and values in pairs,
`group` query heads to a head of them). The score and the choice are one
code for both; what attends differs. The first op in the tree that takes
a SET of rows.

  score   `I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`: `heads`
          index heads of `dim` against ONE index key a token (its own
          entry in the slab, `dim` wide: a tick scores `S x 2 dim` bytes,
          not the latent rows), a learned weight a head (any sign, the
          caller's scale folded in), summed to ONE score a pair, float32.
          A row the query may not see (s > t) scores `NEG`.
  select  the `topk` largest visible scores of each query; all of them
          while `t + 1 <= topk`. Over a prompt the set is a MASK `[T, T]`
          of int8: the k-th largest score of each row is found exactly,
          by 32 steps of bisection on the scores' bit patterns (a
          float32's bits order as the number does once the negative half
          is flipped), and the mask is `score >= that`. Scores tied with
          the k-th are ALL kept (`lax.top_k` keeps the earliest): one row
          more than `topk`, where the reference keeps `topk`. In a tick
          the set is `lax.top_k`'s indices, and the rows are gathered; a
          caller may have the tick stop at the furthest live position
          (`tick_rows`, `top_rows`: the same set from shorter sorts).
  attend  over a prompt, the blocked prompt form under the mask
          (`selected_prompt_attention`): every visible block of scores is
          still computed and the unselected pairs weigh nothing, a sound
          first form (the selection saves no operation yet; the share of
          the kernel's time its SELECTED pairs would need is the
          benchmark's `dsa_attention_roofline.tput`). In a tick, the
          absorbed form over the `topk` gathered rows
          (`mla.absorbed_attention` with `visible`). For grouped-query
          heads the same two: `gqa_selected_prompt_attention` (the
          `group` query heads of a key head stacked on ONE block of its
          keys, so that a block of keys and a tile of the mask are read
          and decoded once for all of them) and `gqa_selected_tick` (a
          gather of the `topk` rows of keys and of values a slot, and
          attention over them).

On a TPU the prompt steps are Pallas kernels, named for a trace:
`dsa_index_t<T>`, `dsa_select_t<T>`, `mla_selected_t<T>`,
`gqa_selected_t<T>` (T the prompt's length); elsewhere the same numbers in `jax.numpy`, which is also the
kernels' reference. Everything here is ONE sequence (no batch axis): the
caller maps over a batch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

F32 = jnp.float32
NEG = -1e30
_LOG2E = 1.4426950408889634
_VMEM_LIMIT = 96 << 20
_INT_MIN = -2 ** 31


# ------------------------------------------------------------- the score

def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """The plain form. q_i [t, heads, dim] at `positions` [t], k_i [S,
    dim] (row s holds position s), w [t, heads] float32 -> [t, S] float32,
    `NEG` where s > position."""
    def head(j, acc):       # [t, S] a head: never [t, heads, S]
        s = jnp.einsum("td,sd->ts", q_i[:, j], k_i,
                       preferred_element_type=F32)
        return acc + jax.nn.relu(s) * w[:, j].astype(F32)[:, None]

    score = jax.lax.fori_loop(
        0, q_i.shape[1], head, jnp.zeros((q_i.shape[0], k_i.shape[0]), F32))
    seen = jnp.arange(k_i.shape[0])[None, :] <= positions[:, None]
    return jnp.where(seen, score, NEG)


def _index_kernel(q0_ref, q_ref, k_ref, w_ref, o_ref, *, tq: int, tk: int):
    """One (tile of queries, tile of keys) program. Refs: q0 [1] (SMEM:
    the position of the block's first query), q [heads, tq, dim], k [tk,
    dim], w [tq, heads], o [tq, tk]. A tile no query of which sees a key
    of it is written `NEG` whole, and its keys were not fetched (the
    index map stops at the diagonal)."""
    i, j = pl.program_id(0), pl.program_id(1)
    row0 = q0_ref[0] + i * tq

    @pl.when(j * tk > row0 + tq - 1)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, NEG, F32)

    @pl.when(j * tk <= row0 + tq - 1)
    def _():
        k = k_ref[...]
        w = w_ref[...].astype(F32)
        acc = jnp.zeros((tq, tk), F32)
        for h in range(q_ref.shape[0]):
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        q_at = row0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_at = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[...] = jnp.where(k_at <= q_at, acc, NEG)


def _index_pallas(q_i, k_i, w, q0, tokens: int, tq: int, tk: int,
                  interpret: bool) -> jax.Array:
    """q_i [block, heads, dim] at positions q0 .. q0 + block - 1 against
    k_i [Tp, dim] -> [block, Tp] float32."""
    block, heads, dim = q_i.shape
    tp = k_i.shape[0]

    def last_seen(i, j, q0_ref):      # the last tile of keys tile i sees
        return jnp.minimum(j, (q0_ref[0] + i * tq + tq - 1) // tk)

    return pl.pallas_call(
        functools.partial(_index_kernel, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(block // tq, tp // tk),
            in_specs=[
                pl.BlockSpec((heads, tq, dim), lambda i, j, q0: (0, i, 0)),
                pl.BlockSpec((tk, dim),
                             lambda i, j, q0: (last_seen(i, j, q0), 0)),
                pl.BlockSpec((tq, heads), lambda i, j, q0: (i, 0))],
            out_specs=pl.BlockSpec((tq, tk), lambda i, j, q0: (i, j))),
        out_shape=jax.ShapeDtypeStruct((block, tp), F32),
        interpret=interpret,
        name=f"dsa_index_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.reshape(q0, (1,)).astype(jnp.int32), q_i.transpose(1, 0, 2), k_i,
      w.astype(F32))


# ------------------------------------------------------------ the choice

def kth_largest_mask(scores: jax.Array, k: int) -> jax.Array:
    """The plain form of the selection: scores [t, S] float32 (`NEG`
    where unseen) -> bool [t, S], the seen scores at least as large as
    the row's k-th largest (every seen one where fewer than k are)."""
    k = min(k, scores.shape[-1])
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    return (scores >= kth) & (scores > NEG / 2)


def _select_kernel(s_ref, o_ref, *, k: int, chunk: int):
    """One tile of queries: s [tq, Tp] float32 -> o [tq, Tp] int8. The
    k-th largest key of each row, a bit at a time from the top: the
    largest number with at least k keys not below it. Each pass walks
    the row in chunks of `chunk` keys, so that nothing of the row's size
    stands beside the row itself."""
    tq, tp = s_ref.shape

    def keys_of(s):                     # ordered as the floats are
        bits = jax.lax.bitcast_convert_type(s, jnp.int32)
        return bits ^ ((bits >> 31) & 0x7FFFFFFF)

    def cols(j):
        return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)

    def enough(cand):                   # [tq, 1] bool
        def add(j, n):
            return n + jnp.sum((keys_of(s_ref[:, cols(j)]) >= cand
                                ).astype(jnp.int32), axis=-1, keepdims=True)
        return jax.lax.fori_loop(0, tp // chunk, add,
                                 jnp.zeros((tq, 1), jnp.int32)) >= k

    zero = jnp.zeros((tq, 1), jnp.int32)
    kth = jnp.where(enough(zero), zero, _INT_MIN)

    def step(n, kth):
        cand = kth | jnp.left_shift(jnp.int32(1), 30 - n)
        return jnp.where(enough(cand), cand, kth)

    kth = jax.lax.fori_loop(0, 31, step, kth)

    def write(j, _):
        s = s_ref[:, cols(j)]
        o_ref[:, cols(j)] = ((keys_of(s) >= kth) & (s > NEG / 2)
                             ).astype(jnp.int8)
        return 0

    jax.lax.fori_loop(0, tp // chunk, write, 0)


def _select_pallas(scores, k: int, tokens: int, tq: int, interpret: bool
                   ) -> jax.Array:
    block, tp = scores.shape
    chunk = _fit(tp, 2048)
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk),
        grid=(block // tq,),
        in_specs=[pl.BlockSpec((tq, tp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tq, tp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((block, tp), jnp.int8),
        interpret=interpret,
        name=f"dsa_select_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(scores)


def _fit(n: int, want: int) -> int:
    """The largest `want / 2^i` that divides n."""
    while n % want:
        want //= 2
    return want


def _tiles(block: int, tp: int) -> Tuple[int, int, int]:
    """(queries a score tile, keys a score tile, queries a select tile)
    that divide a block of `block` queries and `tp` keys."""
    return _fit(block, 256), _fit(tp, 512), _fit(block, 32)


def selection_tiles(block: int, tp: int, tokens: int, heads: int, dim: int,
                    topk: int) -> Tuple[Tuple[int, int, int], int]:
    """How `block_selection` will run blocks of `block` queries of a
    prompt of `tokens` (padded to `tp`): (its tiles, or () for the plain
    form, recorded as the kernel's choice; the scores it computes over
    the whole prompt: the kernel's tiles up to the diagonal, every pair
    in the plain form)."""
    tq, tk, ts = _tiles(block, tp)
    whole = dispatch.interpret_forced() or (ts >= 32 and tk >= 128)
    reason = dispatch.backend_reason() or (
        "" if whole else f"a block of {block} queries over {tp} keys has "
        "no whole tiles")
    shape = (tokens, heads, dim, topk, block)
    if reason:
        dispatch.record_choice("dsa_select", shape, "reference", reason)
        return (), tp * tp
    dispatch.record_choice("dsa_select", shape, "pallas", tiles=(tq, tk, ts))
    return (tq, tk, ts), sum(tq * min((end // tk + 1) * tk, tp)
                             for end in range(tq - 1, tp, tq))


def block_selection(q_i: jax.Array, k_i: jax.Array, w: jax.Array, q0,
                    topk: int, tokens: int, tiles: Tuple[int, ...]
                    ) -> jax.Array:
    """The selected set of a block of a prompt's queries, as a mask: q_i
    [block, heads, dim] at positions q0 .. q0 + block - 1 (q0 may be
    traced), k_i [Tp, dim] of the whole prompt from position 0 (of which
    the first `tokens` rows are real: a padded key lies behind every real
    query, so it is never selected), w [block, heads] float32 -> int8
    [block, Tp], 1 where the query attends the row. `tiles` from
    `selection_tiles`. The scores of one block are [block, Tp] float32;
    those of a whole prompt are never held."""
    block = q_i.shape[0]
    if not tiles:
        scores = index_scores(q_i, k_i, w, q0 + jnp.arange(block))
        return kth_largest_mask(scores, topk).astype(jnp.int8)
    tq, tk, ts = tiles
    interpret = dispatch.interpret_forced()
    scores = _index_pallas(q_i, k_i, w, q0, tokens, tq, tk, interpret)
    return _select_pallas(scores, topk, tokens, ts, interpret)


def tick_rows(slab_rows: int, topk: int) -> Tuple[int, ...]:
    """The row counts, ascending, at which a tick's selection may stop
    (`tick_selection`'s `upto`), the slab's own last. A slab is a power
    of two and a tail (the longest prompt and the longest answer: 32,768
    + 1,024), and `lax.top_k` on a TPU is a sort of the next power of two
    (33,792 scores cost what 65,536 do, 0.314 ms for four slots; 32,768
    cost 0.181, 16,384 0.080, 8,192 0.035: the chip, PERF.md PR 49): so
    each smaller power of two with that tail, down to twice `topk`."""
    head = 1 << (slab_rows.bit_length() - 1)
    tail = slab_rows - head
    out, n = [slab_rows], head // 2
    while n >= 2 * topk:
        out.append(n + tail)
        n //= 2
    return tuple(sorted(out))


def top_rows(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """`lax.top_k(scores, k)` over [B, S], the largest power of two of the
    rows and the tail behind it sorted apart and their best merged by ONE
    small sort that carries the rows along (a lookup of the merged rows
    costs a gather 10 ns an entry, 0.087 ms a layer for four slots: the
    chip, PERF.md PR 49): the same values and rows in the same order as
    the one sort of twice the rows that a length just past a power of two
    costs (a float32's bits order as `lax.top_k` orders the numbers, -0.0
    under 0.0, once the negative half is flipped; a tie goes to the
    earlier row in each sort, and the head's rows stand before the
    tail's). A length that is a power of two, or whose power of two is
    under 4 k rows (one sort is as cheap there), takes `lax.top_k`."""
    rows = scores.shape[-1]
    head = 1 << (rows.bit_length() - 1)
    if head == rows or head < 4 * k:
        return tuple(jax.lax.top_k(scores, min(k, rows)))
    best_h, at_h = jax.lax.top_k(scores[:, :head], k)
    best_t, at_t = jax.lax.top_k(scores[:, head:], min(k, rows - head))
    best = jnp.concatenate([best_h, best_t], -1)
    bits = jax.lax.bitcast_convert_type(best, jnp.int32)
    # ~x = -x - 1: the largest number first, and no overflow
    order = ~jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    _, best, at = jax.lax.sort(
        (order, best, jnp.concatenate([at_h, at_t + head], -1)),
        dimension=-1, num_keys=1, is_stable=True)
    return best[:, :k], at[:, :k]


def tick_upto(positions: jax.Array, upto: Tuple[int, ...]) -> jax.Array:
    """Which of `upto` (ascending row counts, the slab's own last) a tick
    stops at: the first that holds every slot's position."""
    return (positions.max() >= jnp.asarray(upto[:-1], jnp.int32)).sum()


def tick_selection(q_i: jax.Array, k_i: jax.Array, w: jax.Array,
                   positions: jax.Array, topk: int,
                   upto: Tuple[int, ...] = ()
                   ) -> Tuple[jax.Array, jax.Array]:
    """A decode tick's set, for every slot: q_i [B, heads, dim], k_i [B,
    S, dim] (the slab's index keys as they lie), w [B, heads], positions
    [B] -> (rows [B, k] int32, seen [B, k] bool: False for the entries
    that stand for nothing while `position + 1 < k`), k = min(topk, S).

    With `upto` (`tick_rows`) only the first rows of the slab are scored
    and sorted, as many as `tick_upto` says hold every slot's position:
    the rows behind are unseen to every slot, so the set is the same, row
    for row (a dead slot stands at position 0)."""
    def select(q_i, k_i, w, positions, rows=None):
        if rows is not None:
            k_i = k_i[:, :rows]
        with jax.named_scope("dsa_index_tick"):
            # every head at once: [B, heads, S] float32 is small beside a
            # tick, and the slab's keys are read ONCE (a head at a time,
            # as `index_scores` takes a block of a prompt's queries, would
            # read them `heads` times)
            s = jnp.einsum("bhd,bsd->bhs", q_i, k_i,
                           preferred_element_type=F32)
            scores = (jax.nn.relu(s) * w[..., None].astype(F32)).sum(1)
            seen = jnp.arange(k_i.shape[1])[None, :] <= positions[:, None]
            scores = jnp.where(seen, scores, NEG)
        with jax.named_scope("dsa_select_tick"):
            if rows is None:
                return jax.lax.top_k(scores, min(topk, scores.shape[-1]))
            return top_rows(scores, min(topk, scores.shape[-1]))

    if len(upto) > 1:
        best, rows = jax.lax.switch(
            tick_upto(positions, upto),
            [functools.partial(select, rows=n) for n in upto],
            q_i, k_i, w, positions)
    else:
        best, rows = select(q_i, k_i, w, positions)
    return rows, best > NEG / 2


# --------------------------------------------- attention under the mask

def _masked_blocked(q_n, q_r, k_n, k_r, v, tiles, scale: float, block: int
                    ) -> jax.Array:
    """The prompt form under a mask in `jax.numpy`: q_n [H, Tp, d_n], q_r
    [H, Tp, d_r], k_n [H, Tp, d_n], k_r [Tp, d_r], v [H, Tp, d_v], tiles
    [nb, nb, block, block] int8 -> [H, Tp, d_v]; a block of queries at a
    time over every key."""
    h, tp, d_v = v.shape
    nb = tp // block
    per = tiles.shape[1]                  # tiles a plane, if packed

    def q_block(args):
        qn_i, qr_i, m_i = args            # [H, blk, .], [per, blk, blk]
        m_i = jnp.concatenate([_kept(m_i, b) for b in range(nb // per)])
        s = (jnp.einsum("htd,hsd->hts", qn_i, k_n,
                        preferred_element_type=F32)
             + jnp.einsum("htd,sd->hts", qr_i, k_r,
                          preferred_element_type=F32)) * scale
        seen = jnp.moveaxis(m_i, 0, 1).reshape(block, tp)
        s = jnp.where(seen[None], s, NEG)
        p = jnp.where(seen[None], jnp.exp(s - s.max(-1, keepdims=True)),
                      0.0)
        out = jnp.einsum("hts,hsd->htd", p.astype(v.dtype), v,
                         preferred_element_type=F32)
        return (out / p.sum(-1, keepdims=True)).astype(v.dtype)

    cut = lambda x: jnp.moveaxis(x.reshape(h, nb, block, -1), 1, 0)
    out = jax.lax.map(q_block, (cut(q_n), cut(q_r), tiles))
    return jnp.moveaxis(out, 0, 1).reshape(h, tp, d_v)


def _selected_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, m_ref, o_ref, *,
                     scale: float, block: int, per: int):
    """One (head, block of queries) program. Refs: qn [block, d_n], qr
    [block, d_r], kn [Tp, d_n] and v [Tp, d_v] of this head, kr [Tp, d_r]
    of the sequence, m [per, block, block] int8: the mask of this block of
    queries as `mask_tiles` lays it out (block ki of keys: bit `ki // per`
    of tile `ki % per`; `per` the blocks of keys where nothing is packed);
    o [block, d_v]. The blocks of keys
    up to the diagonal are walked under a running softmax; a pair the
    mask leaves out weighs 0 (not exp(NEG - NEG) = 1 before the row's
    first kept key). The mask holds the causal rule already."""
    qi = pl.program_id(1)
    cd = qn_ref.dtype
    fold = scale * _LOG2E
    qn = (qn_ref[...].astype(F32) * fold).astype(cd)
    qr = (qr_ref[...].astype(F32) * fold).astype(cd)
    last = (((1,), (1,)), ((), ()))

    def step(ki, carry):
        m_prev, l_prev, acc = carry
        rows = pl.ds(pl.multiple_of(ki * block, block), block)
        s = jax.lax.dot_general(qn, kn_ref[rows, :], last,
                                preferred_element_type=F32) \
            + jax.lax.dot_general(qr, kr_ref[rows, :], last,
                                  preferred_element_type=F32)
        kept = _kept(m_ref[ki % per], ki // per)
        s = jnp.where(kept, s, NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(kept, jnp.exp2(s - m_new), 0.0)
        alpha = jnp.exp2(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(cd), v_ref[rows, :], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        return m_new, alpha * l_prev + jnp.sum(p, -1, keepdims=True), acc

    carry = (jnp.full((block, 1), NEG, F32), jnp.zeros((block, 1), F32),
             jnp.zeros((block, o_ref.shape[-1]), F32))
    _, l, acc = jax.lax.fori_loop(0, qi + 1, step, carry)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _selected_pallas(q_n, q_r, k_n, k_r, v, tiles, scale: float, block: int,
                     tokens: int, interpret: bool) -> jax.Array:
    h, tp, d_n = q_n.shape
    d_r, d_v = q_r.shape[-1], v.shape[-1]
    nb, per = tp // block, tiles.shape[1]
    per_head = lambda d: pl.BlockSpec((None, tp, d), lambda g, i: (g, 0, 0))
    per_block = lambda d: pl.BlockSpec((None, block, d),
                                       lambda g, i: (g, i, 0))
    return pl.pallas_call(
        functools.partial(_selected_kernel, scale=scale, block=block,
                          per=per),
        grid=(h, nb),
        in_specs=[per_block(d_n), per_block(d_r), per_head(d_n),
                  pl.BlockSpec((tp, d_r), lambda g, i: (0, 0)),
                  per_head(d_v),
                  pl.BlockSpec((None, per, block, block),
                               lambda g, i: (i, 0, 0, 0))],
        out_specs=per_block(d_v),
        out_shape=jax.ShapeDtypeStruct((h, tp, d_v), q_n.dtype),
        interpret=interpret,
        name=f"mla_selected_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(q_n, q_r, k_n, k_r, v, tiles)


PLANES = 8      # blocks of keys that share a byte of the packed mask


def mask_tiles(mask: jax.Array, block: int) -> jax.Array:
    """[rows, Tp] int8 of 0 and 1 -> the mask as the selected form takes
    it: tiles (block of queries, block of keys, query, key), so that a
    kernel takes a tile by its number, and where the blocks of keys are a
    whole number of `PLANES`, eight of them in one byte: [rows / block, nb
    / 8, block, block], bit b of tile j' the tile of block `b nb / 8 + j'`
    (a 32,768-token prompt's mask is 134 MB so, not 1.07 GB); else [rows
    / block, nb, block, block]."""
    rows, tp = mask.shape
    nb = tp // block
    tiles = mask.reshape(rows // block, block, nb, block
                         ).transpose(0, 2, 1, 3)
    if nb % PLANES:
        return tiles
    planes = tiles.reshape(rows // block, PLANES, nb // PLANES, block, block)
    bit = jnp.arange(PLANES, dtype=jnp.int32)[None, :, None, None, None]
    return jnp.sum(planes.astype(jnp.int32) << bit, axis=1).astype(jnp.int8)


def _kept(tile: jax.Array, plane) -> jax.Array:
    """A tile of the mask as bools: bit `plane` of a packed tile."""
    return ((tile.astype(jnp.int32) >> plane) & 1) != 0


def selected_prompt_attention(q_n: jax.Array, q_r: jax.Array,
                              k_n: jax.Array, k_r: jax.Array, v: jax.Array,
                              tiles: jax.Array, scale: float, block: int,
                              tokens: int) -> jax.Array:
    """Attention of some heads over a prompt from position 0 under the
    selection: q_n [H, Tp, d_n], q_r [H, Tp, d_r], k_n [H, Tp, d_n], k_r
    [Tp, d_r] (the ONE rotated key part), v [H, Tp, d_v], tiles
    `mask_tiles` of every block's `block_selection`. Returns [H, Tp, d_v] in
    q's dtype. The caller expands keys and values for as many heads as
    it can hold and calls once a group."""
    h, tp, d_n = q_n.shape
    shape = (tokens, h, d_n + q_r.shape[-1], v.shape[-1], block)
    resident = 2 * (tp * 3 * 128 * q_n.dtype.itemsize
                    + tiles.shape[1] * block * block)
    reason = dispatch.backend_reason() or (
        "" if resident < _VMEM_LIMIT * 7 // 8 else
        f"{resident} bytes of a head's keys and values and a block's mask "
        "exceed the kernel's VMEM")
    if reason:
        dispatch.record_choice("mla_selected", shape, "reference", reason)
        return _masked_blocked(q_n, q_r, k_n, k_r, v, tiles, scale, block)
    dispatch.record_choice("mla_selected", shape, "pallas")
    return _selected_pallas(q_n, q_r, k_n, k_r, v, tiles, scale, block,
                            tokens, dispatch.interpret_forced())


# ------------------------- grouped-query heads under the mask, and a tick

def _gqa_masked_blocked(q, k, v, tiles, scale: float, block: int
                        ) -> jax.Array:
    """The grouped-query prompt form under a mask in `jax.numpy`: q [H,
    Tp, d], k and v [G, Tp, d] (query head h reads head `h // (H / G)`),
    tiles [nb, nb or nb / 8, block, block] int8 -> [H, Tp, d]; a block of
    queries at a time over every key."""
    h, tp, d = q.shape
    g = k.shape[0]
    nb = tp // block
    per = tiles.shape[1]

    def q_block(args):
        q_i, m_i = args                   # [H, blk, d], [per, blk, blk]
        m_i = jnp.concatenate([_kept(m_i, b) for b in range(nb // per)])
        seen = jnp.moveaxis(m_i, 0, 1).reshape(block, tp)[None, None]
        s = jnp.einsum("gjtd,gsd->gjts", q_i.reshape(g, h // g, block, d),
                       k, preferred_element_type=F32) * scale
        s = jnp.where(seen, s, NEG)
        p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        out = jnp.einsum("gjts,gsd->gjtd", p.astype(v.dtype), v,
                         preferred_element_type=F32)
        return (out / p.sum(-1, keepdims=True)).astype(v.dtype
                                                       ).reshape(h, block, d)

    cut = jnp.moveaxis(q.reshape(h, nb, block, d), 1, 0)
    out = jax.lax.map(q_block, (cut, tiles))
    return jnp.moveaxis(out, 0, 1).reshape(h, tp, d)


def _gqa_selected_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, scale: float,
                         block: int, per: int):
    """One (head of keys, block of queries) program. Refs: q [group,
    block, d], the query heads that read this head of keys; k and v [Tp,
    d] of this head; m [per, block, block] int8, the mask of this block of
    queries as `mask_tiles` lays it out; o [group, block, d]. The `group`
    heads' queries stand as ONE [group * block, d] operand, so a block of
    keys is read, and its tile of the mask decoded, once for all of them.
    The blocks of keys up to the diagonal are walked under a running
    softmax, which starts ABOVE `NEG`: a pair the mask leaves out then
    weighs exp2(NEG - m) = 0 even before the row's first kept key. The
    mask holds the causal rule already."""
    qi = pl.program_id(1)
    group, _, d = q_ref.shape
    cd = q_ref.dtype
    rows = group * block
    q = (q_ref[...].astype(F32) * (scale * _LOG2E)).astype(cd
                                                           ).reshape(rows, d)

    def step(ki, carry):
        m_prev, l_prev, acc = carry
        at = pl.ds(pl.multiple_of(ki * block, block), block)
        s = jax.lax.dot_general(q, k_ref[at, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        kept = _kept(m_ref[ki % per], ki // per)
        s = jnp.where(kept[None], s.reshape(group, block, block), NEG
                      ).reshape(rows, block)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(cd), v_ref[at, :], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        return m_new, alpha * l_prev + jnp.sum(p, -1, keepdims=True), acc

    carry = (jnp.full((rows, 1), NEG / 2, F32), jnp.zeros((rows, 1), F32),
             jnp.zeros((rows, d), F32))
    _, l, acc = jax.lax.fori_loop(0, qi + 1, step, carry)
    o_ref[...] = (acc / l).reshape(group, block, d).astype(o_ref.dtype)


def _gqa_selected_pallas(q, k, v, tiles, scale: float, block: int,
                         tokens: int, interpret: bool) -> jax.Array:
    h, tp, d = q.shape
    g = k.shape[0]
    group, nb, per = h // g, tp // block, tiles.shape[1]
    per_head = pl.BlockSpec((None, tp, d), lambda j, i: (j, 0, 0))
    per_block = pl.BlockSpec((None, group, block, d),
                             lambda j, i: (j, 0, i, 0))
    out = pl.pallas_call(
        functools.partial(_gqa_selected_kernel, scale=scale, block=block,
                          per=per),
        grid=(g, nb),
        in_specs=[per_block, per_head, per_head,
                  pl.BlockSpec((None, per, block, block),
                               lambda j, i: (i, 0, 0, 0))],
        out_specs=per_block,
        out_shape=jax.ShapeDtypeStruct((g, group, tp, d), q.dtype),
        interpret=interpret,
        name=f"gqa_selected_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(q.reshape(g, group, tp, d), k, v, tiles)
    return out.reshape(h, tp, d)


def gqa_selected_prompt_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                  tiles: jax.Array, scale: float,
                                  block: int, tokens: int) -> jax.Array:
    """Attention of grouped-query heads over a prompt from position 0
    under the selection: q [H, Tp, d] (rotated), k (rotated) and v [G,
    Tp, d], query head h reading head `h // (H / G)` of them; tiles
    `mask_tiles` of every block's `block_selection`. Returns [H, Tp, d]
    in q's dtype. Every visible block of scores is computed and the
    unselected pairs weigh nothing, as `selected_prompt_attention`."""
    h, tp, d = q.shape
    g = k.shape[0]
    if h % g:
        raise ValueError(f"{h} query heads over {g} heads of keys")
    group = h // g
    shape = (tokens, h, g, d, block)
    # what a program holds: a head's keys and values twice (the next
    # head's on their way), the block's mask twice, the stacked queries'
    # scores in float32 about three times over
    resident = (4 * tp * max(d, 128) * q.dtype.itemsize
                + 2 * tiles.shape[1] * block * block
                + 3 * group * block * block * 4)
    reason = dispatch.backend_reason() or (
        "" if resident < _VMEM_LIMIT * 7 // 8 else
        f"{resident} bytes of a head's keys and values, a block's mask and "
        f"{group} heads' scores exceed the kernel's VMEM")
    if reason:
        dispatch.record_choice("gqa_selected", shape, "reference", reason)
        return _gqa_masked_blocked(q, k, v, tiles, scale, block)
    dispatch.record_choice("gqa_selected", shape, "pallas")
    return _gqa_selected_pallas(q, k, v, tiles, scale, block, tokens,
                                dispatch.interpret_forced())


def gqa_selected_tick(q: jax.Array, keys: jax.Array, values: jax.Array,
                      picked: jax.Array, seen: jax.Array, scale: float
                      ) -> jax.Array:
    """A decode tick's attention over the rows `tick_selection` picked:
    q [B, H, d] (rotated), keys and values [B, S, G, d] as the slab holds
    them, picked [B, k] int32, seen [B, k] bool -> [B, H, d] in q's
    dtype. The `k` rows of keys and of values a slot are gathered (never
    a row the indexer left out) and every query head attends its head of
    them; an entry of `picked` that stands for nothing weighs 0."""
    b, h, d = q.shape
    g = keys.shape[2]
    with jax.named_scope("gqa_gather_tick"):
        at = picked[:, :, None, None]
        k = jnp.take_along_axis(keys, at, axis=1)           # [B, k, G, d]
        v = jnp.take_along_axis(values, at, axis=1)
    with jax.named_scope("gqa_selected_tick"):
        s = jnp.einsum("bgjd,bkgd->bgjk", q.reshape(b, g, h // g, d), k,
                       preferred_element_type=F32) * scale
        ok = seen[:, None, None, :]
        s = jnp.where(ok, s, NEG)
        p = jnp.where(ok, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        out = jnp.einsum("bgjk,bkgd->bgjd", p.astype(v.dtype), v,
                         preferred_element_type=F32)
        return (out / p.sum(-1, keepdims=True)).astype(q.dtype
                                                       ).reshape(b, h, d)
