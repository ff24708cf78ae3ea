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
  absorbed  over the cache: W_kvb's key half is folded into the query
            (q_n W_uk^T, rank wide), the scores are taken against the
            rows as they lie (H query heads to one shared row), the
            weighted sum of rows goes through W_uv. Same numbers, no
            per-head key or value formed over the cache. At 128 heads it
            does 128 x (576 + 512) x 2 FLOPs for each 1,280-byte row it
            reads, 218 FLOP/B: at the ridge of a v5e, where Kimi-Linear's
            32 heads (54 FLOP/B) are bandwidth-bound. A tick's run (at
            most `ops/swa.DECODE_ROWS` rows a slot, seeing rows `<=
            position`) WALKS (`_decode_kernel`): slot b's blocks of rows
            from block 0 to the one that holds its last position and no
            other (`ops/swa.decode_blocks`, the rule `ops/swa.py`'s decode
            form walks keys and values by), under a running softmax
            with float32 scores, sum and accumulator, so a tick reads
            the rows its slots hold and not `max_batch x max_seq_len`
            twice; a parked slot costs one block. On a TPU it is the
            Pallas kernel `mla_decode_t<t>`, ONE call a layer: the
            positions scalar-prefetched, the folded queries resident,
            slots and a slot's blocks the kernel's own loops over blocks
            it copies from HBM itself, two ahead of the products. A block
            is fetched ONCE: the values are the first `rank` lanes of the
            key row, so the weighted sum is taken over the block the
            scores were taken against, in VMEM. All H heads (and the
            run's t rows) are the rows of one product against the block;
            a block every row of the run sees whole is not masked. The
            block follows from the entry's shape (`ops/swa.decode_block`).
            Elsewhere (a backend
            without Mosaic, a longer run) the PLAIN form: two passes over
            every row of every slot with [B, H, t, S] float32 scores
            between them, masked afterwards; it is the walk's reference.
            `dispatch.kernel_choices("mla_decode")` lists the shapes (B,
            t, H, width, rank, S) and which ran.
            With `visible` the rows seen are the caller's, not `<=
            position`, and the form is the plain one: a RING of latent
            rows (`ring_visible`: the token at position p lies in row `p
            mod rows`, and a row counts while its token is one of the
            last `window`), or rows gathered by a selection
            (`ops/dsa.py`).
  band      over a prompt whose layer sees the last `window` positions
            (query t sees `t - window < s <= t`): `band_prompt_attention`,
            blocks of `block >= window - 1` queries, each against its own
            block of keys and the one before, one softmax over the two.
            On a TPU the Pallas kernel `mla_band_w<W>_t<T>` (one program
            a head and block of queries; only those two blocks of the
            head's keys and values are fetched), elsewhere the same in
            `jax.numpy`. `ops/swa.ring_rows` lays a prompt's rows out as the
            ring holds them after it.

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
from .swa import (DECODE_ROWS, _DECODE_BUFFERS, _block_seen, _block_start,
                  decode_block, decode_blocks)

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
                       positions: Optional[jax.Array], w_kvb: jax.Array,
                       scale: Optional[float] = None,
                       visible: Optional[jax.Array] = None) -> jax.Array:
    """Attention of q over the cache as it lies: rows [B, S, width] are
    `latent_row`s, query (b, j) sees rows <= positions[b, j] (ascending
    along t), or, given `visible` [B, t, S] bool, the rows it marks
    (`positions` is then not read; every query must see a row). q_n [B,
    t, H, d_n], q_r [B, t, H, d_r], w_kvb [rank, H, d_n + d_v]. Returns
    [B, t, H, d_v] in q's dtype. A tick's run by positions walks each
    slot's blocks up to its last position; `visible`, a longer run and a
    backend without Mosaic take the plain form over every row (module
    docstring). The choice reads the shapes alone, and is recorded as
    `mla_decode`."""
    rank, d_n, d_r = w_kvb.shape[0], q_n.shape[-1], q_r.shape[-1]
    w_uk, w_uv = w_kvb[..., :d_n], w_kvb[..., d_n:]
    q_c = jnp.einsum("bthd,chd->bthc", q_n, w_uk,
                     preferred_element_type=F32).astype(q_n.dtype)
    q = _padded([q_c, q_r], rows.shape[-1])
    block = decode_block(rows.shape, rows.dtype)
    shape = q.shape + (rank, rows.shape[1])
    reason = _walk_refused(q, rows, visible)
    if reason:
        dispatch.record_choice("mla_decode", shape, "reference", reason,
                               block=block)
        scores = jnp.einsum("bthw,bsw->bhts", q, rows,
                            preferred_element_type=F32)
        scores = _scaled(scores, d_n + d_r, scale)
        if visible is None:
            col = jnp.arange(rows.shape[1])[None, None, :]
            visible = col <= positions[:, :, None]
        scores = jnp.where(visible[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_n.dtype)
        # the values are the first `rank` lanes of the key row: the sum
        # is taken over the whole row where it lies and cut afterwards
        mixed = jnp.einsum("bhts,bsw->bthw", probs, rows)[..., :rank]
    else:
        dispatch.record_choice("mla_decode", shape, "pallas", block=block)
        mixed = _decode_pallas(
            q, rows, positions.astype(jnp.int32), rank,
            float((d_n + d_r) ** -0.5 if scale is None else scale), block,
            dispatch.interpret_forced())
    return jnp.einsum("bthc,chd->bthd", mixed, w_uv,
                      preferred_element_type=F32).astype(q_n.dtype)


# ------------------------------------------------------- the decode walk

def _decode_kernel(pos_ref, q_ref, rows_hbm, o_ref, buf, sem, m_s, l_s,
                   acc_s, *, block: int, t: int, heads: int, fold: float):
    """The whole tick's walk, one slot after another, as
    `ops/swa._decode_kernel` walks keys and values. Refs: pos [B, t] in
    SMEM; q [B, padded, width], a slot's `t x H` folded query rows
    `[q_c | q_r | 0]` padded to whole sublanes, resident; rows [B, S,
    width], the slab entry as it lies in HBM; o [B, padded, v_width].
    Scratch: `buf.shape[0]` blocks of rows with their DMA semaphores, one
    slot's running max, sum and accumulator.

    A block is copied ONCE and serves twice: the scores are taken against
    its whole rows, the weighted sum over their first `v_width` lanes
    (the latent; a whole number of lane tiles, so the cut is free). Slot
    b takes `decode_blocks(positions[b, -1])` steps, the blocks of ALL
    slots are one sequence of copies that runs `buffers - 1` ahead of the
    products, and the blocks every row of the run sees whole (those
    before the one that holds the run's FIRST position) come first and
    are not masked: the selects over [t x H, block] scores are the vector
    unit's work, and only a slot's last blocks need them. A block's
    scores are ONE product and its accumulator is rescaled once: halves
    of a block, each with its own rescale of [H, rank] float32, ran a
    layer of 128 heads in 296 us for 171 (PERF.md, PR 48). The scale and
    log2(e) multiply the float32 scores, so the query is the plain form's
    to the bit; products take the rows' dtype (or the queries', the
    wider) and accumulate in float32."""
    slots, padded, _ = q_ref.shape
    buffers = buf.shape[0]
    s_rows = rows_hbm.shape[1]
    v_width = acc_s.shape[1]
    cd = jnp.promote_types(q_ref.dtype, buf.dtype)

    def copy(slot, j, at):
        rows = pl.ds(pl.multiple_of(_block_start(j, block, s_rows), 8),
                     block)
        return pltpu.make_async_copy(rows_hbm.at[slot, rows, :], buf.at[at],
                                     sem.at[at])

    def start(slot, j, at):
        @pl.when(slot < slots)
        def _():
            copy(slot, j, at).start()

    def blocks_of(slot):
        return decode_blocks(pos_ref[jnp.minimum(slot, slots - 1), t - 1],
                             block, s_rows)

    def after(slot, j):
        """The block that follows block `j` of `slot` in the sequence."""
        more = j + 1 < blocks_of(slot)
        return jnp.where(more, slot, slot + 1), jnp.where(more, j + 1, 0)

    ahead = (jnp.int32(0), jnp.int32(0))
    for at in range(buffers - 1):
        start(*ahead, at)
        ahead = after(*ahead)
    # row r of a slot is a head of query r // H of the run (the padding
    # rows go with the last query): a small static count, so comparisons
    row = jax.lax.broadcasted_iota(jnp.int32, (padded, 1), 0)
    query = sum((row >= i * heads).astype(jnp.int32) for i in range(1, t))
    k_in = jax.lax.broadcasted_iota(jnp.int32, (padded, block), 1)

    def slot_walk(b, carry):
        m_s[...] = jnp.full(m_s.shape, _NEG, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)
        q_at = jnp.full((padded, 1), pos_ref[b, t - 1], jnp.int32)
        for i in range(t - 1):
            q_at = jnp.where(query == i, pos_ref[b, i], q_at)

        def step(j, carry, masked):
            done, a_slot, a_j = carry
            at = jax.lax.rem(done, buffers)
            start(a_slot, a_j, jax.lax.rem(done + buffers - 1, buffers))
            copy(b, j, at).wait()
            rows = buf[at].astype(cd)
            s = jax.lax.dot_general(
                q_ref[b].astype(cd), rows, (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * fold
            if masked:
                k_at = _block_start(j, block, s_rows) + k_in
                s = jnp.where(_block_seen(q_at, k_at, j, block), s, _NEG)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m_prev - m_new)
            m_s[...] = m_new
            l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
            acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
                p.astype(cd), rows[:, :v_width], (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            return (done + 1,) + after(a_slot, a_j)

        last = blocks_of(b)
        whole = jnp.minimum(
            jnp.minimum((pos_ref[b, 0] + 1) // block, s_rows // block), last)
        carry = jax.lax.fori_loop(
            0, whole, functools.partial(step, masked=False), carry)
        carry = jax.lax.fori_loop(
            whole, last, functools.partial(step, masked=True), carry)
        o_ref[b] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, slots, slot_walk, (jnp.int32(0),) + ahead)


def _sublanes(rows: int) -> int:
    """A slot's `t x H` query rows up to whole packed sublanes."""
    return -(-rows // 16) * 16


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _decode_pallas(q, rows, positions, rank: int, scale: float, block: int,
                   interpret: bool) -> jax.Array:
    """Jitted on its own so that the layers of a tick share one lowering.
    q [B, t, H, width], rows [B, S, width] as the slab holds them,
    positions [B, t] int32 -> [B, t, H, rank] in q's dtype."""
    b, t, heads, width = q.shape
    s_rows = rows.shape[1]
    padded = _sublanes(t * heads)
    v_width = rank if rank % LANES == 0 else width
    stacked = jnp.pad(q.reshape(b, t * heads, width),
                      ((0, 0), (0, padded - t * heads), (0, 0)))
    resident = lambda w: pl.BlockSpec((b, padded, w),
                                      lambda i, pos: (0, 0, 0))
    visited = b * -(-s_rows // block)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, t=t, heads=heads,
                          fold=scale * _LOG2E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[resident(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=resident(v_width),
            scratch_shapes=[
                pltpu.VMEM((_DECODE_BUFFERS, block, width), rows.dtype),
                pltpu.SemaphoreType.DMA((_DECODE_BUFFERS,)),
                pltpu.VMEM((padded, 1), F32),
                pltpu.VMEM((padded, 1), F32),
                pltpu.VMEM((padded, v_width), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, padded, v_width), q.dtype),
        interpret=interpret,
        # the run's rows are in the name, so that a trace tells a tick's
        # call from a verify pass's
        name=f"mla_decode_t{t}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * visited * block * padded * (width + v_width),
            bytes_accessed=(stacked.size + b * padded * v_width)
            * q.dtype.itemsize
            + visited * block * width * rows.dtype.itemsize,
            transcendentals=visited * block * padded),
    )(positions, stacked, rows)
    return out[:, :t * heads, :rank].reshape(b, t, heads, rank)


def _walk_refused(q: jax.Array, rows: jax.Array,
                  visible: Optional[jax.Array]) -> str:
    """Why the absorbed form of q [B, t, H, width] over rows [B, S,
    width] cannot walk; "" where it can."""
    b, t, heads, width = q.shape
    s_rows = rows.shape[1]
    if visible is not None:
        return "the caller's `visible` rows are no walk up to a position"
    if t > DECODE_ROWS:
        return f"a run of {t} rows is no tick's (over {DECODE_ROWS})"
    reason = dispatch.backend_reason()
    if reason:
        return reason
    if not dispatch.interpret_forced() and (width % LANES or s_rows % 16):
        return (f"rows of {width} numbers or an entry of {s_rows} rows do "
                "not fill the kernel's tiles")
    # queries and outputs of every slot are resident, twice (the
    # pipeline's two buffers): 19 MB at 128 slots of 32 heads
    resident = 2 * b * _sublanes(t * heads) * 2 * width * q.dtype.itemsize
    if resident > _VMEM_LIMIT // 2:
        return (f"{resident} bytes of queries and outputs exceed the "
                "kernel's VMEM")
    return ""


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


# -------------------------------------------------------- the band, rings

def ring_visible(positions: jax.Array, rows: int, window: int) -> jax.Array:
    """Which rows of a ring of `rows >= window` rows the query at
    `positions` [B, t] sees, its own row written: row r holds the newest
    position `p <= position` with `p mod rows == r`, and counts while
    `position - p < window` and `p >= 0`. Returns [B, t, rows] bool."""
    age = (positions[..., None] - jnp.arange(rows)) % rows
    return (age < window) & (age <= positions[..., None])


def _band_seen(block: int, window: int, n):
    """(which keys of the block before, which of the own block) a query
    of block `n` sees, [block, block] bool each, by their places in their
    blocks: the key at `j` of the block before lies `block + i - j`
    behind the query at `i`; block 0 has no block before it."""
    i = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return (block + i - j < jnp.where(n > 0, window, 0),
            (j <= i) & (i - j < window))


def _band_blocked(q_n, q_r, k_n, k_r, v, scale: float, block: int,
                  window: int) -> jax.Array:
    """The band in `jax.numpy`: q_n [H, Tp, d_n], q_r [H, Tp, d_r], k_n
    [H, Tp, d_n], k_r [Tp, d_r], v [H, Tp, d_v] -> [H, Tp, d_v]."""
    h, tp, d_v = v.shape
    nb = tp // block

    def cut(x):                 # [.., Tp, d] -> [nb, .., block, d]
        return jnp.moveaxis(
            x.reshape(x.shape[:-2] + (nb, block, x.shape[-1])), -3, 0)

    def shifted(x):             # block i holds block i - 1 (block 0: itself)
        return jnp.concatenate([x[:1], x[:-1]], 0)

    kn_b, kr_b, v_b = cut(k_n), cut(k_r), cut(v)

    def q_block(args):
        n, qn_i, qr_i, kn_0, kr_0, v_0, kn_1, kr_1, v_1 = args
        before, own = _band_seen(block, window, n)

        def scores(kn, kr, seen):
            s = (jnp.einsum("htd,hsd->hts", qn_i, kn,
                            preferred_element_type=F32)
                 + jnp.einsum("htd,sd->hts", qr_i, kr,
                              preferred_element_type=F32)) * scale
            return jnp.where(seen[None], s, _NEG)

        s = jnp.concatenate([scores(kn_0, kr_0, before),
                             scores(kn_1, kr_1, own)], -1)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        return jnp.einsum("hts,hsd->htd", p,
                          jnp.concatenate([v_0, v_1], 1)).astype(v.dtype)

    out = jax.lax.map(q_block, (
        jnp.arange(nb), cut(q_n), cut(q_r), shifted(kn_b), shifted(kr_b),
        shifted(v_b), kn_b, kr_b, v_b))
    return jnp.moveaxis(out, 0, 1).reshape(h, tp, d_v)


def _band_kernel(qn_ref, qr_ref, kn0_ref, kn1_ref, kr0_ref, kr1_ref,
                 v0_ref, v1_ref, o_ref, *, scale: float, block: int,
                 window: int):
    """One (head, block of queries) program: the block's queries against
    the block of keys before theirs (refs `0`; block 0 has none, and is
    handed its own again, masked whole) and their own (refs `1`), one
    softmax over both."""
    qi = pl.program_id(1)
    cd = qn_ref.dtype
    fold = scale * _LOG2E
    qn = (qn_ref[...].astype(F32) * fold).astype(cd)
    qr = (qr_ref[...].astype(F32) * fold).astype(cd)
    last = (((1,), (1,)), ((), ()))
    before, own = _band_seen(block, window, qi)

    def scores(kn_ref, kr_ref, seen):
        s = jax.lax.dot_general(qn, kn_ref[...], last,
                                preferred_element_type=F32) \
            + jax.lax.dot_general(qr, kr_ref[...], last,
                                  preferred_element_type=F32)
        return jnp.where(seen, s, _NEG)

    s0 = scores(kn0_ref, kr0_ref, before)
    s1 = scores(kn1_ref, kr1_ref, own)
    m = jnp.maximum(jnp.max(s0, -1, keepdims=True),
                    jnp.max(s1, -1, keepdims=True))
    p0, p1 = jnp.exp2(s0 - m), jnp.exp2(s1 - m)
    total = jnp.sum(p0, -1, keepdims=True) + jnp.sum(p1, -1, keepdims=True)
    rows = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(p0.astype(cd), v0_ref[...], rows,
                              preferred_element_type=F32) \
        + jax.lax.dot_general(p1.astype(cd), v1_ref[...], rows,
                              preferred_element_type=F32)
    o_ref[...] = (acc / total).astype(o_ref.dtype)


def _band_pallas(q_n, q_r, k_n, k_r, v, scale: float, block: int,
                 window: int, tokens: int, interpret: bool) -> jax.Array:
    h, tp, d_n = q_n.shape
    d_r, d_v = q_r.shape[-1], v.shape[-1]
    own = lambda d: pl.BlockSpec((None, block, d), lambda g, i: (g, i, 0))
    before = lambda d: pl.BlockSpec(
        (None, block, d), lambda g, i: (g, jnp.maximum(i - 1, 0), 0))
    return pl.pallas_call(
        functools.partial(_band_kernel, scale=scale, block=block,
                          window=window),
        grid=(h, tp // block),
        in_specs=[own(d_n), own(d_r), before(d_n), own(d_n),
                  pl.BlockSpec((block, d_r),
                               lambda g, i: (jnp.maximum(i - 1, 0), 0)),
                  pl.BlockSpec((block, d_r), lambda g, i: (i, 0)),
                  before(d_v), own(d_v)],
        out_specs=own(d_v),
        out_shape=jax.ShapeDtypeStruct((h, tp, d_v), q_n.dtype),
        interpret=interpret,
        name=f"mla_band_w{window}_t{tokens}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q_n, q_r, k_n, k_n, k_r, k_r, v, v)


def band_prompt_attention(q_n: jax.Array, q_r: jax.Array, k_n: jax.Array,
                          k_r: jax.Array, v: jax.Array, scale: float,
                          window: int, block: int, tokens: int
                          ) -> jax.Array:
    """Attention of some heads over a prompt from position 0 in which
    query t sees `t - window < s <= t`: q_n [H, Tp, d_n], q_r [H, Tp,
    d_r], k_n [H, Tp, d_n], k_r [Tp, d_r] (the ONE rotated key part), v
    [H, Tp, d_v], Tp a whole number of `block >= window - 1`, of which
    the first `tokens` rows are the prompt. Returns [H, Tp, d_v] in q's
    dtype. ONE sequence: the caller maps over a batch, and expands keys
    and values for as many heads as it can hold."""
    h, tp, d_n = q_n.shape
    if block < window - 1 or tp % block:
        raise ValueError(f"the band of {window} needs whole blocks of at "
                         f"least {window - 1} (block {block}, {tp} rows)")
    shape = (tokens, h, d_n + q_r.shape[-1], v.shape[-1], window, block)
    reason = dispatch.backend_reason()
    if reason:
        dispatch.record_choice("mla_band", shape, "reference", reason)
        return _band_blocked(q_n, q_r, k_n, k_r, v, scale, block, window)
    dispatch.record_choice("mla_band", shape, "pallas")
    return _band_pallas(q_n, q_r, k_n, k_r, v, scale, block, window,
                        tokens, dispatch.interpret_forced())
