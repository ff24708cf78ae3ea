"""Attention for grouped-query heads as a served model needs it, where
`rep` query heads share one key-value head: the PROMPT form over a whole
prompt or under a sliding window (a layer may see only the last `window`
positions, `models/smallthinker.py`), and the DECODE form of a tick over
the engine's slab.

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
  decode    a tick's run (`decode_attention`: one token a slot, or the
            speculative verify's k + 1) over the slab entry [B, S, G, d]
            WHERE IT LIES: no transpose, no repeat, no copy. Slot b's
            walk visits the blocks 0 .. `positions[b, -1] // block` and
            no other (`decode_blocks`), under a running softmax with
            float32 scores and accumulator, so a tick reads the rows its
            slots hold and not `max_batch x max_seq_len`; a parked slot
            (position 0: `models/engine.py` `_finish`) costs one block.
            Row j of a run masks by its own position, and a block it
            sees nothing of leaves its max, sum and accumulator untouched
            to the bit: a verify row is a sequential tick's. On a TPU it
            is the Pallas kernel `gqa_decode_t<t>`, ONE call a layer: the
            positions scalar-prefetched, the queries resident, the walk
            the kernel's own loops (slots, then a slot's blocks) over
            blocks it copies from HBM itself, two copies ahead of the
            products. Any G and any d of whole 128-lane tiles go, under
            the axes the chip lays the entry down in (`_lies_by_group`):
            G a power of two as [B, S G, d], a block all G heads'
            rows, contiguous; any other G as [B, G, S, d], a block G
            runs of rows, one strided copy (as [B, S, G d] XLA copies
            the slab). A slot's `t x H` query rows are the rows of ONE
            product against a block's `block x G` key rows; a score of
            another key-value head's key is masked like a row past the
            position. Heads narrower than a row ride it PACKED
            (`models/gpt2.py`: p heads to a row of W lanes): each is a
            query head whose row has the other heads' lanes zeroed, so
            H = G x p, d = W, and `scale` is the head's own width's. The
            block follows from the entry's shape (`_decode_block`).
            Elsewhere the same blocks in `jax.numpy`, the reference.
            `dispatch.kernel_choices("gqa_decode")` lists the shapes (B,
            t, H, G, d, S); `decode_rows_read` is the host's count.

The window counts the query's own position: query i sees keys j with
`i - window < j <= i`, so a ring of `window` rows holds exactly what a
decode tick may see.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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


def ring_rows(x: jax.Array, rows: int) -> jax.Array:
    """x [B, T, ...] at positions 0 .. T-1 as a ring of `rows` rows holds
    it: the last min(T, rows) positions, each at `p mod rows`."""
    t = x.shape[1]
    if t <= rows:
        return x
    return jnp.roll(x[:, t - rows:], (t - rows) % rows, axis=1)


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


# ---------------------------------------------------------------- decode
# what one DMA of the decode walk moves of a slot's keys (and as much of
# its values): `_decode_block` sizes a block of rows to it. Large enough
# that a copy runs at the memory's rate and the loop's own cost a block
# is small beside it, small enough that a parked slot's one block is
# cheap (PERF.md, PR 41: the sweep)
_DECODE_BLOCK_BYTES = 256 << 10
# the columns of scores (key rows x key-value heads) one step of the
# kernel holds in float32: a block of more is walked in chunks of this many
_DECODE_CHUNK = 1024
# blocks of keys (and of values) in VMEM: the copies run two ahead of the
# products, which hides a copy's latency behind the block before it
_DECODE_BUFFERS = 3
# the most rows a run may have and still be a tick's: the speculative
# verify's k + 1 at any k served. A longer run is a suffix or a prompt,
# whose [t, S] scores pay for the slab's read with their own rows
DECODE_ROWS = 8


def _decode_block(rows: int, groups: int, d: int, itemsize: int,
                  arrays: int = 2) -> int:
    """The rows of one block of the decode walk over a slab entry [B,
    rows, groups, d]: the power of two whose keys fill
    `_DECODE_BLOCK_BYTES` (a step copies `arrays` = 2 such blocks, keys
    and values; where ONE array is both, its one copy may be as large as
    the two together), not under 128 and not over the entry."""
    block = 128
    while block * groups * d * itemsize * arrays <= _DECODE_BLOCK_BYTES:
        block *= 2
    return min(block, rows)


def decode_block(shape: Tuple[int, ...], dtype) -> int:
    """The block a tick's walk takes a slab entry of this dtype by: keys
    and values [B, rows, groups, d] (`decode_attention`), or latent rows
    [B, rows, width], ONE row a token that is key and value, so a step
    copies one array where the other copies two and its block holds
    twice the rows at the same bytes (`ops/mla.absorbed_attention`: 256
    rows of 1,280 bytes). What the kernels' wrappers ask, and what a host
    that counts the walk's rows asks too (`decode_rows_read`)."""
    itemsize = jnp.dtype(dtype).itemsize
    if len(shape) == 3:
        return _decode_block(shape[1], 1, shape[2], itemsize, arrays=1)
    _, rows, groups, d = shape
    return _decode_block(rows, groups, d, itemsize)


def decode_blocks(positions, block: int, rows: int):
    """The blocks a slot's walk visits: from block 0 to the one that holds
    the slot's last position (a position past the entry visits every
    block and no more). Shared by the kernel, its reference and the
    host's count (numpy or jax.numpy in, the same out)."""
    return (positions // block + 1).clip(1, -(-rows // block))


def decode_rows_read(positions, block: int, rows: int) -> int:
    """The rows one layer's walk reads over ALL slots at these positions
    (numpy, [B] or [B, t]: a slot's last position ends its walk): whole
    blocks, a parked slot's one block."""
    last = np.asarray(positions).reshape(len(positions), -1)[:, -1]
    return int(decode_blocks(last, block, rows).sum()) * block


def _block_start(j, block: int, rows: int):
    """Where block `j` is read from: its own start, held inside an entry
    whose rows are no whole number of blocks (the last block then reads
    rows the one before it held, which `_block_seen` masks)."""
    return jnp.minimum(j * block, rows - block)


def _block_seen(q_at, k_at, j, block: int):
    return (k_at <= q_at) & (k_at >= j * block)


def _decode_walk(q, ck, cv, positions, block: int,
                 scale: Optional[float] = None):
    """The decode form's running softmax in `jax.numpy`, block by block
    as the kernel walks: q [B, t, H, d], ck, cv [B, S, G, d], positions
    [B, t] -> (max, sum, accumulator) [B, G, rep, t, .], float32. Every
    slot steps through the most blocks any slot needs; a step past a
    slot's last block leaves its carry as it was."""
    b, t, h, d = q.shape
    s_rows, g = ck.shape[1], ck.shape[2]
    qg = q.reshape(b, t, g, h // g, d)
    q_at = positions[:, None, None, :, None]
    last = decode_blocks(positions[:, -1], block, s_rows)
    scale = d ** -0.5 if scale is None else scale

    def step(j, carry):
        m, l, acc = carry
        at = _block_start(j, block, s_rows)
        k_j = jax.lax.dynamic_slice_in_dim(ck, at, block, 1)
        v_j = jax.lax.dynamic_slice_in_dim(cv, at, block, 1)
        s = jnp.einsum("btgrd,bsgd->bgrts", qg, k_j,
                       preferred_element_type=F32) * scale
        k_at = at + jnp.arange(block)
        s = jnp.where(_block_seen(q_at, k_at, j, block), s, _NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        new = (m_new, alpha * l + p.sum(-1, keepdims=True),
               acc * alpha + jnp.einsum(
                   "bgrts,bsgd->bgrtd", p.astype(q.dtype), v_j,
                   preferred_element_type=F32))
        walks = (j < last)[:, None, None, None, None]
        return jax.tree.map(lambda a, b_: jnp.where(walks, a, b_),
                            new, carry)

    lead = (b, g, h // g, t)
    init = (jnp.full(lead + (1,), _NEG, F32), jnp.zeros(lead + (1,), F32),
            jnp.zeros(lead + (d,), F32))
    return jax.lax.fori_loop(0, jnp.max(last), step, init)


def _decode_blocked(q, ck, cv, positions, block: int,
                    scale: Optional[float] = None) -> jax.Array:
    b, t, h, d = q.shape
    _, l, acc = _decode_walk(q, ck, cv, positions, block, scale)
    return (acc / l).astype(q.dtype).transpose(0, 3, 1, 2, 4).reshape(
        b, t, h * d)


def _decode_kernel(pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                   q_s, m_s, l_s, acc_s, *, block: int, chunk: int,
                   groups: int, t: int, scale: float):
    """The whole tick's walk, one slot after another. Refs: pos [B, t] in
    SMEM; q [B, t x H, d], every slot's query heads as the model has
    them, resident (a tick's queries are a few hundred KB); k, v the
    slab entry as it lies in HBM (`_lies_by_group`): [B, S x G, d], rows
    and key-value heads read as ONE axis (for G a power of two [B, S, G,
    d] and [B, S G, d] are the same bytes on the chip; [B, S, G d] is
    not, and costs a copy of the slab), or [B, G, S, d], a head's rows
    one after another, which is where any other G lies; o as q. Scratch:
    `kbuf.shape[0]` blocks of keys and as many of values (as the entry
    lies: [block x G, d] or [G, block, d]) with their DMA semaphores, one
    slot's queries scaled and padded to whole sublanes, their running
    max, sum and accumulator.

    ALL heads of a slot are the rows of one product against a block of
    `block x G` key rows, and a score whose key row belongs to another
    key-value head than its query's is masked like a row past the
    position: the matrix unit takes each key tile ONCE for the 32 rows,
    where a product a key-value head would take a strided eighth of the
    block for 4. The walk is the kernel's own loop: slot b takes
    `positions[b, -1] // block + 1` steps, so a block past the slot's
    last costs neither a copy nor a product. The blocks of ALL slots are
    one sequence of copies that runs `buffers - 1` ahead of the
    products, over a slot's end into the next slot's first blocks, so
    that a slab of parked slots streams like one long slot. The scale
    (`scale`: d ** -0.5 for whole heads, a packed head's own for rows of
    several) and log2(e) are folded into the queries; products take the
    cache's dtype (or the queries', the wider) and accumulate in
    float32."""
    slots, rows, d = q_ref.shape
    buffers = kbuf.shape[0]
    padded = q_s.shape[0]
    by_group = len(k_hbm.shape) == 4
    s_rows = k_hbm.shape[2] if by_group else k_hbm.shape[1] // groups
    cd = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    heads = rows // t
    rep = heads // groups

    def copies(slot, j, buf):
        at = _block_start(j, block, s_rows)
        if by_group:    # G runs of `block` rows, one copy
            src = (slot, slice(None), pl.ds(pl.multiple_of(at, 16), block))
        else:
            src = (slot, pl.ds(pl.multiple_of(at * groups, 8),
                               block * groups))
        return (pltpu.make_async_copy(k_hbm.at[src], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[src], vbuf.at[buf],
                                      sem.at[1, buf]))

    def tile(ref, buf, c0):
        """Rows [c0, c0 + chunk) of a block, all heads: [chunk x G, d]."""
        if by_group:
            return ref[buf, :, c0:c0 + chunk, :].reshape(groups * chunk, d)
        return ref[buf, c0 * groups:(c0 + chunk) * groups, :]

    def start(slot, j, buf):
        @pl.when(slot < slots)
        def _():
            for c in copies(slot, j, buf):
                c.start()

    def blocks_of(slot):
        return decode_blocks(pos_ref[jnp.minimum(slot, slots - 1), t - 1],
                             block, s_rows)

    def after(slot, j):
        """The block that follows block `j` of `slot` in the sequence."""
        more = j + 1 < blocks_of(slot)
        return jnp.where(more, slot, slot + 1), jnp.where(more, j + 1, 0)

    ahead = (jnp.int32(0), jnp.int32(0))
    for buf in range(buffers - 1):
        start(*ahead, buf)
        ahead = after(*ahead)
    # row r of a slot is head r mod H of query r // H of the run (the
    # padding rows go with the last query); its key-value head is
    # (r mod H) // rep. Small static counts, so comparisons, no division
    row = jax.lax.broadcasted_iota(jnp.int32, (padded, 1), 0)
    query = sum((row >= i * heads).astype(jnp.int32) for i in range(1, t))
    head = row - query * heads
    g_row = sum((head >= g * rep).astype(jnp.int32)
                for g in range(1, groups))
    # column c of a chunk is key-value head c mod G of key row c // G;
    # where the entry lies by head, key row c mod chunk of head c // chunk
    col = jax.lax.broadcasted_iota(jnp.int32, (padded, chunk * groups), 1)
    if by_group:
        g_col = sum((col >= g * chunk).astype(jnp.int32)
                    for g in range(1, groups))
        mine, k_in = g_col == g_row, col - g_col * chunk
    else:
        mine, k_in = (col % groups) == g_row, col // groups
    q_s[...] = jnp.zeros(q_s.shape, q_s.dtype)

    def slot_walk(b, carry):
        q_s[:rows, :] = q_ref[b].astype(F32) * (scale * _LOG2E)
        m_s[...] = jnp.full(m_s.shape, _NEG, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)
        q_at = jnp.full((padded, 1), pos_ref[b, t - 1], jnp.int32)
        for i in range(t - 1):
            q_at = jnp.where(query == i, pos_ref[b, i], q_at)
        q = q_s[...].astype(cd)

        def step(j, carry):
            done, a_slot, a_j = carry
            buf = jax.lax.rem(done, buffers)
            start(a_slot, a_j, jax.lax.rem(done + buffers - 1, buffers))
            for c in copies(b, j, buf):
                c.wait()
            at = _block_start(j, block, s_rows)
            for c0 in range(0, block, chunk):
                s = jax.lax.dot_general(
                    q, tile(kbuf, buf, c0).astype(cd),
                    (((1,), (1,)), ((), ())), preferred_element_type=F32)
                s = jnp.where(
                    mine & _block_seen(q_at, at + c0 + k_in, j, block),
                    s, _NEG)
                m_prev = m_s[...]
                m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
                p = jnp.exp2(s - m_new)
                alpha = jnp.exp2(m_prev - m_new)
                m_s[...] = m_new
                l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
                acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
                    p.astype(cd), tile(vbuf, buf, c0).astype(cd),
                    (((1,), (0,)), ((), ())), preferred_element_type=F32)
            return (done + 1,) + after(a_slot, a_j)

        carry = jax.lax.fori_loop(0, blocks_of(b), step, carry)
        o_ref[b] = (acc_s[:rows, :] / l_s[:rows, :]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, slots, slot_walk, (jnp.int32(0),) + ahead)


def _lies_by_group(groups: int) -> bool:
    """How the chip lays an entry [B, S, G, d] down (XLA's default for
    the shape, read from compiled ticks: PERF.md section 6, PR 61): with
    G a power of two row by row, G heads to a sublane tile of their own
    size, so [B, S G, d] is the same bytes; with any other G (GPT-2
    small's 3 rows of 256 lanes) a tile of G would be padded, and S is
    the second-minor axis: the bytes are [B, G, S, d]'s."""
    return bool(groups & (groups - 1))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _decode_pallas(q, ck, cv, positions, block: int, interpret: bool,
                   scale: Optional[float] = None) -> jax.Array:
    """Jitted on its own so that the layers of a tick share one lowering
    (`_prefill_pallas`). q [B, t, H, d], ck, cv [B, S, G, d] as the slab
    holds them, positions [B, t] int32 -> [B, t, H d]. The kernel's
    operand is the entry's bytes under the axes they lie in: a reshape
    or a transpose that the chip compiles to a bitcast."""
    b, t, heads, d = q.shape
    s_rows, groups = ck.shape[1], ck.shape[2]
    rows = t * heads
    # the power of two that holds `_DECODE_CHUNK` columns of scores
    chunk = max(128, 1 << (_DECODE_CHUNK // groups).bit_length() - 1)
    if block % chunk:       # a short entry is one block, and one chunk
        chunk = block
    padded = -(-rows // 8) * 8
    if _lies_by_group(groups):
        kv_block = (groups, block, d)
        lying = lambda x: x.transpose(0, 2, 1, 3)
    else:
        kv_block = (block * groups, d)
        lying = lambda x: x.reshape(b, s_rows * groups, d)
    resident = pl.BlockSpec((b, rows, d), lambda i, pos: (0, 0, 0))
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    visited = b * -(-s_rows // block)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, chunk=chunk,
                          groups=groups, t=t,
                          scale=d ** -0.5 if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[resident, where_it_lies, where_it_lies],
            out_specs=resident,
            scratch_shapes=[
                pltpu.VMEM((_DECODE_BUFFERS,) + kv_block, ck.dtype),
                pltpu.VMEM((_DECODE_BUFFERS,) + kv_block, cv.dtype),
                pltpu.SemaphoreType.DMA((2, _DECODE_BUFFERS)),
                pltpu.VMEM((padded, d), F32),
                pltpu.VMEM((padded, 1), F32),
                pltpu.VMEM((padded, 1), F32),
                pltpu.VMEM((padded, d), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        interpret=interpret,
        # the run's rows are in the name, so that a trace tells a tick's
        # call from a verify pass's
        name=f"gqa_decode_t{t}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * visited * rows * block * groups * d,
            bytes_accessed=2 * q.size * q.dtype.itemsize
            + 2 * visited * block * groups * d * ck.dtype.itemsize,
            transcendentals=visited * rows * block * groups),
    )(positions, q.reshape(b, rows, d), lying(ck), lying(cv))
    return out.reshape(b, t, heads * d)


def decode_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     positions: jax.Array, scale: Optional[float] = None
                     ) -> jax.Array:
    """A tick's attention over the slab where it lies: q [B, t, H, d] (t
    = 1, or the speculative verify's k + 1), ck, cv [B, S, G, d] in their
    own dtype, query (b, j) seeing rows `<= positions[b, j]` of slot b
    (positions [B, t] int32, ascending along t). Returns [B, t, H d] in
    q's dtype: what the masked softmax over all S rows gives, from a walk
    that ends at each slot's last block (module docstring). The block
    follows from the entry's shape (`decode_block`). Any G goes; `scale`
    (d ** -0.5 where none is given) is what a row of several packed
    heads passes: its queries are rows with the other heads' lanes
    zeroed, and a head's own width scales them (`models/gpt2.py`)."""
    b, t, h, d = q.shape
    s_rows, groups = ck.shape[1], ck.shape[2]
    block = decode_block(ck.shape, ck.dtype)
    shape = (b, t, h, groups, d, s_rows)
    positions = positions.astype(jnp.int32)
    interpret = dispatch.interpret_forced()
    reason = dispatch.backend_reason()
    if not reason and not interpret and (d % 128 or s_rows % 16):
        reason = (f"rows of {d} numbers or an entry of {s_rows} rows do "
                  "not fill the kernel's tiles")
    if reason:
        dispatch.record_choice("gqa_decode", shape, "reference", reason,
                               block=block)
        return _decode_blocked(q, ck, cv, positions, block, scale)
    dispatch.record_choice("gqa_decode", shape, "pallas", block=block)
    return _decode_pallas(q, ck, cv, positions, block, interpret, scale)


def cache_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                    positions: jax.Array) -> jax.Array:
    """Masked attention of q [B, t, n_heads, hd] over the cache as it
    lies, ck/cv [B, S, n_kv, hd] in their own dtype; query (b, j) sees
    rows <= positions[b, j]. Heads are contracted per kv group: head h
    is (g, r) = (h // rep, h % rep), the order jnp.repeat(axis=2) gave,
    so `wo` sees the same columns. Returns [B, t, n_heads hd] (d_model
    for `models/llama.py`; `models/smallthinker.py`, whose heads do not
    add up to its hidden size, calls this too).

    A run of at most `DECODE_ROWS` rows is a tick's (one token a slot,
    or the speculative verify's k + 1) and takes the decode form
    (`decode_attention`): each slot's rows up to its position, block by
    block. A longer run (a suffix on a cached prefix, a prompt of at
    most one block, an uncached forward) takes the slab form: its
    [t, S] scores spread the slab's read over their rows, and nothing
    says its slots are short. The choice reads `q`'s shape alone."""
    if q.shape[1] <= DECODE_ROWS:
        return decode_attention(q, ck, cv, positions)
    return slab_attention(q, ck, cv, positions)


def slab_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """`cache_attention` over ALL rows of ALL slots: float32 scores of
    every query against the whole entry, masked afterwards."""
    b, t, heads, hd = q.shape
    groups = ck.shape[2]
    qg = q.reshape(b, t, groups, heads // groups, hd)
    scores = jnp.einsum("btgrd,bsgd->bgrts", qg, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / (hd ** 0.5)
    col = jnp.arange(ck.shape[1])[None, None, None, None, :]
    visible = col <= positions[:, None, None, :, None]
    scores = jnp.where(visible, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    a = jnp.einsum("bgrts,bsgd->btgrd", probs, cv)
    return a.reshape(b, t, heads * hd)
