"""Paged KV cache with hash-chained prefix reuse — the serving-envelope
lever the ROADMAP names first: a fleet of requests sharing a long system
prompt must not re-prefill it per request (vLLM's PagedAttention prefix
cache, rebuilt for this engine's fixed-slab TPU decode design).

Layout: one device pool per engine, K and V each
``[layers, num_blocks, block_size, kv_heads * head_dim]`` in the model's
cache dtype: heads and head_dim lie flat in one axis, so a block's rows
stay whole lanes for heads of any width (with a 64-wide head_dim as an
axis of its own the chip's default layout makes ``num_blocks`` the
minor-most axis, and a program that wants another order copies the
whole pool in and out). A family's own cache may hold the same row in
another split of that flat axis (models/gpt2.py: heads of 64 four to a
row of 256 lanes, ``[B, S, 3, 256]``), and `_heads` is whatever trailing
pair the family's cache has: the flat axis is the same bytes in the
same order either way. Blocks are the unit of sharing:

- **hash-chained index** — block ``i`` of a prompt is keyed by
  ``H(chain_digest(blocks < i), tokens_i)``, so a lookup walks the
  longest cached block-aligned prefix without comparing whole prompts
  (token tuples are still verified on match — a digest collision must
  never serve wrong KV). A partial tail block (prompt ends mid-block)
  is indexed separately under its parent digest + exact token tuple.
- **refcounts** — every admitted request pins the blocks backing its
  matched prefix for its lifetime; pinned blocks are never evicted or
  mutated. Refcount-0 blocks STAY cached (that is the cache) and are
  only reclaimed by LRU eviction under pool pressure, leaves first so a
  chain interior never orphans reachable descendants.
- **copy-on-write** — extending a cached partial block (request B's
  prompt continues where request A's ended mid-block) copies the shared
  block into a fresh one and writes the new tokens into the copy; the
  original stays indexed for future short matches.
- **graceful exhaustion** — when the pool has no free or evictable
  block, commit simply stops caching that prompt's remaining blocks;
  prefill correctness never depends on pool capacity.

Correctness invariant (asserted in tier-1 on CPU): engine outputs with
the cache enabled are bit-identical to the uncached engine. It holds
because cached prefix KV is byte-for-byte what a full prefill would
recompute (same absolute RoPE positions, same window length, and masked
softmax contributes exact zeros for unwritten rows), and because a
weight swap invalidates the whole index — stale-generation KV is never
matched again (in-flight slots keep decoding off their own slab copy).
It holds to the bit while a family's prefill has ONE form: `models/llama.py`
attends over the run alone for a prompt of more than one block of its
prompt form, so there a suffix on top of cached rows and a prefill of the
whole prompt agree to rounding, and a near-tie of two logits may fall the
other way.

**int8 blocks** (``RAY_TPU_KV_INT8=1`` or the ``int8=`` ctor arg): the
pool stores K/V as int8 with per-block-CHANNEL fp32 scales (amax over
the block's token rows, one scale per (layer, head, head_dim) channel
— the channel-wise shape that keeps RoPE'd K's per-dim dynamic range).
Quantize-on-commit (inside the commit's one program, donated and in
place like every other pool mutation) and dequantize-on-gather are
jits, so the HALVED bytes per block
buy a doubled default pool (``resolve_pool_config`` sizes 2x blocks
when int8 is on and the pool wasn't pinned explicitly) — bigger decode
batches and higher prefix-cache residency for the same HBM. Everything
OUTSIDE the pool stays bit-exact: gather hands back fp KV in the cache
dtype and the suffix prefill / splice / decode path is unchanged; the
quantization error itself is bounded by the rtol equivalence test in
tests/test_speculate.py.

**Tiered KV plane** (``serve/kvplane.py``): the pool is tier 1 of a
three-tier hierarchy. ``attach_arena()`` hooks a host-RAM arena into
the eviction path — a block evicted under pool pressure spills its
int8+per-block-channel-scales wire form (``_payload_locked``'s layout)
to the arena instead of dying, and a later ``lookup()`` whose chain
walk breaks consults the arena and re-adopts the block through the
normal insert path (int8 pools round-trip bit-exactly; fp pools
re-enter within the int8 tolerance contract).
``export_prefix()``/``import_prefix()`` move whole block-aligned
prefixes in the same wire format for tier 3 (chunk-fabric objects any
replica can adopt); ``prefix_digests()`` exposes the chain digests the
cluster-wide prefix directory is keyed by.

**Drafting from cache** (``propose()``): the index's hash chains store
EXACT token tuples, so the longest chain extending a request's current
context IS a free speculative draft — no draft model, no extra
compile. The engine's prompt-lookup proposer (models/engine.py) reads
it; proposals are never pinned (a wrong draft is rejected by the
verify pass, so correctness never depends on what propose returns).

Surfaces (the full treatment every subsystem gets):
``util.state.kv_cache_stats()``, ``ray_tpu kvcache``, dashboard
``/api/kvcache``, lazy-init Prometheus counters/gauges (no pusher on
import; the pool-utilization gauge reads the int8-doubled block count
when int8 is on), and prefix-hit / evict instant markers in the merged
timeline. Knobs: ``RAY_TPU_KV_CACHE`` (enable, default 1),
``RAY_TPU_KV_BLOCK_SIZE`` (default 16), ``RAY_TPU_KV_POOL_BLOCKS``
(default: one decode slab's worth, ``max_batch * ceil(S/block)``;
doubled under int8), ``RAY_TPU_KV_INT8`` (default 0).
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ROOT_DIGEST = b"ray_tpu-kv-root"
_EVENTS_KEPT = 512


def kv_int8_default() -> bool:
    """The ``RAY_TPU_KV_INT8`` env default every pool owner (the
    colocated engine, the disagg prefill tier) resolves through."""
    from ray_tpu.util import envknobs

    return envknobs.get_str("RAY_TPU_KV_INT8", "0") == "1"


def resolve_pool_config(config: Any,
                        block_size: Optional[int] = None,
                        pool_blocks: Optional[int] = None, *,
                        slots: int = 4,
                        int8: bool = False) -> Tuple[int, int]:
    """Resolve ``(block_size, pool_blocks)`` from explicit args, the
    ``RAY_TPU_KV_BLOCK_SIZE`` / ``RAY_TPU_KV_POOL_BLOCKS`` env knobs, or
    the ``slots * ceil(max_seq_len / block_size)`` sizing default — the
    ONE implementation every pool owner (the colocated engine, the
    disaggregated prefill tier) defaults through. Under ``int8`` a
    DEFAULTED pool doubles its block count — int8 blocks cost half the
    bytes, so the same HBM budget holds twice the prefixes (an explicit
    block count, arg or env, is always honored as-is)."""
    from ray_tpu.util import envknobs

    bs = int(block_size
             or envknobs.get_int("RAY_TPU_KV_BLOCK_SIZE", 16))
    pb = int(pool_blocks
             or envknobs.get_int("RAY_TPU_KV_POOL_BLOCKS", 0))
    if not pb:
        pb = slots * (-(-config.max_seq_len // bs))
        if int8:
            pb *= 2
    return bs, pb


def _chain(digest: bytes, tokens: Tuple[int, ...]) -> bytes:
    h = hashlib.blake2b(digest, digest_size=16)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


def _ns_root(namespace: Optional[str]) -> bytes:
    """The hash-chain root for a cache namespace. A multi-tenant LoRA
    deployment keys prefixes by (tenant, prompt) — a tenant's KV is
    computed under ITS adapter, so another tenant matching it would be
    served wrong values silently. Deriving a per-namespace root makes
    every digest downstream tenant-scoped; namespace=None keeps the
    historical root, so single-tenant deployments are bit-identical."""
    if namespace is None:
        return _ROOT_DIGEST
    h = hashlib.blake2b(_ROOT_DIGEST, digest_size=16)
    h.update(str(namespace).encode())
    return h.digest()


def prefix_digests(tokens, block_size: int,
                   namespace: Optional[str] = None,
                   max_blocks: int = 32) -> List[str]:
    """Chain digests at every full-block boundary of `tokens`, LONGEST
    FIRST — the keys the cluster-wide prefix directory (conductor
    ``kvplane_lookup``) matches against. Hex, because the digests cross
    the RPC plane as JSON-safe metadata. Namespace-scoped exactly like
    the index itself, so one tenant's directory entries can never match
    another's prompt."""
    tokens = np.asarray(tokens).reshape(-1)
    digest = _ns_root(namespace)
    out: List[str] = []
    n_full = min(len(tokens) // block_size, max_blocks)
    for i in range(n_full):
        blk = tuple(int(t) for t in
                    tokens[i * block_size:(i + 1) * block_size])
        digest = _chain(digest, blk)
        out.append(digest.hex())
    out.reverse()
    return out


# --------------------------------------------------------- device ops
# All pool mutation is jitted with the pool donated, so XLA updates the
# arrays in place: a commit touches O(prompt) bytes, the adoption of one
# spilled block O(block), never O(pool).

def _quantize(blk):
    """[..., bs, W] float -> (int8 same shape, f32 scale [..., 1, W]):
    per-block-CHANNEL symmetric quantization, one scale per (layer,
    head, head_dim) channel, amax'd over the block's token rows.
    amax==0 channels take scale 1 so 0/0 never NaNs (their rows
    quantize to exact 0 either way)."""
    f = blk.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _flat(x):
    """[L, rows, H, hd] -> [L, 1, rows, H*hd]: one block (or its
    scales, rows = 1) as the pool holds it."""
    return x.reshape((x.shape[0], 1, x.shape[1], -1))


@functools.partial(jax.jit, donate_argnums=(0,))
def _commit_blocks(pool, c, ids, cow):
    """A whole commit's writes to ONE pool (keys or values) in one
    program. `pool` is ``(blocks [L,N,bs,W],)`` in the cache dtype, or
    ``(int8 blocks, f32 scales [L,N,1,W])``; `c` ``[L,S,H,hd]`` is a
    prefill's fill as `_prefill_paged` returned it, cut into ``S // bs``
    blocks where it lies. ``ids[i]`` is the pool row that takes the
    prompt's block `i`; an id past the pool means "not written" (the
    scatter drops it), so the shape is the same for every prompt length
    and the program compiles once for a window and a pool.

    `cow` = (shared row, position, rows kept) is the commit's one
    copy-on-write: the block at `position` takes its first `rows kept`
    token rows from the SHARED pool row and the rest from `c` (0 rows
    kept: no merge). The int8 pool merges in float32 (the shared rows
    dequantized) and then quantizes every block by its own channel
    scales. Temporaries are O(c), never O(pool)."""
    data = pool[0]
    layers, _, bs, width = data.shape
    nb = ids.shape[0]
    src, pos, kept = cow
    blocks = c[:, :nb * bs].reshape((layers, nb, bs, width))
    one = (layers, 1, bs, width)
    shared = jax.lax.dynamic_slice(data, (0, src, 0, 0), one)
    if len(pool) == 2:
        blocks = blocks.astype(jnp.float32)
        shared = shared.astype(jnp.float32) * jax.lax.dynamic_slice(
            pool[1], (0, src, 0, 0), (layers, 1, 1, width))
    at = (0, pos, 0, 0)
    row = jnp.arange(bs)[None, None, :, None]
    blocks = jax.lax.dynamic_update_slice(
        blocks, jnp.where(row < kept, shared,
                          jax.lax.dynamic_slice(blocks, at, one)), at)
    if len(pool) == 1:
        return (data.at[:, ids].set(blocks, mode="drop",
                                    unique_indices=True),)
    q, scale = _quantize(blocks)
    return (data.at[:, ids].set(q, mode="drop", unique_indices=True),
            _write_scales(pool[1], scale, ids))


def _write_scales(scales, new, ids):
    """``scales[:, ids[i]] = new[:, i]`` for the ids inside the pool,
    one row at a time: a scale row is no whole tile, and XLA's scatter
    would copy the scale pool to another layout and back, where a row's
    update stays in place. An id past the pool rewrites the last row
    with itself."""
    last = scales.shape[1] - 1

    def body(i, s):
        at = jnp.minimum(ids[i], last)
        row = jnp.where(ids[i] <= last,
                        jax.lax.dynamic_slice_in_dim(new, i, 1, axis=1),
                        jax.lax.dynamic_slice_in_dim(s, at, 1, axis=1))
        return jax.lax.dynamic_update_slice_in_dim(s, row, at, axis=1)

    return jax.lax.fori_loop(0, ids.shape[0], body, scales)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_block(pool_k, pool_v, bid, blk_k, blk_v):
    """pool [L,N,bs,W] <- blk [L,bs,H,hd] at block row `bid` (a spilled
    block's re-entry; a commit goes through `_commit_blocks`)."""
    at = (0, bid, 0, 0)
    return (jax.lax.dynamic_update_slice(pool_k, _flat(blk_k), at),
            jax.lax.dynamic_update_slice(pool_v, _flat(blk_v), at))


def _unflat(x, ntok, heads):
    """Gathered blocks [L, n, rows, W] -> [L, n*rows, H, hd][:, :ntok]."""
    ll, n, rows = x.shape[:3]
    return x.reshape((ll, n * rows) + heads)[:, :ntok]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gather_prefix(pool_k, pool_v, bids, ntok, heads):
    """Assemble a matched prefix: block rows `bids` concatenated along
    the token axis, truncated to the matched token count (the tail
    block may be partial), `heads` = (H, hd) apart again."""
    return (_unflat(jnp.take(pool_k, bids, axis=1), ntok, heads),
            _unflat(jnp.take(pool_v, bids, axis=1), ntok, heads))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _write_block_qraw(pool_k, pool_v, sk, sv, bid, qk, qv, sck, scv):
    """Adopt an already-quantized wire-format block (tier-2/3 re-entry:
    int8 [L,bs,H,hd], scales [L,1,H,hd]) into the int8 pool VERBATIM —
    no requantize, so a spill/readopt round trip is bit-exact for int8
    pools."""
    at = (0, bid, 0, 0)
    return (jax.lax.dynamic_update_slice(pool_k, _flat(qk), at),
            jax.lax.dynamic_update_slice(pool_v, _flat(qv), at),
            jax.lax.dynamic_update_slice(sk, _flat(sck), at),
            jax.lax.dynamic_update_slice(sv, _flat(scv), at))


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gather_prefix_q(pool_k, pool_v, sk, sv, bids, ntok, heads, dtype):
    """Dequant-on-gather: assemble a matched prefix out of the int8
    pool back into the cache dtype — downstream (suffix prefill,
    splice, decode) sees ordinary fp KV, so everything outside the
    quantized pool stays bit-exact plumbing."""
    def _deq(pool, scales):
        q = jnp.take(pool, bids, axis=1)       # [L, n, bs, W]
        s = jnp.take(scales, bids, axis=1)     # [L, n, 1, W]
        return _unflat((q.astype(jnp.float32) * s).astype(dtype), ntok,
                       heads)

    return _deq(pool_k, sk), _deq(pool_v, sv)


# ----------------------------------------------------- prometheus (lazy)
# Created on first pool construction, never at import: importing
# ray_tpu.models must not spawn a metrics pusher (weights/metrics.py
# pattern — rebound ONCE to a complete dict).

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def kvcache_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _metrics = dict(
                lookups=Counter(
                    "ray_tpu_kvcache_lookups_total",
                    "prefix-cache lookups at admission",
                    tag_keys=("outcome",)),
                reused_tokens=Counter(
                    "ray_tpu_kvcache_reused_tokens_total",
                    "prompt tokens served from cached KV blocks "
                    "(prefill skipped)"),
                prefilled_tokens=Counter(
                    "ray_tpu_kvcache_prefilled_tokens_total",
                    "prompt tokens actually prefilled (suffix after the "
                    "cached prefix)"),
                evictions=Counter(
                    "ray_tpu_kvcache_evictions_total",
                    "refcount-0 blocks LRU-evicted under pool pressure"),
                cow_copies=Counter(
                    "ray_tpu_kvcache_cow_copies_total",
                    "copy-on-write block copies (shared partial block "
                    "extended)"),
                utilization=Gauge(
                    "ray_tpu_kvcache_pool_utilization",
                    "fraction of pool blocks holding cached or pinned "
                    "KV"))
    return _metrics


class PrefixMatch:
    """Result of a lookup: the pinned block table backing the longest
    cached prefix, and how many prompt tokens it covers."""

    __slots__ = ("bids", "tokens", "full_blocks", "partial_bid",
                 "partial_len", "outcome")

    def __init__(self, bids: List[int], tokens: int, full_blocks: int,
                 partial_bid: Optional[int], partial_len: int,
                 outcome: str):
        self.bids = bids
        self.tokens = tokens
        self.full_blocks = full_blocks
        self.partial_bid = partial_bid
        self.partial_len = partial_len
        self.outcome = outcome


class _Block:
    __slots__ = ("bid", "tokens", "filled", "ref", "last_used",
                 "children", "index_key", "parent_bid", "parent_digest",
                 "ns")

    def __init__(self, bid: int):
        self.bid = bid
        self.tokens: Tuple[int, ...] = ()
        self.filled = 0
        self.ref = 0
        self.last_used = 0
        self.children = 0
        # ("full", digest) | ("partial", parent_digest, tokens) | None
        # (None = orphaned by invalidate(): unreachable, freed on the
        # last release)
        self.index_key: Optional[tuple] = None
        self.parent_bid: Optional[int] = None
        # the chain digest this block EXTENDS — the forward-walk key
        # the draft proposer follows (propose()); partial blocks reuse
        # their index key's parent digest
        self.parent_digest: Optional[bytes] = None
        # cache namespace (LoRA tenant) the block was committed under —
        # invalidate(namespace=) scopes an adapter hot-swap's flush to
        # exactly this tenant's blocks
        self.ns: Optional[str] = None


class PagedKVCache:
    """Block-pool KV allocator + prefix index for one engine.

    Thread-safe; in practice only the engine's decode thread mutates it
    while stats/snapshot readers come from anywhere."""

    def __init__(self, config: Any, *, block_size: int, num_blocks: int,
                 int8: Optional[bool] = None):
        from .family import slab_spec

        if block_size < 1 or num_blocks < 1:
            raise ValueError("block_size and num_blocks must be >= 1")
        spec = slab_spec(config, 1)
        heads, head_dim = spec.row_shape
        self.layers = spec.layers
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = spec.dtype
        self.int8 = kv_int8_default() if int8 is None else bool(int8)
        self._heads = (int(heads), int(head_dim))
        shape = (self.layers, self.num_blocks, self.block_size,
                 heads * head_dim)
        pool_dtype = jnp.int8 if self.int8 else self.dtype
        self._pool_k = jnp.zeros(shape, pool_dtype)
        self._pool_v = jnp.zeros(shape, pool_dtype)
        if self.int8:
            sshape = (self.layers, self.num_blocks, 1, heads * head_dim)
            self._scale_k = jnp.zeros(sshape, jnp.float32)
            self._scale_v = jnp.zeros(sshape, jnp.float32)
        self._empty_k = jnp.zeros((self.layers, 0, heads, head_dim),
                                  self.dtype)
        self._lock = threading.Lock()
        self._blocks: Dict[int, _Block] = {}
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._full_index: Dict[bytes, int] = {}
        self._partial_index: Dict[bytes,
                                  Dict[Tuple[int, ...], int]] = {}
        # forward-walk index for the draft proposer: chain digest ->
        # {tokens: bid} of the FULL blocks extending it (partial tails
        # are already forward-indexed by _partial_index)
        self._children: Dict[bytes, Dict[Tuple[int, ...], int]] = {}
        self._tick = itertools.count(1)
        # tier-2 host arena (serve/kvplane.HostArena) — None keeps the
        # historical single-tier behavior bit-identically
        self._arena: Optional[Any] = None
        self._events: List[Dict[str, Any]] = []
        # programs launched by commits (`_write_planned_locked`), and
        # what the latest commit() made of them: (dispatches, blocks
        # inserted) — the engine's loop record reads it on the thread
        # that committed
        self._dispatches = 0
        self.last_commit: Tuple[int, int] = (0, 0)
        self._stats: Dict[str, int] = {
            k: 0 for k in ("lookups", "hits", "partial_hits", "misses",
                           "reused_tokens", "prefilled_tokens",
                           "inserted_blocks", "evictions", "cow_copies",
                           "invalidations")}
        kvcache_metrics()  # lazy registration, before the first event

    # ------------------------------------------------------------ lookup

    def lookup(self, tokens: np.ndarray, max_tokens: int,
               namespace: Optional[str] = None) -> PrefixMatch:
        """Longest cached block-aligned (+ partial tail) prefix of
        `tokens`, capped at `max_tokens` so the caller always has a
        suffix left to prefill (the last prompt position's logits feed
        the first sampled token). Matched blocks are PINNED — pair every
        lookup with a release() of the returned/committed table.
        `namespace` scopes the match (LoRA tenant: KV computed under
        one tenant's adapter can never serve another — pass the SAME
        namespace to the paired commit())."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        with self._lock:
            digest = _ns_root(namespace)
            bids: List[int] = []
            matched = 0
            now0 = next(self._tick)
            while matched + bs <= max_tokens:
                blk = tuple(int(t) for t in tokens[matched:matched + bs])
                nxt = _chain(digest, blk)
                bid = self._full_index.get(nxt)
                if bid is None or self._blocks[bid].tokens != blk:
                    # tier-2: a block evicted under pool pressure may
                    # still live in the host arena — re-adopt it through
                    # the normal insert path and keep walking
                    bid = None
                    if self._arena is not None:
                        payload = self._arena.take_full(nxt, blk)
                        if payload is not None:
                            parent = bids[-1] if bids else None
                            bid = self._adopt_payload_locked(
                                payload, parent, now0)
                            if bid is None:
                                self._arena.give_back(payload)
                    if bid is None:
                        break
                # pin AS WE WALK: an arena adoption further down the
                # chain may have to evict, and an unpinned match would
                # be a legal victim
                b = self._blocks[bid]
                b.ref += 1
                b.last_used = now0
                bids.append(bid)
                digest = nxt
                matched += bs
            full_blocks = len(bids)
            partial_bid: Optional[int] = None
            partial_len = 0
            for ptoks, bid in self._partial_index.get(digest, {}).items():
                k = len(ptoks)
                if (k > partial_len and matched + k <= max_tokens
                        and tuple(int(t) for t in
                                  tokens[matched:matched + k]) == ptoks):
                    partial_bid, partial_len = bid, k
            if partial_bid is None and self._arena is not None:
                payload = self._arena.take_partial(
                    digest, tokens[matched:], max_tokens - matched)
                if payload is not None:
                    parent = bids[-1] if bids else None
                    bid = self._adopt_payload_locked(payload, parent,
                                                     now0)
                    if bid is None:
                        self._arena.give_back(payload)
                    else:
                        partial_bid = bid
                        partial_len = len(payload["tokens"])
            if partial_bid is not None:
                b = self._blocks[partial_bid]
                b.ref += 1
                b.last_used = now0
                bids.append(partial_bid)
                matched += partial_len
            plen = len(tokens)
            if matched and plen - matched <= bs:
                outcome = "hit"
                self._stats["hits"] += 1
            elif matched:
                outcome = "partial"
                self._stats["partial_hits"] += 1
            else:
                outcome = "miss"
                self._stats["misses"] += 1
            self._stats["lookups"] += 1
            self._stats["reused_tokens"] += matched
        m = kvcache_metrics()
        m["lookups"].inc(tags={"outcome": outcome})
        if matched:
            m["reused_tokens"].inc(matched)
        return PrefixMatch(bids, matched, full_blocks, partial_bid,
                           partial_len, outcome)

    def gather(self, match: PrefixMatch):
        """Device prefix ``([L, tokens, H, hd] k, same v)`` for a match
        (empty arrays for a miss — the uncached-prefill program shape)."""
        if match.tokens == 0:
            return self._empty_k, self._empty_k
        bids = jnp.asarray(match.bids, jnp.int32)
        with self._lock:
            # dispatch under the lock: commit()'s pool writes are jitted
            # with the pool DONATED, so a gather dispatched between a
            # concurrent commit's donation and its pool-reference swap
            # would read a deleted Array (concurrent callers exist — the
            # disaggregated prefill tier runs prefills in parallel).
            # Same-device stream order makes the dispatch itself the
            # only critical section; the compute overlaps freely.
            if self.int8:
                return _gather_prefix_q(self._pool_k, self._pool_v,
                                        self._scale_k, self._scale_v,
                                        bids, match.tokens, self._heads,
                                        self.dtype)
            return _gather_prefix(self._pool_k, self._pool_v, bids,
                                  match.tokens, self._heads)

    # ------------------------------------------------ tiered KV plane

    def attach_arena(self, arena: Optional[Any]) -> None:
        """Hook a tier-2 host arena (serve/kvplane.HostArena) into the
        pool: evictions spill their wire-format payload to
        ``arena.accept()`` instead of dying, and a broken lookup chain
        walk consults ``arena.take_full()/take_partial()`` before
        giving up. ``attach_arena(None)`` detaches (single-tier
        behavior, bit-identical to pre-kvplane)."""
        with self._lock:
            self._arena = arena

    def _payload_locked(self, b: _Block) -> Dict[str, Any]:
        """One block's tier-2/3 wire-format payload: int8 K/V + f32
        per-block-channel scales, heads and head_dim apart, plus the
        index identity needed to re-adopt it. int8 pools hand out their
        bytes verbatim (lossless round trip); fp pools quantize on
        spill, re-entering within the int8 tolerance contract."""
        bid = b.bid
        if self.int8:
            wire = (self._pool_k[:, bid], self._scale_k[:, bid],
                    self._pool_v[:, bid], self._scale_v[:, bid])
        else:
            wire = (_quantize(self._pool_k[:, bid])
                    + _quantize(self._pool_v[:, bid]))
        # [L, rows, W] as the pool holds it -> the wire's [L, rows, H, hd]
        qk, sk, qv, sv = (np.asarray(x).reshape(x.shape[:2] + self._heads)
                          for x in wire)
        return {"index_key": b.index_key, "tokens": b.tokens,
                "filled": b.filled, "ns": b.ns,
                "parent_digest": b.parent_digest,
                "qk": qk, "qv": qv, "sk": sk, "sv": sv}

    def _adopt_payload_locked(self, payload: Dict[str, Any],
                              parent: Optional[int],
                              now: int) -> Optional[int]:
        """Re-adopt a wire-format payload into the pool through the
        normal insert path. Returns the new bid, or None when no block
        could be allocated (the caller gives the payload back to its
        tier). The adopted block starts UNPINNED — lookup/import pin
        explicitly."""
        key = payload.get("index_key")
        if key is None:
            return None
        bid = self._alloc_locked()
        if bid is None:
            return None
        if self.int8:
            (self._pool_k, self._pool_v, self._scale_k,
             self._scale_v) = _write_block_qraw(
                self._pool_k, self._pool_v, self._scale_k,
                self._scale_v, np.int32(bid), payload["qk"],
                payload["qv"], payload["sk"], payload["sv"])
        else:
            bk = (jnp.asarray(payload["qk"], jnp.float32)
                  * jnp.asarray(payload["sk"])).astype(self.dtype)
            bv = (jnp.asarray(payload["qv"], jnp.float32)
                  * jnp.asarray(payload["sv"])).astype(self.dtype)
            self._pool_k, self._pool_v = _write_block(
                self._pool_k, self._pool_v, np.int32(bid), bk, bv)
        self._insert_locked(bid, key, payload["tokens"],
                            payload["filled"], parent, now,
                            payload.get("ns"),
                            payload.get("parent_digest"))
        self._blocks[bid].ref = 0
        return bid

    def export_prefix(self, tokens, namespace: Optional[str] = None,
                      max_blocks: int = 32
                      ) -> Optional[Tuple[Dict[str, Any], int, str]]:
        """Pack the longest cached full-block chain prefix of `tokens`
        in the tier-3 wire format (stacked int8 blocks + scales + the
        exact token prefix). Returns ``(packed, n_tokens, digest_hex)``
        — digest_hex is the chain digest the prefix directory keys the
        published object by — or None when nothing is cached. Nothing
        is pinned: tier 3 holds a COPY, eviction of the source blocks
        is irrelevant."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        payloads: List[Dict[str, Any]] = []
        with self._lock:
            digest = _ns_root(namespace)
            matched = 0
            while (matched + bs <= len(tokens)
                   and len(payloads) < max_blocks):
                blk = tuple(int(t) for t in tokens[matched:matched + bs])
                nxt = _chain(digest, blk)
                bid = self._full_index.get(nxt)
                if bid is None or self._blocks[bid].tokens != blk:
                    break
                payloads.append(self._payload_locked(self._blocks[bid]))
                digest = nxt
                matched += bs
        if not payloads:
            return None
        packed = {"qk": np.stack([p["qk"] for p in payloads]),
                  "qv": np.stack([p["qv"] for p in payloads]),
                  "sk": np.stack([p["sk"] for p in payloads]),
                  "sv": np.stack([p["sv"] for p in payloads]),
                  "tokens": np.asarray(tokens[:matched], np.int64)}
        return packed, matched, digest.hex()

    def import_prefix(self, tokens, packed: Dict[str, Any],
                      namespace: Optional[str] = None) -> int:
        """Adopt a tier-3 packed prefix (``export_prefix``'s format,
        fetched over the chunk fabric) into this pool's index. Blocks
        already cached are skipped; the rest enter through the normal
        insert path, unpinned. Returns the number of blocks adopted.
        The packed token prefix is verified against `tokens` — a
        digest-directory collision must never seed wrong KV."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        ptoks = np.asarray(packed["tokens"]).reshape(-1)
        if len(ptoks) > len(tokens) \
                or not np.array_equal(tokens[:len(ptoks)], ptoks):
            return 0
        nb = int(packed["qk"].shape[0])
        adopted_bids: List[int] = []
        with self._lock:
            digest = _ns_root(namespace)
            now = next(self._tick)
            parent: Optional[int] = None
            for i in range(nb):
                if (i + 1) * bs > len(ptoks):
                    break
                blk = tuple(int(t) for t in
                            tokens[i * bs:(i + 1) * bs])
                nxt = _chain(digest, blk)
                bid = self._full_index.get(nxt)
                if bid is not None and self._blocks[bid].tokens == blk:
                    parent, digest = bid, nxt
                    continue
                payload = {"index_key": ("full", nxt), "tokens": blk,
                           "filled": bs, "ns": namespace,
                           "parent_digest": digest,
                           "qk": packed["qk"][i], "qv": packed["qv"][i],
                           "sk": packed["sk"][i], "sv": packed["sv"][i]}
                bid = self._adopt_payload_locked(payload, parent, now)
                if bid is None:
                    break
                # pin for the loop's duration: a later adoption's alloc
                # must not evict an earlier adopted leaf
                self._blocks[bid].ref = 1
                adopted_bids.append(bid)
                parent, digest = bid, nxt
            for bid in adopted_bids:
                self._blocks[bid].ref = 0
            util = 1.0 - len(self._free) / self.num_blocks
        kvcache_metrics()["utilization"].set(util)
        return len(adopted_bids)

    def force_evict(self, n: int) -> int:
        """Evict up to `n` unpinned leaf blocks (LRU order) regardless
        of pool pressure — the ``evict_storm`` chaos op. With an arena
        attached every victim spills to tier 2, so a storm sheds
        capacity, never correctness."""
        evicted = 0
        with self._lock:
            for _ in range(int(n)):
                victim: Optional[_Block] = None
                for b in self._blocks.values():
                    if b.ref == 0 and b.children == 0 \
                            and b.index_key is not None:
                        if victim is None \
                                or b.last_used < victim.last_used:
                            victim = b
                if victim is None:
                    break
                self._evict_locked(victim)
                self._free.append(victim.bid)
                evicted += 1
            util = 1.0 - len(self._free) / self.num_blocks
        kvcache_metrics()["utilization"].set(util)
        return evicted

    # ----------------------------------------------------------- propose

    def propose(self, tokens, k: int,
                namespace: Optional[str] = None) -> List[int]:
        """Draft up to `k` tokens CONTINUING `tokens` off the prefix
        index's exact token chains (prompt-lookup speculative decoding,
        models/engine.py): walk the chain matching the context's
        block-aligned prefix, then follow the full-block children (and
        finally any partial tail) whose tokens extend the context's
        remainder. Returns [] when no cached chain extends the context.
        Nothing is pinned — a wrong draft is simply rejected by the
        verify pass, so correctness never depends on this answer."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        n = len(tokens)
        out: List[int] = []
        with self._lock:
            digest = _ns_root(namespace)
            matched = 0
            while matched + bs <= n:
                blk = tuple(int(t) for t in tokens[matched:matched + bs])
                nxt = _chain(digest, blk)
                bid = self._full_index.get(nxt)
                if bid is None or self._blocks[bid].tokens != blk:
                    break
                digest = nxt
                matched += bs
            rem = tuple(int(t) for t in tokens[matched:])
            if len(rem) >= bs:
                return []  # context diverged from every cached chain
            while len(out) < k:
                kids = self._children.get(digest, {})
                step = None
                for toks, bid in kids.items():
                    if toks[:len(rem)] == rem and len(toks) > len(rem):
                        step = (toks, bid)
                        break
                if step is None:
                    break
                toks, bid = step
                out.extend(toks[len(rem):])
                key = self._blocks[bid].index_key
                if key is None or key[0] != "full":
                    break
                digest, rem = key[1], ()
            if len(out) < k:
                # the longest partial tail extending what's left
                best: Tuple[int, ...] = ()
                for toks in self._partial_index.get(digest, {}):
                    if toks[:len(rem)] == rem and len(toks) > len(rem) \
                            and len(toks) > len(best):
                        best = toks
                if best:
                    out.extend(best[len(rem):])
        return out[:k]

    # ------------------------------------------------------------ commit

    def _plan_cow_locked(self, cow: List[int], match: PrefixMatch,
                         position: int) -> None:
        """The commit's one copy-on-write: the block at `position`
        widens the matched SHARED partial, which keeps its own pool row
        and stays indexed for future shorter matches."""
        cow[:] = (match.partial_bid, position, match.partial_len)
        self._stats["cow_copies"] += 1
        kvcache_metrics()["cow_copies"].inc()

    def _write_planned_locked(self, ck, cv, ids, cow) -> None:
        """Everything a commit planned, in place: `_commit_blocks` once
        for the keys' pool and once for the values' (one compiled
        program, two launches)."""
        cow = tuple(np.int32(x) for x in cow)
        if self.int8:
            self._pool_k, self._scale_k = _commit_blocks(
                (self._pool_k, self._scale_k), ck, ids, cow)
            self._pool_v, self._scale_v = _commit_blocks(
                (self._pool_v, self._scale_v), cv, ids, cow)
        else:
            (self._pool_k,) = _commit_blocks((self._pool_k,), ck, ids,
                                             cow)
            (self._pool_v,) = _commit_blocks((self._pool_v,), cv, ids,
                                             cow)
        self._dispatches += 2

    def note_prefilled(self, n_tokens: int) -> None:
        with self._lock:
            self._stats["prefilled_tokens"] += int(n_tokens)
        kvcache_metrics()["prefilled_tokens"].inc(int(n_tokens))

    def commit(self, tokens: np.ndarray, ck, cv,
               match: PrefixMatch,
               namespace: Optional[str] = None) -> List[int]:
        """Insert the prompt's uncached blocks from its freshly filled
        single-sequence cache ``ck/cv [L, S, H, hd]`` and return the
        request's pinned block table (matched + inserted). Stops quietly
        when the pool is exhausted — caching is best-effort, the slot's
        own slab copy is already correct. `namespace` must match the
        paired lookup()'s.

        Two steps under the lock. The PLAN is host bookkeeping alone:
        walk the blocks, reuse what is indexed, allocate (and evict) for
        what is not, index it, and note its pool row at its position in
        `ids`. The WRITE is `_commit_blocks` on each pool, whatever the
        number of blocks; a commit that planned nothing launches
        nothing."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        plen = len(tokens)
        n_full, tail = divmod(plen, bs)
        with self._lock:
            before = (self._dispatches, self._stats["inserted_blocks"])
            table = list(match.bids)
            digest = _ns_root(namespace)
            now = next(self._tick)
            parent: Optional[int] = None
            exhausted = False
            # position i: the pool row for the prompt's block i; rows
            # past the pool (each its own, the scatter's indices are
            # unique) are not written
            ids = self.num_blocks + np.arange(ck.shape[1] // bs,
                                              dtype=np.int32)
            cow = [0, 0, 0]
            for i in range(n_full):
                blk = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                nxt = _chain(digest, blk)
                if i < match.full_blocks:
                    parent, digest = match.bids[i], nxt
                    continue
                existing = self._full_index.get(nxt)
                if (existing is not None
                        and self._blocks[existing].tokens == blk):
                    b = self._blocks[existing]
                    b.ref += 1
                    b.last_used = now
                    table.append(existing)
                    parent, digest = existing, nxt
                    continue
                bid = self._alloc_locked()
                if bid is None:
                    exhausted = True
                    break
                if (i == match.full_blocks
                        and match.partial_bid is not None):
                    # the matched SHARED partial block sits at this
                    # position and this prompt widens it to a full
                    # block
                    self._plan_cow_locked(cow, match, i)
                ids[i] = bid
                self._insert_locked(bid, ("full", nxt), blk, bs, parent,
                                    now, namespace, digest)
                table.append(bid)
                parent, digest = bid, nxt
            if tail and not exhausted:
                self._plan_tail_locked(tokens, ids, cow, match, digest,
                                       parent, n_full, tail, table, now,
                                       namespace)
            if self._stats["inserted_blocks"] > before[1]:
                self._write_planned_locked(ck, cv, ids, cow)
            util = 1.0 - len(self._free) / self.num_blocks
            self.last_commit = (
                self._dispatches - before[0],
                self._stats["inserted_blocks"] - before[1])
        kvcache_metrics()["utilization"].set(util)
        return table

    def _plan_tail_locked(self, tokens, ids, cow, match, digest, parent,
                          n_full, tail, table, now,
                          namespace: Optional[str] = None) -> None:
        if n_full >= len(ids):
            # the tail block's nominal extent crosses the cache window
            # (block_size not dividing max_seq_len, prompt near max):
            # the fill has no whole block there — skip caching this
            # tail, correctness first
            return
        bs = self.block_size
        tail_toks = tuple(int(t) for t in tokens[n_full * bs:])
        # the matched partial is the TAIL's predecessor only when it sat
        # at the final block position (otherwise it was widened to a
        # full block by the loop above)
        tail_partial = (match.partial_bid
                        if match.full_blocks == n_full else None)
        if tail_partial is not None and match.partial_len == tail:
            return  # the matched partial already covers the whole tail
        by_tok = self._partial_index.get(digest, {})
        existing = by_tok.get(tail_toks)
        if existing is not None:
            b = self._blocks[existing]
            b.ref += 1
            b.last_used = now
            table.append(existing)
            return
        bid = self._alloc_locked()
        if bid is None:
            return
        if tail_partial is not None:
            # extending a SHARED cached block
            self._plan_cow_locked(cow, match, n_full)
        ids[n_full] = bid
        self._insert_locked(bid, ("partial", digest, tail_toks),
                            tail_toks, tail, parent, now, namespace,
                            digest)
        table.append(bid)

    def _insert_locked(self, bid: int, index_key: tuple,
                       blk_tokens: Tuple[int, ...], filled: int,
                       parent: Optional[int], now: int,
                       ns: Optional[str] = None,
                       parent_digest: Optional[bytes] = None) -> None:
        b = _Block(bid)
        b.tokens = blk_tokens
        b.filled = filled
        b.ref = 1  # the committing request's pin
        b.last_used = now
        b.index_key = index_key
        b.parent_bid = parent
        b.parent_digest = parent_digest
        b.ns = ns
        self._blocks[bid] = b
        if index_key[0] == "full":
            self._full_index[index_key[1]] = bid
            if parent_digest is not None:
                self._children.setdefault(parent_digest,
                                          {})[blk_tokens] = bid
        else:
            self._partial_index.setdefault(index_key[1],
                                           {})[index_key[2]] = bid
        if parent is not None and parent in self._blocks:
            self._blocks[parent].children += 1
        self._stats["inserted_blocks"] += 1

    # -------------------------------------------------- alloc / evict

    def _alloc_locked(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        victim: Optional[_Block] = None
        for b in self._blocks.values():
            # evictable: unpinned leaf (children of refcount-0 interiors
            # are themselves refcount-0, so leaves always drain first)
            if b.ref == 0 and b.children == 0 and b.index_key is not None:
                if victim is None or b.last_used < victim.last_used:
                    victim = b
        if victim is None:
            return None
        self._evict_locked(victim)
        return victim.bid

    def _evict_locked(self, b: _Block) -> None:
        # tier-2 spill BEFORE the index drop: the payload needs the
        # block's index identity, and accept() only ever touches host
        # memory (arena dict insert), so holding the lock is safe
        if self._arena is not None and b.index_key is not None:
            try:
                self._arena.accept(self._payload_locked(b))
            except Exception:  # noqa: BLE001 — spill is best-effort
                pass
        self._drop_index_locked(b)
        if b.parent_bid is not None and b.parent_bid in self._blocks:
            self._blocks[b.parent_bid].children -= 1
        del self._blocks[b.bid]
        self._stats["evictions"] += 1
        kvcache_metrics()["evictions"].inc()
        self._event_locked({"kind": "evict", "bid": b.bid,
                            "block_tokens": b.filled})

    def _drop_index_locked(self, b: _Block) -> None:
        key = b.index_key
        if key is None:
            return
        if key[0] == "full":
            self._full_index.pop(key[1], None)
            if b.parent_digest is not None:
                kids = self._children.get(b.parent_digest)
                if kids is not None:
                    kids.pop(b.tokens, None)
                    if not kids:
                        del self._children[b.parent_digest]
        else:
            by_tok = self._partial_index.get(key[1])
            if by_tok is not None:
                by_tok.pop(key[2], None)
                if not by_tok:
                    del self._partial_index[key[1]]
        b.index_key = None

    # ---------------------------------------------------- release / gc

    def release(self, table: List[int]) -> None:
        """Drop a finished request's pins. Refcount-0 blocks remain
        cached (LRU-evictable); orphans (invalidated while pinned) are
        freed outright."""
        with self._lock:
            for bid in table:
                b = self._blocks.get(bid)
                if b is None:
                    continue
                b.ref = max(0, b.ref - 1)
                if b.ref == 0 and b.index_key is None:
                    if b.parent_bid is not None \
                            and b.parent_bid in self._blocks:
                        self._blocks[b.parent_bid].children -= 1
                    del self._blocks[b.bid]
                    self._free.append(b.bid)
            util = 1.0 - len(self._free) / self.num_blocks
        kvcache_metrics()["utilization"].set(util)

    def invalidate(self, namespace: Optional[str] = ...) -> None:
        """Weight swap: every cached block's KV was computed under the
        OLD params — drop the whole index so no future lookup matches
        it. In-flight slots keep their pinned (now orphaned) blocks for
        refcount accounting only; they decode off their own slab.

        ``invalidate(namespace=tenant)`` scopes the flush to ONE cache
        namespace (a LoRA adapter hot-swap stales exactly that tenant's
        KV — every other tenant's blocks, and the base namespace, stay
        cached). A namespaced chain hangs off its own root digest, so
        the dropped blocks' parents are always in the same namespace
        and no surviving chain loses a reachable interior."""
        scoped = namespace is not ...
        with self._lock:
            for b in list(self._blocks.values()):
                if scoped and b.ns != namespace:
                    continue
                self._drop_index_locked(b)
                if b.ref == 0:
                    if scoped and b.parent_bid is not None \
                            and b.parent_bid in self._blocks:
                        self._blocks[b.parent_bid].children -= 1
                    del self._blocks[b.bid]
                    self._free.append(b.bid)
            if not scoped:
                for b in self._blocks.values():
                    b.children = 0
            self._stats["invalidations"] += 1
            ev: Dict[str, Any] = {"kind": "invalidate"}
            if scoped:
                ev["namespace"] = namespace
            self._event_locked(ev)
            util = 1.0 - len(self._free) / self.num_blocks
        kvcache_metrics()["utilization"].set(util)

    # -------------------------------------------------- stats / events

    def _event_locked(self, ev: Dict[str, Any]) -> None:
        ev.setdefault("ts", time.time())
        self._events.append(ev)
        if len(self._events) > _EVENTS_KEPT:
            del self._events[:len(self._events) - _EVENTS_KEPT]

    def record_event(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._event_locked(dict(ev))

    def drain_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._events = self._events, []
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            cached = sum(1 for b in self._blocks.values()
                         if b.index_key is not None)
            pinned = sum(1 for b in self._blocks.values() if b.ref > 0)
            s.update(
                enabled=True,
                block_size=self.block_size,
                num_blocks=self.num_blocks,
                free_blocks=len(self._free),
                cached_blocks=cached,
                pinned_blocks=pinned,
                pool_utilization=1.0 - len(self._free) / self.num_blocks,
                int8=self.int8,
                # bytes-per-block capacity factor vs the fp pool — the
                # "effective pool doubled" evidence every surface (and
                # the bench record) reports
                capacity_factor=2 if self.int8 else 1,
                pool_bytes=int(self._pool_k.nbytes + self._pool_v.nbytes
                               + ((self._scale_k.nbytes
                                   + self._scale_v.nbytes)
                                  if self.int8 else 0)),
            )
        looked = s["lookups"]
        s["hit_rate"] = ((s["hits"] + s["partial_hits"]) / looked
                         if looked else 0.0)
        seen = s["reused_tokens"] + s["prefilled_tokens"]
        s["token_reuse_rate"] = s["reused_tokens"] / seen if seen else 0.0
        return s
