"""Paged KV cache with prefix reuse (ISSUE-6 acceptance surface):
block-pool allocator semantics (refcounts, COW, LRU eviction), engine
bit-identity cached-vs-uncached (incl. weight swap invalidation),
prefill-work proportionality to the hit rate, and the one-set-of-numbers
consistency check across state API / CLI / dashboard / Prometheus /
timeline.

The `kvcache` marker tags the scenarios; everything here is tier-1-safe
on CPU — the e2e surface check runs on a virtual cluster with
log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.kvcache import PagedKVCache
from ray_tpu.models.llama import LlamaConfig, llama_init

pytestmark = pytest.mark.kvcache

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4  # test block size: small enough to exercise chains + tails


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


def _engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("kv_pool_blocks", 32)
    return ContinuousBatchingEngine(model, CFG, **kw)


def _reference(model, prompt, n):
    return np.asarray(generate(model, CFG, jnp.asarray([prompt],
                                                       jnp.int32),
                               max_new_tokens=n))[0].tolist()


def _fake_kv(seed: int) -> tuple:
    """A deterministic single-sequence cache fill [L, S, H, hd] for
    allocator-level tests (the allocator never inspects KV values)."""
    rng = np.random.default_rng(seed)
    shape = (CFG.num_layers, CFG.max_seq_len, CFG.num_kv_heads,
             CFG.head_dim)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32))


# ------------------------------------------------------- allocator unit

def test_allocator_refcount_sharing_and_gather():
    pool = PagedKVCache(CFG, block_size=BS, num_blocks=8)
    tokens = np.arange(1, 9, dtype=np.int32)          # 2 full blocks
    ck, cv = _fake_kv(0)
    miss = pool.lookup(tokens, max_tokens=7)
    assert miss.outcome == "miss" and miss.tokens == 0
    table = pool.commit(tokens, ck, cv, miss)
    assert len(table) == 2
    st = pool.stats()
    assert st["inserted_blocks"] == 2 and st["pinned_blocks"] == 2

    # a second identical prompt shares block 0 (block 1 ends at token 8
    # > max_tokens=7, so the suffix stays prefillable)
    m2 = pool.lookup(tokens, max_tokens=7)
    assert m2.tokens == BS and m2.outcome == "hit"
    pk, pv = pool.gather(m2)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(ck)[:, :BS])
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(cv)[:, :BS])

    pool.release(table)
    pool.release(m2.bids)
    st = pool.stats()
    # releases drop pins, NOT cache entries
    assert st["pinned_blocks"] == 0 and st["cached_blocks"] == 2
    assert pool.lookup(tokens, max_tokens=7).tokens == BS


def test_allocator_eviction_spares_referenced_blocks():
    pool = PagedKVCache(CFG, block_size=BS, num_blocks=2)
    ck, cv = _fake_kv(1)
    tok_a = np.arange(10, 14, dtype=np.int32)
    tok_b = np.arange(20, 24, dtype=np.int32)
    tok_c = np.arange(30, 34, dtype=np.int32)
    table_a = pool.commit(tok_a, ck, cv, pool.lookup(tok_a, 3))
    table_b = pool.commit(tok_b, ck, cv, pool.lookup(tok_b, 3))
    assert len(table_a) == len(table_b) == 1
    pool.release(table_b)  # B unpinned; A stays pinned

    table_c = pool.commit(tok_c, ck, cv, pool.lookup(tok_c, 3))
    assert len(table_c) == 1        # allocated by evicting B (LRU ref-0)
    st = pool.stats()
    assert st["evictions"] == 1
    # the pinned block was never reclaimed; the unpinned one was
    assert pool.lookup(np.concatenate([tok_a, tok_a]), 7).tokens == BS
    assert pool.lookup(np.concatenate([tok_b, tok_b]), 7).tokens == 0

    # pool exhausted with everything pinned: commit degrades to no-op
    tok_d = np.arange(40, 44, dtype=np.int32)
    table_d = pool.commit(tok_d, ck, cv, pool.lookup(tok_d, 3))
    assert table_d == [] and pool.stats()["evictions"] == 1


def test_allocator_cow_divergence_after_shared_prefix():
    pool = PagedKVCache(CFG, block_size=BS, num_blocks=8)
    base = np.arange(1, 7, dtype=np.int32)             # 6: full + tail 2
    ck_a, cv_a = _fake_kv(2)
    table_a = pool.commit(base, ck_a, cv_a, pool.lookup(base, 5))
    assert len(table_a) == 2                           # b0 full, b1 tail
    assert pool.stats()["cow_copies"] == 0

    # B shares the 6-token prefix then diverges; its fill agrees with
    # A's on the shared region (bit-identity invariant of prefill)
    ext = np.concatenate([base, np.arange(50, 54, dtype=np.int32)])
    ck_b = jnp.asarray(np.where(
        (np.arange(CFG.max_seq_len) < 6)[None, :, None, None],
        np.asarray(ck_a), np.asarray(_fake_kv(3)[0])), jnp.float32)
    cv_b = ck_b + 1.0
    m_b = pool.lookup(ext, max_tokens=9)
    assert m_b.tokens == 6 and m_b.partial_bid is not None
    table_b = pool.commit(ext, ck_b, cv_b, m_b)
    st = pool.stats()
    # the shared partial was widened via copy-on-write, not mutated
    assert st["cow_copies"] == 1
    # ...so A's 6-token prefix entry still matches for a third prompt
    third = np.concatenate([base, np.arange(70, 74, dtype=np.int32)])
    assert pool.lookup(third, max_tokens=9).tokens == 6
    # and B's widened chain serves B-shaped prompts with B's contents
    m_b2 = pool.lookup(ext, max_tokens=8)
    assert m_b2.tokens == 8
    pk, _pv = pool.gather(m_b2)
    np.testing.assert_array_equal(np.asarray(pk),
                                  np.asarray(ck_b)[:, :8])


def test_allocator_skips_tail_crossing_cache_window():
    """block_size not dividing max_seq_len: a tail block whose nominal
    extent crosses the cache window must not be cached (dynamic_slice
    would clamp the start and store shifted rows)."""
    pool = PagedKVCache(CFG, block_size=24, num_blocks=8)   # S=128
    tokens = np.arange(1, 123, dtype=np.int32)   # 5 full blocks + 2
    ck, cv = _fake_kv(4)
    table = pool.commit(tokens, ck, cv, pool.lookup(tokens, 121))
    assert len(table) == 5                       # tail (extent 144) skipped
    m = pool.lookup(tokens, max_tokens=121)
    assert m.tokens == 120 and m.partial_bid is None
    pool.release(table)
    pool.release(m.bids)


# ------------------------- one program a commit vs a block at a time

def _np_quantize(block):
    """[L, bs, W] float32 -> (int8, float32 scales [L, 1, W]): the int8
    pool's per-block-channel quantization, in numpy."""
    f = np.asarray(block, np.float32)
    amax = np.max(np.abs(f), axis=1, keepdims=True)
    scale = np.where(amax > 0, amax / np.float32(127.0),
                     np.float32(1.0)).astype(np.float32)
    return np.clip(np.round(f / scale), -127, 127).astype(np.int8), scale


class _BlockAtATime:
    """A plain reference for ``PagedKVCache.commit``: the same walk over
    the prompt's blocks, each new block cut out of the fill and written
    to its pool row on its own (a copy-on-write first takes the shared
    row's leading token rows), in numpy. It shares the pool's allocator
    and index, and holds the device arrays' content itself."""

    def __init__(self, pool):
        self.pool = pool
        self.k = np.zeros(pool._pool_k.shape, np.float32)
        self.v = np.zeros(pool._pool_v.shape, np.float32)
        self.sk = self.sv = None
        if pool.int8:
            self.sk = np.zeros(pool._scale_k.shape, np.float32)
            self.sv = np.zeros(pool._scale_v.shape, np.float32)

    def _write(self, data, scales, bid, fill, start, cow):
        bs = self.pool.block_size
        block = np.asarray(fill, np.float32)[:, start:start + bs]
        block = block.reshape(block.shape[:2] + (-1,))
        if cow is not None:
            src, kept = cow
            old = data[:, src] * (scales[:, src] if self.pool.int8
                                  else np.float32(1.0))
            block = np.where(np.arange(bs)[None, :, None] < kept, old,
                             block)
        if self.pool.int8:
            data[:, bid], scales[:, bid] = _np_quantize(block)
        else:
            data[:, bid] = block

    def _new_block(self, bid, ck, cv, start, cow=None):
        p = self.pool
        if cow is not None:
            p._stats["cow_copies"] += 1
        self._write(self.k, self.sk, bid, ck, start, cow)
        self._write(self.v, self.sv, bid, cv, start, cow)

    def commit(self, tokens, ck, cv, match):
        from ray_tpu.models.kvcache import _chain, _ns_root
        p, bs = self.pool, self.pool.block_size
        tokens = np.asarray(tokens).reshape(-1)
        n_full, tail = divmod(len(tokens), bs)
        inserted = p._stats["inserted_blocks"]
        table, digest, parent = list(match.bids), _ns_root(None), None
        now = next(p._tick)
        for i in range(n_full):
            blk = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            nxt = _chain(digest, blk)
            if i < match.full_blocks:
                parent, digest = match.bids[i], nxt
                continue
            bid = p._full_index.get(nxt)
            if bid is not None:
                p._blocks[bid].ref += 1
                p._blocks[bid].last_used = now
            else:
                bid = p._alloc_locked()
                if bid is None:
                    return table, p._stats["inserted_blocks"] - inserted
                widened = (i == match.full_blocks
                           and match.partial_bid is not None)
                self._new_block(bid, ck, cv, i * bs,
                                (match.partial_bid, match.partial_len)
                                if widened else None)
                p._insert_locked(bid, ("full", nxt), blk, bs, parent,
                                 now, None, digest)
            table.append(bid)
            parent, digest = bid, nxt
        partial = (match.partial_bid if match.full_blocks == n_full
                   else None)
        if (tail and (n_full + 1) * bs <= np.shape(ck)[1]
                and not (partial is not None
                         and match.partial_len == tail)):
            toks = tuple(int(t) for t in tokens[n_full * bs:])
            bid = p._partial_index.get(digest, {}).get(toks)
            if bid is not None:
                p._blocks[bid].ref += 1
                p._blocks[bid].last_used = now
                table.append(bid)
            else:
                bid = p._alloc_locked()
                if bid is not None:
                    self._new_block(bid, ck, cv, n_full * bs,
                                    (partial, match.partial_len)
                                    if partial is not None else None)
                    p._insert_locked(bid, ("partial", digest, toks),
                                     toks, tail, parent, now, None,
                                     digest)
                    table.append(bid)
        return table, p._stats["inserted_blocks"] - inserted

    def block(self, bid):
        """(k, v) of one pool row as ``gather`` hands them back."""
        p = self.pool
        out = []
        for data, scales in ((self.k, self.sk), (self.v, self.sv)):
            x = data[:, bid]
            if p.int8:
                x = (x * scales[:, bid]).astype(p.dtype)
            out.append(x.reshape(x.shape[:2] + p._heads))
        return out


def _tok(start, n):
    return np.arange(start, start + n, dtype=np.int32)


# name -> (block size, pool blocks, steps); a step is (tokens, the most
# a lookup may match, the fill's seed, release the table afterwards)
_BASE6 = _tok(1, 6)
_COMMIT_CASES = {
    "miss": (BS, 8, [(_tok(1, 8), 7, 10, False)]),
    "full_hit_extended": (BS, 8, [
        (_tok(1, 8), 7, 11, False),
        (np.concatenate([_tok(1, 8), _tok(40, 9)]), 16, 12, False)]),
    "shared_partial_widened_to_a_full_block": (BS, 8, [
        (_BASE6, 5, 13, False),
        (np.concatenate([_BASE6, _tok(50, 4)]), 9, 14, False)]),
    "shared_partial_widened_in_the_tail": (BS, 8, [
        (_BASE6, 5, 15, False),
        (np.concatenate([_BASE6, _tok(60, 1)]), 6, 16, False)]),
    "partial_tail": (BS, 8, [(_tok(1, 7), 6, 17, False),
                             (_tok(1, 7), 6, 18, False)]),
    "tail_crosses_the_window": (24, 8, [(_tok(1, 122), 121, 19, False)]),
    "pool_exhausted_mid_commit": (BS, 3, [(_tok(1, 18), 17, 20, False)]),
    "window_not_divisible_by_the_block": (24, 8, [
        (_tok(1, 50), 49, 21, False),
        (np.concatenate([_tok(1, 48), _tok(90, 30)]), 77, 22, False)]),
    "evicts_the_least_recently_used": (BS, 4, [
        (_tok(1, 8), 7, 23, True), (_tok(20, 8), 7, 24, True),
        (_tok(40, 10), 9, 25, False)]),
}


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("case", sorted(_COMMIT_CASES))
def test_commit_in_one_program_equals_a_block_at_a_time(case, int8):
    """What `commit` leaves in the pool, returns and counts, against the
    reference that writes one block at a time: two pools go through the
    same steps, one committed by the program under test, the other by
    `_BlockAtATime`."""
    bs, blocks, steps = _COMMIT_CASES[case]
    pool = PagedKVCache(CFG, block_size=bs, num_blocks=blocks, int8=int8)
    twin = PagedKVCache(CFG, block_size=bs, num_blocks=blocks, int8=int8)
    ref = _BlockAtATime(twin)
    for tokens, most, seed, release in steps:
        ck, cv = _fake_kv(seed)
        match, match_ref = (p.lookup(tokens, most) for p in (pool, twin))
        assert (match.bids, match.tokens, match.partial_bid) == (
            match_ref.bids, match_ref.tokens, match_ref.partial_bid)
        before = pool.stats()["inserted_blocks"]
        table = pool.commit(tokens, ck, cv, match)
        table_ref, inserted_ref = ref.commit(tokens, ck, cv, match_ref)
        assert table == table_ref
        assert pool.last_commit[1] == inserted_ref \
            == pool.stats()["inserted_blocks"] - before
        # a commit is its one program on each of the two pools, or none
        assert pool.last_commit[0] == (2 if inserted_ref else 0)
        assert pool.stats() == twin.stats()
        for bid in range(blocks):
            one = type(match)([bid], bs, 1, None, 0, "hit")
            got_k, got_v = pool.gather(one)
            want_k, want_v = ref.block(bid)
            # the float pool holds the fill's bits; a scale computed by
            # XLA and by numpy may differ in its last place
            tol = dict(rtol=1e-6, atol=1e-6) if int8 else dict(rtol=0)
            np.testing.assert_allclose(np.asarray(got_k), want_k, **tol)
            np.testing.assert_allclose(np.asarray(got_v), want_v, **tol)
        if release:
            pool.release(table)
            twin.release(table_ref)
    if case == "pool_exhausted_mid_commit":
        assert len(table) == 3 and pool.stats()["free_blocks"] == 0
    if case == "tail_crosses_the_window":
        assert len(table) == 5
    if case.startswith("shared_partial"):
        assert pool.stats()["cow_copies"] == 1
    if case == "evicts_the_least_recently_used":
        assert pool.stats()["evictions"] == 3


# ------------------------------------------------ engine bit-identity

def test_cached_engine_bit_identical_to_uncached(model):
    cached = _engine(model)
    uncached = _engine(model, prefix_cache=False)
    base = [1, 2, 3, 4, 5, 6, 7, 8]                   # block-aligned
    prompts = [base, base, base + [9, 10, 11],
               base[:6] + [7, 7], [5, 5, 5]]
    try:
        for p in prompts:
            got = cached.generate(p, 6)
            assert got == uncached.generate(p, 6), p
            assert got == _reference(model, p, 6), p
        st = cached.kv_stats()
        assert st["hits"] >= 1 and st["reused_tokens"] > 0
        assert uncached.kv_stats()["enabled"] is False
    finally:
        cached.stop()
        uncached.stop()


def test_weight_swap_invalidates_prefix_cache(model):
    params_b = jax.tree.map(lambda x: x * 1.25, model)
    eng = _engine(model)
    fresh_b = _engine(params_b, prefix_cache=False)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    try:
        eng.generate(prompt, 4)                   # caches prefix under A
        applied = eng.update_params(params_b, version=2)
        assert applied.wait(timeout=30.0)
        # same prompt post-swap: a stale-prefix match would serve
        # params-A KV and diverge from the uncached params-B engine
        assert eng.generate(prompt, 4) == fresh_b.generate(prompt, 4)
        st = eng.kv_stats()
        assert st["invalidations"] == 1
    finally:
        eng.stop()
        fresh_b.stop()


# ------------------------------------- prefill-work proportionality

def test_prefix_reuse_drops_prefill_work_without_full_copy(model):
    progs_before = engine_mod._prefill_paged._cache_size()
    eng = _engine(model)
    shared = [11, 12, 13, 14, 15, 16, 17, 18]         # 2 aligned blocks
    prompts = [shared + [30 + i] for i in range(4)]
    try:
        for p in prompts:
            assert eng.generate(p, 3) == _reference(model, p, 3)
        st = eng.kv_stats()
    finally:
        eng.stop()
    # request 1 prefills all 9 tokens; 2..4 only the 1-token suffix
    assert st["misses"] == 1 and st["hits"] == 3
    assert st["prefilled_tokens"] == 9 + 3 * 1
    assert st["reused_tokens"] == 3 * 8
    # splice writes O(prompt) rows per admission — the old _adopt_slot
    # full-slab copy (max_batch x max_seq_len) is gone entirely
    assert st["spliced_tokens"] == 4 * 9
    assert not hasattr(engine_mod, "_adopt_slot")
    # one compiled program per distinct (cached, suffix) shape: the
    # 9-token miss prefill + the 1-on-8 suffix prefill
    progs_after = engine_mod._prefill_paged._cache_size()
    assert progs_after - progs_before <= 2


def test_pool_exhaustion_falls_back_to_full_prefill(model):
    eng = _engine(model, kv_pool_blocks=2)
    try:
        for i in range(5):
            p = [60 + 10 * i + j for j in range(8)]   # all-distinct
            assert eng.generate(p, 3) == _reference(model, p, 3), p
        st = eng.kv_stats()
        assert st["pinned_blocks"] == 0               # all released
        assert st["num_blocks"] == 2
    finally:
        eng.stop()


# -------------------------------------------------- admission cap

def test_admission_cap_bounds_prefill_bursts(model, monkeypatch):
    import concurrent.futures as cf

    eng = _engine(model)
    try:
        assert eng.max_prefills_per_tick == 1         # default
        prompts = [[i + 1, i + 2] for i in range(6)]
        with cf.ThreadPoolExecutor(6) as pool:
            futs = [pool.submit(eng.generate, p, 4) for p in prompts]
            got = [f.result(timeout=120) for f in futs]
        for p, g in zip(prompts, got):
            assert g == _reference(model, p, 4), p
        assert eng.max_prefills_admitted_per_tick <= 1
        assert eng.adopted == 0                       # colocated path
    finally:
        eng.stop()
    monkeypatch.setenv("RAY_TPU_MAX_PREFILLS_PER_TICK", "3")
    eng = _engine(model)
    try:
        assert eng.max_prefills_per_tick == 3
    finally:
        eng.stop()


# ------------------------------------------------ serve TTFT label

def test_stream_exposes_cache_outcome_for_ttft_label(model):
    eng = _engine(model)
    try:
        p = [41, 42, 43, 44, 45, 46, 47, 48]
        s1 = eng.stream(p, 3)
        assert list(s1) and s1.cache_outcome == "miss"
        s2 = eng.stream(p, 3)
        assert list(s2) and s2.cache_outcome == "hit"
        # plen-1 cap: the second block ends exactly at the prompt end,
        # so one block (4 tokens) is reusable and the suffix prefills
        assert s2.reused_tokens == 4
    finally:
        eng.stop()
    from ray_tpu.serve.replica import _replica_metrics

    assert "cache" in _replica_metrics()["ttft"]._tag_keys


# ----------------------------------------------- e2e surface check

@pytest.fixture
def kvcache_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def test_all_surfaces_report_consistent_numbers(kvcache_cluster, capsys):
    """kv_cache_stats() / CLI / /api/kvcache / Prometheus / timeline
    markers all report the SAME hit/miss/eviction numbers for one
    engine's workload."""
    import urllib.request

    from ray_tpu.dashboard import DashboardServer
    from ray_tpu.scripts import cli
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    w = kvcache_cluster
    model = llama_init(CFG, jax.random.PRNGKey(0))
    eng = _engine(model)
    try:
        shared = [21, 22, 23, 24, 25, 26, 27, 28]
        for i in range(3):
            eng.generate(shared + [90 + i], 3)
        eng.publish_kv_telemetry(force=True)
        local = eng.kv_stats()
    finally:
        eng.stop()
    metrics_mod.flush()

    # state API (the stats push is a fire-and-forget notify: poll until
    # the FINAL snapshot — lookups settled — lands at the conductor)
    import time as time_mod

    key = f"{w.worker_id[:12]}:{eng.engine_id}"
    deadline = time_mod.monotonic() + 10.0
    while True:
        st = state.kv_cache_stats()
        mine = st["engines"].get(key)
        if mine is not None and mine.get("lookups") == local["lookups"]:
            break
        assert time_mod.monotonic() < deadline, st
        time_mod.sleep(0.1)
    for key in ("lookups", "hits", "partial_hits", "misses",
                "reused_tokens", "prefilled_tokens", "evictions"):
        assert mine[key] == local[key], key
    assert st["totals"]["hits"] == local["hits"]

    # CLI (same conductor snapshot)
    host, port = w.conductor_address
    cli.main(["kvcache", "--json", "--address", f"{host}:{port}"])
    cli_out = json.loads(capsys.readouterr().out)
    assert cli_out["totals"]["hits"] == local["hits"]
    assert cli_out["totals"]["misses"] == local["misses"]

    # dashboard /api/kvcache
    srv = DashboardServer(w.conductor_address, port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/api/kvcache",
                                    timeout=10.0) as r:
            dash = json.loads(r.read())
    finally:
        srv.stop()
    assert dash["totals"]["hits"] == local["hits"]
    assert dash["totals"]["reused_tokens"] == local["reused_tokens"]
    hit_events = [e for e in dash["events"]
                  if e.get("kind") == "prefix_hit"
                  and e.get("engine") == eng.engine_id]
    assert len(hit_events) == local["hits"] + local["partial_hits"]

    # Prometheus exposition: the kvcache families exist and the
    # process-global counters cover at least this engine's work
    prom = state.prometheus_metrics()
    assert "ray_tpu_kvcache_lookups_total" in prom
    assert "ray_tpu_kvcache_pool_utilization" in prom
    lookup_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_kvcache_lookups_total{"))
    assert lookup_total >= local["lookups"]

    # merged timeline: one instant marker per prefix hit
    trace = state.timeline(merged=True)
    markers = [e for e in trace if e.get("cat") == "kvcache"
               and e.get("args", {}).get("engine") == eng.engine_id
               and e.get("tid") == "prefix_hit"]
    assert len(markers) == local["hits"] + local["partial_hits"]
    assert all(m["ph"] == "i" and m["pid"] == "kvcache" for m in markers)
