"""Global KV plane (ISSUE-19 acceptance surface): the tiered prefix
cache (HBM -> host RAM -> object store) with the cluster-wide prefix
directory (serve/kvplane.py + models/kvcache.py tier hooks +
conductor-side directory).

Covered here: HostArena spill/pop semantics (LRU byte bound, exact-token
collision guard, longest-partial probe, per-request attribution), the
pool-level tier-2 round trip (int8 pools byte-identical, fp pools within
the int8 tolerance contract), tier-3 export/import bit-identity across
pools, namespace isolation across every tier, the conductor directory's
atomic commit / TTL reap / keep-last-K GC, router directory routing
(hit -> holder, holder death -> hash + tier-3 hint, miss -> hash
bit-identically), the evict_storm chaos op absorbed by the arena with
outputs unchanged, the speculation-aware autoscaler discount (never
over-scales, bit-identical without a signal), per-caller chunk-fabric
attribution, and the one-set-of-numbers check across state API == CLI
== dashboard == Prometheus == timeline.

The `kvplane` marker tags the scenarios; everything is tier-1-safe on
CPU — cluster tests run on a module-scoped cluster with
log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.disagg import DecodeServer, DisaggRouter, PrefillServer
from ray_tpu.serve.kvplane import HostArena

pytestmark = pytest.mark.kvplane

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4  # KV block size: small enough to spill/readopt multiple blocks


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def kvplane_cluster():
    ray_tpu.init(num_cpus=6, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


# ------------------------------------------------- HostArena (tier 2)

def _fake_payload(digest: bytes, toks, *, ns=None, partial=False,
                  parent: bytes = b"parent", seed: int = 0):
    """A wire-format payload shaped like _payload_locked's output —
    int8 K/V plus f32 scales — keyed the way the pool keys it."""
    rng = np.random.default_rng(seed)
    n = len(toks)
    qk = rng.integers(-127, 127, (2, n, 2, 4)).astype(np.int8)
    qv = rng.integers(-127, 127, (2, n, 2, 4)).astype(np.int8)
    sk = rng.random((2, 1, 2, 4)).astype(np.float32)
    sv = rng.random((2, 1, 2, 4)).astype(np.float32)
    key = ("partial", parent, tuple(toks)) if partial \
        else ("full", digest)
    return {"index_key": key, "tokens": tuple(toks), "filled": n,
            "ns": ns, "parent_digest": parent,
            "qk": qk, "qv": qv, "sk": sk, "sv": sv}


def test_arena_roundtrip_pops_bit_identical_with_collision_guard():
    """accept -> take_full returns the exact arrays (and POPS — a hit
    moves the block back to tier 1, never double residency); a digest
    probe whose token tuple differs returns None and leaves the entry."""
    arena = HostArena(max_bytes=1 << 20, replica="unit")
    p = _fake_payload(b"d1", (1, 2, 3, 4))
    arena.accept(dict(p))
    # digest collision with different tokens must never re-adopt
    assert arena.take_full(b"d1", (9, 9, 9, 9)) is None
    got = arena.take_full(b"d1", (1, 2, 3, 4))
    assert got is not None
    for f in ("qk", "qv", "sk", "sv"):
        assert np.array_equal(got[f], p[f])
    assert got["tokens"] == p["tokens"]
    # POP semantics: the hit consumed the entry
    assert arena.take_full(b"d1", (1, 2, 3, 4)) is None
    st = arena.stats()
    assert st["spills"] == 1
    assert st["tier2_hits"] == 1
    assert st["tier2_probes"] == 3
    assert st["tier2_reused_tokens"] == 4
    assert st["entries"] == 0 and st["bytes"] == 0
    kinds = [e["kind"] for e in arena.drain_events()]
    assert kinds == ["spill", "tier2_hit"]


def test_arena_lru_byte_bound_and_oversize_reject():
    one = _fake_payload(b"a", (1, 2, 3, 4))
    size = sum(int(one[f].nbytes) for f in ("qk", "qv", "sk", "sv"))
    arena = HostArena(max_bytes=2 * size, replica="unit")
    arena.accept(_fake_payload(b"a", (1, 2, 3, 4)))
    arena.accept(_fake_payload(b"b", (5, 6, 7, 8)))
    arena.accept(_fake_payload(b"c", (9, 10, 11, 12)))  # evicts "a"
    st = arena.stats()
    assert st["arena_evictions"] == 1 and st["entries"] == 2
    assert st["bytes"] == 2 * size
    assert arena.take_full(b"a", (1, 2, 3, 4)) is None
    assert arena.take_full(b"b", (5, 6, 7, 8)) is not None
    # a payload bigger than the whole arena is refused outright
    tiny = HostArena(max_bytes=size - 1, replica="unit")
    tiny.accept(_fake_payload(b"x", (1, 2, 3, 4)))
    assert tiny.stats()["spills"] == 0
    assert tiny.stats()["entries"] == 0


def test_arena_partial_probe_longest_match_within_budget():
    arena = HostArena(max_bytes=1 << 20, replica="unit")
    arena.accept(_fake_payload(b"root", (7, 8), partial=True,
                               parent=b"root"))
    arena.accept(_fake_payload(b"root", (7, 8, 9), partial=True,
                               parent=b"root"))
    # longest prefix-matching tail within the token budget wins
    got = arena.take_partial(b"root", [7, 8, 9, 10], budget=3)
    assert got is not None and got["tokens"] == (7, 8, 9)
    # budget now excludes 3-token tails; the 2-token tail still matches
    got2 = arena.take_partial(b"root", [7, 8, 9, 10], budget=2)
    assert got2 is not None and got2["tokens"] == (7, 8)
    # tails that do not prefix-match the remainder never match
    arena.accept(_fake_payload(b"root", (7, 9), partial=True,
                               parent=b"root"))
    assert arena.take_partial(b"root", [7, 8], budget=4) is None


def test_arena_give_back_and_request_attribution():
    arena = HostArena(max_bytes=1 << 20, replica="unit")
    p = _fake_payload(b"d", (1, 2, 3, 4))
    size = sum(int(p[f].nbytes) for f in ("qk", "qv", "sk", "sv"))
    arena.accept(dict(p))
    arena.begin_request()
    got = arena.take_full(b"d", (1, 2, 3, 4))
    assert got is not None
    acc = arena.end_request()
    assert acc["blocks"] == 1 and acc["tokens"] == 4
    assert acc["nbytes"] == size and acc["ms"] >= 0.0
    # the accumulator resets with the bracket
    assert arena.end_request()["blocks"] == 0
    # give_back restores a failed re-adoption without counting a spill
    spills_before = arena.stats()["spills"]
    arena.give_back(got)
    st = arena.stats()
    assert st["spills"] == spills_before
    assert st["entries"] == 1 and st["bytes"] == size
    assert arena.take_full(b"d", (1, 2, 3, 4)) is not None


# -------------------------------------- pool-level tier-2 round trip

def _filled_pool(model, prompt: np.ndarray, *, int8: bool,
                 num_blocks: int = 16, arena_bytes: int = 64 << 20):
    """A PagedKVCache with `prompt` committed and an arena attached —
    the unit-scale stand-in for a prefill replica's tier-1 + tier-2."""
    from ray_tpu.models.engine import _prefill_paged
    from ray_tpu.models.kvcache import PagedKVCache

    empty = jnp.zeros((CFG.num_layers, 0, CFG.num_kv_heads,
                       CFG.head_dim), jnp.float32)
    _, ck, cv, _, _ = _prefill_paged(model, prompt[None], CFG, empty, empty)
    kv = PagedKVCache(CFG, block_size=BS, num_blocks=num_blocks,
                      int8=int8)
    arena = HostArena(max_bytes=arena_bytes, replica="unit")
    kv.attach_arena(arena)
    m = kv.lookup(prompt, max_tokens=len(prompt) - 1)
    kv.release(kv.commit(prompt, ck, cv, m))
    return kv, arena, ck, cv


def test_pool_spill_readopt_bit_identical_int8(model):
    """The tier-2 correctness invariant at the pool level: evict a
    whole committed chain into the arena, walk the lookup back through
    it, and the re-exported wire bytes (int8 K/V + scales + digest) are
    EXACTLY what was there before the eviction."""
    prompt = np.arange(101, 117, dtype=np.int32)  # 4 full blocks
    kv, arena, _, _ = _filled_pool(model, prompt, int8=True)
    before = kv.export_prefix(prompt)
    assert before is not None and before[1] == 16
    evicted = kv.force_evict(100)
    assert evicted == 4
    # the chain is GONE from tier 1...
    assert kv.export_prefix(prompt) is None
    st = arena.stats()
    assert st["spills"] == 4 and st["entries"] == 4
    # ...and the lookup re-adopts every block from tier 2
    m = kv.lookup(prompt, max_tokens=16)
    assert m.outcome == "hit" and m.tokens == 16
    kv.release(m.bids)
    after = kv.export_prefix(prompt)
    assert after is not None and after[1] == 16
    packed_b, _, dig_b = before
    packed_a, _, dig_a = after
    assert dig_a == dig_b
    for f in ("qk", "qv", "sk", "sv", "tokens"):
        assert np.array_equal(packed_a[f], packed_b[f]), f
    st = arena.stats()
    assert st["tier2_hits"] == 4
    assert st["tier2_reused_tokens"] == 16
    assert st["entries"] == 0  # POPPED back to tier 1


def test_pool_spill_readopt_fp_within_tolerance(model):
    """fp pools quantize on spill and re-enter within the int8
    tolerance contract — the readopted chain still serves the lookup
    and its dequantized rows stay close to the exact fill."""
    prompt = np.arange(201, 213, dtype=np.int32)  # 3 full blocks
    kv, arena, ck, _ = _filled_pool(model, prompt, int8=False)
    assert kv.force_evict(100) == 3
    assert arena.stats()["spills"] == 3
    m = kv.lookup(prompt, max_tokens=12)
    assert m.outcome == "hit" and m.tokens == 12
    gk, _ = kv.gather(m)
    ref = np.asarray(ck[:, :12], np.float32)
    got = np.asarray(gk, np.float32)
    assert np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9) < 0.05
    kv.release(m.bids)


def test_tier3_export_import_bit_identical_across_pools(model):
    """Tier 3's packed wire format survives a pool-to-pool hop
    byte-for-byte on int8 pools: export from A, adopt into a fresh B,
    re-export from B — identical arrays, identical chain digest. A
    prompt that does not match the packed tokens adopts NOTHING (a
    directory collision must never seed wrong KV)."""
    from ray_tpu.models.kvcache import PagedKVCache

    prompt = np.arange(301, 313, dtype=np.int32)  # 3 full blocks
    kv_a, _, _, _ = _filled_pool(model, prompt, int8=True)
    out = kv_a.export_prefix(prompt)
    assert out is not None
    packed, n_tokens, digest_hex = out
    assert n_tokens == 12 and packed["qk"].shape[0] == 3
    kv_b = PagedKVCache(CFG, block_size=BS, num_blocks=16, int8=True)
    assert kv_b.import_prefix(prompt, packed) == 3
    out_b = kv_b.export_prefix(prompt)
    assert out_b is not None
    packed_b, n_b, dig_b = out_b
    assert n_b == 12 and dig_b == digest_hex
    for f in ("qk", "qv", "sk", "sv", "tokens"):
        assert np.array_equal(packed_b[f], packed[f]), f
    # adopting the prefix makes the next prefill lookup a hit
    m = kv_b.lookup(prompt, max_tokens=11)
    assert m.tokens == 8 and m.outcome == "hit"
    kv_b.release(m.bids)
    # token-verification guard: wrong prompt adopts nothing
    kv_c = PagedKVCache(CFG, block_size=BS, num_blocks=16, int8=True)
    other = np.arange(401, 413, dtype=np.int32)
    assert kv_c.import_prefix(other, packed) == 0


def test_namespace_isolation_across_tiers(model):
    """Digest chains are namespace-rooted, so isolation is inherited by
    every tier: blocks spilled under one namespace can never serve
    another namespace's lookup, and export under a foreign namespace
    finds nothing."""
    from ray_tpu.models.kvcache import prefix_digests

    prompt = np.arange(501, 517, dtype=np.int32)
    kv, arena, _, _ = _filled_pool(model, prompt, int8=True)
    # the chains themselves differ at the root
    assert prefix_digests(prompt, BS, None) \
        != prefix_digests(prompt, BS, "tenantA|v1")
    assert kv.export_prefix(prompt, namespace="tenantA|v1") is None
    kv.force_evict(100)
    # foreign-namespace lookup misses tier 2 entirely...
    m_other = kv.lookup(prompt, max_tokens=16, namespace="tenantA|v1")
    assert m_other.tokens == 0 and m_other.outcome == "miss"
    assert arena.stats()["tier2_hits"] == 0
    # ...while the owning namespace re-adopts the full chain
    m_same = kv.lookup(prompt, max_tokens=16)
    assert m_same.tokens == 16
    kv.release(m_same.bids)


# ------------------------------------ e2e spill/readopt bit-identity

def test_outputs_bit_identical_under_pool_pressure(model):
    """The headline invariant: a prefill tier whose pool is too small
    for the working set (evictions -> arena spills -> readopts) serves
    outputs BIT-IDENTICAL to a single-tier engine whose pool holds
    everything. int8 pools make the tier-2 round trip lossless, so the
    hit/miss pattern — and therefore every output — matches."""
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=8,
                       kv_int8=True, kvplane=True,
                       kvplane_arena_bytes=64 << 20)
    dec = DecodeServer(model, CFG, max_batch=2)
    colo = ContinuousBatchingEngine(model, CFG, max_batch=4,
                                    kv_block_size=BS,
                                    kv_pool_blocks=32, kv_int8=True)
    router = DisaggRouter(decode=[dec], prefill=[pf], max_queue_depth=4,
                          affinity_tokens=BS)
    prompts = [list(range(10 * i + 1, 10 * i + 13)) for i in range(4)]
    try:
        for p in prompts:                       # overflow the 8-block pool
            assert router.generate(p, 5) == colo.generate(p, 5), p
        # the repeats walk back through the arena (their blocks were
        # evicted) — still bit-identical to the big-pool engine's hits
        for p in prompts:
            assert router.generate(p, 5) == colo.generate(p, 5), p
    finally:
        dec.stop()
        colo.stop()
    kst = pf.kvplane_stats()
    assert kst["spills"] > 0, kst
    assert kst["tier2_hits"] > 0, kst
    assert kst["tier2_reused_tokens"] > 0


def test_evict_storm_absorbed_by_arena_outputs_unchanged(model):
    """The evict_storm chaos op: a scripted force-eviction fires before
    request 2's lookup, the arena catches every victim, and every
    output (including the stormed repeat) stays bit-identical — a storm
    sheds capacity, never correctness."""
    plan = json.dumps([{"action": "evict_storm", "role": "prefill",
                        "blocks": 6, "at": "request:2"}])
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32,
                       kv_int8=True, kvplane=True,
                       kvplane_arena_bytes=64 << 20, chaos=plan)
    dec = DecodeServer(model, CFG, max_batch=2)
    colo = ContinuousBatchingEngine(model, CFG, max_batch=4,
                                    kv_block_size=BS,
                                    kv_pool_blocks=32, kv_int8=True)
    router = DisaggRouter(decode=[dec], prefill=[pf], max_queue_depth=4,
                          affinity_tokens=BS)
    base = list(range(601, 613))
    try:
        assert router.generate(base, 5) == colo.generate(base, 5)
        # request 2: the storm evicts the chain, tier 2 hands it back
        assert router.generate(base, 5) == colo.generate(base, 5)
        tail = base + [99]
        assert router.generate(tail, 5) == colo.generate(tail, 5)
    finally:
        dec.stop()
        colo.stop()
    kst = pf.kvplane_stats()
    assert kst["evict_storms"] == 1
    assert kst["storm_evicted_blocks"] >= 1
    assert kst["spills"] >= kst["storm_evicted_blocks"]
    assert kst["tier2_hits"] > 0


def test_evict_storm_action_validation():
    from ray_tpu.resilience.chaos import ChaosAction

    a = ChaosAction.from_dict({"action": "evict_storm",
                               "role": "prefill", "blocks": 3,
                               "at": "request:2"})
    assert a.blocks == 3
    with pytest.raises(ValueError):
        ChaosAction.from_dict({"action": "evict_storm",
                               "role": "prefill", "at": "request:1"})
    with pytest.raises(ValueError):
        ChaosAction.from_dict({"action": "evict_storm",
                               "role": "decode", "blocks": 2,
                               "at": "request:1"})


# ------------------------------- speculation-aware autoscaler demand

def test_speculation_discount_never_over_scales():
    """A decode tier emitting f tokens per verify step drains its queue
    f x faster: the backlog is discounted by the measured factor before
    the policy sizes the tier, so speculation never over-scales — and
    no signal (or factor <= 1) leaves every decision bit-identical."""
    from ray_tpu.serve.autoscale import DisaggPolicy

    pol = DisaggPolicy(target_p99_ms=500.0)
    base = {"queue_depth_p99": 12.0, "decode_cap_per_replica": 4,
            "decode_busy_p99": 4.0}
    want_up = pol.desired_decode(dict(base), 1)
    assert want_up[0] == 3  # proportional jump: ceil(12 / 4)
    # measured 3 tokens/verify: the same backlog fits the tier
    n_spec, reason = pol.desired_decode(
        dict(base, spec_tokens_per_verify=3.0), 1)
    assert n_spec == 1 and n_spec <= want_up[0]
    # partial discount scales LESS, and says why
    n_mid, reason_mid = pol.desired_decode(
        dict(base, spec_tokens_per_verify=2.0), 1)
    assert n_mid == 2 < want_up[0]
    assert "speculation" in reason_mid
    # no signal / degenerate factors: bit-identical decisions
    for f in (None, 0.0, 1.0, 0.6):
        sig = dict(base)
        if f is not None:
            sig["spec_tokens_per_verify"] = f
        assert pol.desired_decode(sig, 1) == want_up


def test_speculation_discount_spares_queue_not_busy_slots():
    """Only QUEUED demand is discounted — an occupied slot is occupied
    whatever its token rate, so busy-slot demand blocks scale-down at
    any speculation factor, while a queue-only backlog may drain."""
    from ray_tpu.serve.autoscale import DisaggPolicy

    pol = DisaggPolicy(target_p99_ms=500.0)
    busy = {"decode_busy_p99": 10.0, "decode_cap_per_replica": 4,
            "queue_depth_p99": 0.0, "spec_tokens_per_verify": 4.0}
    n, _ = pol.desired_decode(dict(busy), 3)
    assert n == 3  # 10 busy slots never fit 2 replicas, factor or not
    queued = {"decode_busy_p99": None, "decode_cap_per_replica": 4,
              "queue_depth_p99": 10.0, "spec_tokens_per_verify": 4.0}
    queued = {k: v for k, v in queued.items() if v is not None}
    n2, reason2 = pol.desired_decode(queued, 3)
    assert n2 == 2, reason2  # 10/4 = 2.5 fits one-fewer replicas


# --------------------------------- conductor directory (cluster)

def test_directory_atomic_commit_and_namespace_isolation(
        kvplane_cluster):
    w = kvplane_cluster
    dig = "ab" * 32
    meta = {"holder": "pf-first", "desc": {"n": 1}, "tokens": 8,
            "nbytes": 123}
    assert w.conductor.call("kvplane_publish", "", dig, meta) \
        == {"status": "committed"}
    # atomic commit: the SECOND publisher loses, first holder serves
    res2 = w.conductor.call("kvplane_publish", "", dig,
                            dict(meta, holder="pf-second"))
    assert res2["status"] == "already" and res2["holder"] == "pf-first"
    # longest-first scan returns the registered entry, sans clock
    entry = w.conductor.call("kvplane_lookup", "", ["ff" * 32, dig])
    assert entry["holder"] == "pf-first" and entry["digest"] == dig
    assert entry["tokens"] == 8 and "started" not in entry
    # namespace isolation: the key includes the namespace
    assert w.conductor.call("kvplane_lookup", "tenantA|v1",
                            [dig]) is None
    # malformed commits are error dicts, never raises
    bad = w.conductor.call("kvplane_publish", "", "cd" * 32, {"n": 1})
    assert bad.get("error")
    # retraction: the holder's refs died, lookups stop routing to it
    assert w.conductor.call("kvplane_unpublish", "", dig) is True
    assert w.conductor.call("kvplane_lookup", "", [dig]) is None


def test_directory_ttl_reap_and_gc(kvplane_cluster, monkeypatch):
    w = kvplane_cluster
    meta = {"holder": "pf-ttl", "desc": {}, "tokens": 8, "nbytes": 1}
    assert w.conductor.call("kvplane_publish", "ttl", "aa" * 32,
                            meta)["status"] == "committed"
    # lazy TTL reap inside the lookup itself (conductor runs in this
    # process, so the env knob takes effect immediately)
    monkeypatch.setenv("RAY_TPU_KVPLANE_T3_TTL_S", "0.05")
    time.sleep(0.1)
    assert w.conductor.call("kvplane_lookup", "ttl",
                            ["aa" * 32]) is None
    monkeypatch.delenv("RAY_TPU_KVPLANE_T3_TTL_S")
    # explicit reap: age 0 drops everything left in any namespace
    for i in range(2):
        w.conductor.call("kvplane_publish", "ttl", f"{i:02d}" * 32,
                         meta)
    assert w.conductor.call("kvplane_reap", 0.0) >= 2
    # keep-last-K GC, namespace-scoped
    for i in range(5):
        w.conductor.call("kvplane_publish", "gcns", f"b{i}" * 32, meta)
    assert w.conductor.call("kvplane_gc", 2, "gcns") == 3
    st = w.conductor.call("get_status", "kvplane")
    assert st["directory"]["namespaces"].get("gcns") == 2
    ctr = st["directory"]["counters"]
    assert ctr["reaped"] >= 3 and ctr["gced"] >= 3


def test_router_directory_hit_and_holder_death_fallback(
        kvplane_cluster, model):
    """Routing upgrades from hash-guess to directory truth: a live
    holder wins outright; an entry whose holder left the pool degrades
    to the hash plus a tier-3 hint the replica fetches (and a bogus
    descriptor fails harmlessly — tier 3 is an accelerator, not a
    dependency); a miss falls back to the hash bit-identically."""
    from ray_tpu.models.kvcache import prefix_digests

    w = kvplane_cluster
    pf = PrefillServer(model, CFG, kv_block_size=BS,
                       kv_pool_blocks=32, kvplane=True)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=4, affinity_tokens=BS)
    prompt = list(range(701, 713))  # 3 full blocks > publish floor
    try:
        out1 = router.generate(prompt, 4)  # miss; prefill publishes t3
        out2 = router.generate(prompt, 4)  # directory hit -> holder
        assert out2 == out1
        # an entry whose holder is gone: hash + hint, bogus desc is
        # swallowed, the request still completes
        ghost = list(range(801, 813))
        digs = prefix_digests(ghost, BS, None)
        assert w.conductor.call(
            "kvplane_publish", "", digs[0],
            {"holder": "pf-ghost", "desc": {"bogus": True},
             "tokens": 8, "nbytes": 0})["status"] == "committed"
        out3 = router.generate(ghost, 4)
        assert len(out3) == 4
    finally:
        dec.stop()
    rs = router.stats()
    assert rs["directory_misses"] >= 1
    assert rs["directory_hits"] >= 1
    assert rs["directory_fallbacks"] >= 1
    kst = pf.kvplane_stats()
    assert kst["tier3_publishes"] >= 1
    assert kst["t3_held_refs"] >= 1
    rks = router.kvplane_stats()
    assert rks["enabled"] and rks["kv_block_size"] == BS
    assert rks["directory_hits"] == rs["directory_hits"]


def test_cold_prefill_tier_readopts_from_tier3_bit_identical(
        kvplane_cluster, model):
    """Tier 3 outlives its holder: the whole prefill tier leaves the
    router (the old replica stays alive, so its published chunks do),
    a cold replica takes its place, and the replay is routed by the
    directory's fallback hint, adopts the prefix from the object store
    and gives the same tokens as before the swap."""
    old = PrefillServer(model, CFG, kv_block_size=BS,
                        kv_pool_blocks=32, kv_int8=True, kvplane=True)
    cold = PrefillServer(model, CFG, kv_block_size=BS,
                         kv_pool_blocks=32, kv_int8=True, kvplane=True)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[old],
                          max_queue_depth=4, affinity_tokens=BS)
    prompts = [list(range(40 * i + 2001, 40 * i + 2014))
               for i in range(3)]  # 3 full blocks each + a tail
    try:
        ref = [router.generate(p, 5) for p in prompts]
        assert old.kvplane_stats()["tier3_publishes"] >= len(prompts)
        for r in router.tier_replicas("prefill"):
            router.remove_dead("prefill", r["rid"])
        router.add_prefill(cold)
        before = router.stats()["directory_fallbacks"]
        got = [router.generate(p, 5) for p in prompts]
    finally:
        dec.stop()
    assert got == ref
    assert router.stats()["directory_fallbacks"] - before == len(prompts)
    kst = cold.kvplane_stats()
    assert kst["tier3_adopts"] == len(prompts), kst
    assert kst["tier3_adopted_blocks"] == 3 * len(prompts)
    assert kst["tier3_reused_tokens"] == 3 * BS * len(prompts)
    assert cold.stats()["reused_tokens"] >= 3 * BS * len(prompts)


# --------------------------- chunk-fabric per-caller attribution

def test_chunk_fetcher_caller_attribution(kvplane_cluster):
    from ray_tpu.util import chunks

    def _reads(totals):
        return totals.get("chunks_local", 0) \
            + totals.get("chunks_fetched", 0)

    w = kvplane_cluster
    payload = {"x": np.arange(4096, dtype=np.int8)}
    refs, desc = chunks.put_tree(w, payload)
    before = _reads(chunks.caller_totals("kvplane"))
    f = chunks.ChunkFetcher(w, caller="kvplane")
    got = chunks.fetch_tree(w, desc, fetcher=f)
    assert np.array_equal(got["x"], payload["x"])
    st = f.stats()
    assert st["caller"] == "kvplane"
    # one host: the chunk rides the local path, but the READ is still
    # attributed to this fetcher's caller bucket
    assert _reads(st) >= 1
    after = _reads(chunks.caller_totals("kvplane"))
    assert after - before == _reads(st)
    # a differently-labeled fetcher accumulates in its own bucket
    kv_before = _reads(chunks.caller_totals("kv"))
    f2 = chunks.ChunkFetcher(w, caller="kv")
    chunks.fetch_tree(w, desc, fetcher=f2)
    assert _reads(chunks.caller_totals("kv")) \
        == kv_before + _reads(f2.stats())
    assert _reads(chunks.caller_totals("kvplane")) == after
    assert chunks.ChunkFetcher(w).stats()["caller"] == "unlabeled"
    del refs


# ----------------------------------------------- e2e surface check

def test_all_surfaces_report_consistent_numbers(kvplane_cluster,
                                                model, capsys):
    """kvplane_status() / CLI / /api/kvplane / Prometheus / timeline
    all report the SAME spill/hit/publish/directory numbers for one
    spill-heavy router+tiers workload."""
    import urllib.request

    from ray_tpu.dashboard import DashboardServer
    from ray_tpu.scripts import cli
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    w = kvplane_cluster
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=8,
                       kv_int8=True, kvplane=True,
                       kvplane_arena_bytes=64 << 20)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=4, affinity_tokens=BS)
    prompts = [list(range(30 * i + 1001, 30 * i + 1013))
               for i in range(4)]
    try:
        for p in prompts:            # overflow the pool -> spills
            router.generate(p, 4)
        for p in prompts:            # readopts + directory hits
            router.generate(p, 4)
    finally:
        dec.stop()
    pf.publish_telemetry(force=True)
    router.publish_telemetry(force=True)
    metrics_mod.flush()
    kst = pf.kvplane_stats()
    rks = router.kvplane_stats()
    assert kst["spills"] > 0 and kst["tier2_hits"] > 0
    assert kst["tier3_publishes"] >= 1
    assert rks["directory_hits"] >= 1

    # state API (fire-and-forget notify: poll until the snapshots land)
    deadline = time.monotonic() + 10.0
    while True:
        st = state.kvplane_status()
        mine = st["components"].get(pf.server_id)
        rt = st["components"].get(router.router_id)
        if mine is not None and rt is not None \
                and mine.get("spills") == kst["spills"] \
                and rt.get("directory_hits") == rks["directory_hits"]:
            break
        assert time.monotonic() < deadline, st
        time.sleep(0.1)
    assert mine["tier2_hits"] == kst["tier2_hits"]
    assert mine["tier3_publishes"] == kst["tier3_publishes"]
    assert mine["entries"] == kst["entries"]
    totals = st["totals"]
    assert totals["spills"] >= kst["spills"]
    assert totals["tier2_hits"] >= kst["tier2_hits"]
    assert totals["directory_hits"] >= rks["directory_hits"]
    assert totals["arena_entries"] >= kst["entries"]
    assert st["directory"]["entries"] >= 1
    assert st["directory"]["counters"]["publishes"] >= 1

    # CLI (same conductor snapshot)
    host, port = w.conductor_address
    cli.main(["kvplane", "--json", "--address", f"{host}:{port}"])
    cli_out = json.loads(capsys.readouterr().out)
    assert cli_out["totals"] == totals
    assert cli_out["directory"] == st["directory"]

    # dashboard /api/kvplane
    srv = DashboardServer(w.conductor_address, port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/api/kvplane",
                                    timeout=10.0) as r:
            dash = json.loads(r.read())
    finally:
        srv.stop()
    assert dash["totals"] == totals
    assert dash["directory"] == st["directory"]
    ev_kinds = {e.get("kind") for e in dash["events"]}
    assert {"spill", "tier2_hit", "tier3_publish"} <= ev_kinds

    # Prometheus: the kvplane families exist and cover this workload
    prom = state.prometheus_metrics()
    for family in ("ray_tpu_kvplane_spills_total",
                   "ray_tpu_kvplane_hits_total",
                   "ray_tpu_kvplane_reused_tokens_total",
                   "ray_tpu_kvplane_directory_total",
                   "ray_tpu_kvplane_arena_bytes"):
        assert family in prom, family
    spill_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_kvplane_spills_total"))
    assert spill_total >= kst["spills"]

    # merged timeline: the kvplane lane mirrors the event log
    trace = state.timeline(merged=True)
    markers = [e for e in trace if e.get("pid") == "kvplane"]
    assert markers and all(m["ph"] == "i" and m["cat"] == "kvplane"
                           for m in markers)
    tids = {m["tid"] for m in markers}
    assert {"spill", "tier2_hit", "tier3_publish",
            "directory_hit"} <= tids
    spills_here = [m for m in markers if m["tid"] == "spill"
                   and m["args"].get("replica") == pf.server_id]
    assert len(spills_here) == kst["spills"]
