"""Multi-tenant LoRA serving (serve/lora.py + engine/router support).

Correctness oracles:
- base-only slots of a LoRA-enabled engine are BIT-IDENTICAL to
  today's base-only engine (the null adapter is an exact no-op);
- mixed-tenant batches are bit-identical to per-tenant sequential
  runs (per-slot adapter gathers are slot-independent);
- one tenant's adapter never leaks into another's output — not
  through the decode tick, not through the (tenant, prompt)-keyed
  prefix cache, not through a hot-swap.

Tier-1-safe under the `lora` marker: tiny configs on CPU, one
module-scoped engine pair, cluster tests on a module-scoped
log_to_driver=0 cluster.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.lora

PROMPT = list(range(1, 9))
LONG_PROMPT = list(range(1, 20))


@pytest.fixture(scope="module")
def tiny():
    import jax

    from ray_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.tiny()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def adapters(tiny):
    from ray_tpu.serve.lora import make_lora_adapter

    cfg, _ = tiny
    return {f"t{i}": make_lora_adapter(cfg, rank=3, seed=10 + i)
            for i in range(4)}


@pytest.fixture(scope="module")
def engines(tiny, adapters):
    """(lora_engine, pool, source, base_engine) shared by the module —
    engine construction compiles the decode programs once."""
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.serve.lora import AdapterPool, LocalAdapterSource

    cfg, params = tiny
    source = LocalAdapterSource(dict(adapters))
    pool = AdapterPool(cfg, slots=3, source=source)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=4,
                                   lora_pool=pool)
    base = ContinuousBatchingEngine(params, cfg, max_batch=4)
    yield eng, pool, source, base
    eng.stop()
    base.stop()


# ------------------------------------------------------------- pool units


def test_pool_refcount_lru_pin_evict(tiny, adapters):
    from ray_tpu.serve.lora import (AdapterPool, LocalAdapterSource,
                                    LoraPoolExhausted)

    cfg, _ = tiny
    pool = AdapterPool(cfg, slots=2,
                       source=LocalAdapterSource(dict(adapters)))
    r0 = pool.acquire("t0")          # miss: pages in
    assert pool.acquire("t0") == r0  # hit: same row, second pin
    s = pool.stats()
    assert (s["hits"], s["misses"]) == (1, 1)
    assert s["residents"]["t0"]["ref"] == 2
    r1 = pool.acquire("t1")          # second row
    assert r1 != r0 and r1 != 0      # row 0 is the null adapter
    # pool full, everything pinned: acquire of a third tenant refuses
    with pytest.raises(LoraPoolExhausted):
        pool.acquire("t2")
    # release t1 fully -> it becomes the LRU unpinned victim
    pool.release("t1")
    r2 = pool.acquire("t2")
    assert r2 == r1                  # evicted + reused t1's row
    s = pool.stats()
    assert s["evictions"] == 1 and "t1" not in s["residents"]
    assert s["tenants"]["t1"]["evictions"] == 1
    # t0 stayed pinned through all of it
    assert s["residents"]["t0"]["ref"] == 2
    # refcount-0 residents stay cached (that IS the cache)
    pool.release("t0")
    pool.release("t0")
    assert pool.stats()["residents"]["t0"]["ref"] == 0
    assert pool.acquire("t0") == r0  # still a hit


def test_pool_row_writes_are_donated_in_place(tiny, adapters):
    """The ROADMAP LoRA follow-up (c): a page-in writes O(row) IN
    PLACE through a donated jit — never an O(pool) stack copy. The
    donation is observable: the pre-write stack buffer is deleted
    (donated into the write) and the post-write stack reuses the same
    device buffer. A copying `.at[row].set` would leave the old array
    alive and allocate a fresh pool (and trips shardlint's
    undonated-pool-write rule anyway)."""
    from ray_tpu.serve.lora import AdapterPool, LocalAdapterSource

    cfg, _ = tiny
    pool = AdapterPool(cfg, slots=2,
                       source=LocalAdapterSource(dict(adapters)))
    name = pool.targets[0][0]
    pool.acquire("t0")  # first page-in: the stacks settle
    before_a = pool._a[name]
    before_scale = pool._scale
    ptr_a = before_a.unsafe_buffer_pointer()
    pool.acquire("t1")  # second page-in writes another row
    assert before_a.is_deleted()       # donated, not copied
    assert before_scale.is_deleted()
    assert pool._a[name].unsafe_buffer_pointer() == ptr_a  # in place
    # content is still per-row correct: t0's row survived t1's write
    sl = pool.adapter_slice(pool.acquire("t0"))
    import numpy as np

    got = np.asarray(sl["targets"][name]["a"], np.float32)
    want = np.asarray(adapters["t0"]["targets"][name]["a"], np.float32)
    assert np.allclose(got[..., :want.shape[-1]], want, atol=1e-2)


def test_pool_rank_ceiling(tiny, adapters):
    from ray_tpu.serve.lora import (AdapterPool, LocalAdapterSource,
                                    make_lora_adapter)

    cfg, _ = tiny
    big = make_lora_adapter(cfg, rank=9, seed=1)
    pool = AdapterPool(cfg, slots=2, rank_max=4,
                       source=LocalAdapterSource({"big": big}))
    with pytest.raises(ValueError, match="rank_max"):
        pool.acquire("big")


# ------------------------------------------------------ engine bit-identity


def test_mixed_batch_bit_identity(engines):
    eng, pool, _source, base = engines
    # mixed batch: two tenants + a base request decode in ONE tick loop
    streams = [eng.stream(PROMPT, 6, adapter_id=a)
               for a in ("t0", "t1", None)]
    mixed = [list(s) for s in streams]
    # sequential per-tenant runs through the same engine
    seq = [eng.generate(PROMPT, 6, adapter_id=a)
           for a in ("t0", "t1", None)]
    assert mixed == seq
    # the base slot of the mixed batch is bit-identical to TODAY's
    # engine (no lora machinery at all) — the null-adapter oracle
    assert mixed[2] == base.generate(PROMPT, 6)
    # ...and the adapters actually did something. One this weak (rank
    # 3, scale 1) need not flip an argmax of the tiny model, so the
    # claim is held to the per-token log-probabilities the streams
    # carry: an adapter that did something moves a score even where
    # the greedy token holds
    scores = [s.scores for s in streams]
    assert all(len(sc) == 6 for sc in scores)
    assert scores[0] != scores[2] and scores[1] != scores[2]
    assert scores[0] != scores[1]


@pytest.mark.slow
def test_gpt2_family_lora_targets():
    """GPT-2's fused-qkv LoRA target (slow-marked: two extra engine
    compiles; `pytest -m lora` includes it, tier-1 skips it — the
    llama-family tests above cover the shared machinery)."""
    import jax

    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init
    from ray_tpu.serve.lora import (AdapterPool, LocalAdapterSource,
                                    make_lora_adapter)

    cfg = GPT2Config.tiny()
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    pool = AdapterPool(cfg, slots=2, source=LocalAdapterSource(
        {"g0": make_lora_adapter(cfg, rank=2, seed=3, scale=32.0)}))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                   lora_pool=pool)
    base = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        b = eng.generate(PROMPT, 4)
        t = eng.generate(PROMPT, 4, adapter_id="g0")
        assert b == base.generate(PROMPT, 4)
        assert t != b and t == eng.generate(PROMPT, 4,
                                            adapter_id="g0")
    finally:
        eng.stop()
        base.stop()


# ------------------------------------------------------- tenant KV cache


def test_tenant_kv_namespace_isolation(engines):
    eng, _pool, _source, _base = engines
    kv = eng.kv_cache
    # a prompt range no other test shares (cross-test prefix overlap
    # would turn the expected miss into a partial hit)
    prompt = list(range(200, 219))
    before = kv.stats()
    out_a = eng.generate(prompt, 4, adapter_id="t0")
    mid = kv.stats()
    # t0 cached its prefix; t1 with the SAME prompt must NOT match it
    out_b = eng.generate(prompt, 4, adapter_id="t1")
    after = kv.stats()
    assert mid["misses"] == before["misses"] + 1
    assert after["misses"] == mid["misses"] + 1  # t1: miss, not hit
    assert after["hits"] == mid["hits"]
    # same tenant again IS a hit, and deterministic
    out_a2 = eng.generate(prompt, 4, adapter_id="t0")
    assert kv.stats()["hits"] == after["hits"] + 1
    assert out_a2 == out_a and out_a != out_b


def test_kvcache_namespace_unit(tiny):
    """Allocator-level: namespaced roots diverge, scoped invalidate
    flushes exactly one namespace."""
    import jax

    from ray_tpu.models.engine import _prefill_paged
    from ray_tpu.models.kvcache import PagedKVCache

    cfg, params = tiny
    kv = PagedKVCache(cfg, block_size=4, num_blocks=16)
    toks = np.arange(1, 13, dtype=np.int32)
    _, ck, cv, _, _ = _prefill_paged(params, toks[None, :], cfg,
                                     kv._empty_k, kv._empty_k)
    for ns in ("a", "b", None):
        m = kv.lookup(toks, max_tokens=11, namespace=ns)
        assert m.outcome == "miss"
        kv.release(kv.commit(toks, ck, cv, m, namespace=ns))
    for ns in ("a", "b", None):
        m = kv.lookup(toks, max_tokens=11, namespace=ns)
        assert m.tokens > 0, ns
        kv.release(m.bids)
    kv.invalidate(namespace="a")
    assert kv.lookup(toks, max_tokens=11, namespace="a").tokens == 0
    m = kv.lookup(toks, max_tokens=11, namespace="b")
    assert m.tokens > 0  # b untouched
    kv.release(m.bids)
    m = kv.lookup(toks, max_tokens=11)  # base namespace untouched
    assert m.tokens > 0
    kv.release(m.bids)


# ---------------------------------------------------------- hot swap


def test_hot_swap_mid_decode_leaves_others_unchanged(engines, tiny):
    from ray_tpu.serve.lora import make_lora_adapter

    eng, pool, source, _base = engines
    cfg, _ = tiny
    # make t2 resident at a known version before the swap
    pool.acquire("t2")
    pool.release("t2")
    v1 = pool.resident_version("t2")
    # reference: t3's uninterrupted output (computed before any swap)
    ref = eng.generate(PROMPT, 10, adapter_id="t3")
    # t3 decodes while t2's adapter is republished + hot-swapped
    stream = eng.stream(PROMPT, 10, adapter_id="t3")
    it = iter(stream)
    got = [next(it)]
    source.publish("t2", make_lora_adapter(cfg, rank=3, seed=99))
    # acquire-on-dirty hot-swaps t2's row in place, between ticks
    row = pool.acquire("t2")
    pool.release("t2")
    assert pool.resident_version("t2") == v1 + 1
    assert pool.stats()["swaps"] >= 1
    got.extend(it)
    assert got == ref  # t3 never saw t2's swap
    # and t2 now decodes under the NEW adapter deterministically
    out2 = eng.generate(PROMPT, 6, adapter_id="t2")
    assert out2 == eng.generate(PROMPT, 6, adapter_id="t2")
    del row


def test_evicted_then_republished_adapter_flushes_stale_kv(tiny,
                                                           adapters):
    """A tenant evicted from the pool, republished, and paged back in
    arrives at a NEW version — its namespace-keyed KV (version-blind
    digests) was computed under the old one and must be flushed on the
    re-page-in, not just on a resident-row hot-swap."""
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.serve.lora import (AdapterPool, LocalAdapterSource,
                                    make_lora_adapter)

    cfg, params = tiny
    v2 = make_lora_adapter(cfg, rank=3, seed=55)
    source = LocalAdapterSource({"t0": dict(adapters["t0"]),
                                 "t1": dict(adapters["t1"])})
    pool = AdapterPool(cfg, slots=1, source=source)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                   lora_pool=pool)
    ref_eng = ContinuousBatchingEngine(
        params, cfg, max_batch=2,
        lora_pool=AdapterPool(cfg, slots=1,
                              source=LocalAdapterSource({"t0": v2})))
    try:
        prompt = list(range(300, 319))
        out1 = eng.generate(prompt, 4, adapter_id="t0")  # KV @ v1
        eng.generate(prompt, 4, adapter_id="t1")  # slots=1: evicts t0
        source.publish("t0", v2)
        out2 = eng.generate(prompt, 4, adapter_id="t0")  # re-page @ v2
        # bit-identical to a clean v2-only engine: the v1-era cached
        # prefix was flushed, never spliced under the v2 adapter
        ref = ref_eng.generate(prompt, 4, adapter_id="t0")
        assert out2 == ref
        assert out2 != out1
    finally:
        eng.stop()
        ref_eng.stop()


def test_cold_page_in_never_stalls_hot_tenant(tiny, adapters):
    """A cold adapter's (slow) fetch runs on the SUBMITTING thread:
    the hot tenant's decode ticks keep flowing while it pages."""
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.serve.lora import AdapterPool, LocalAdapterSource

    cfg, params = tiny
    delay = 0.4
    source = LocalAdapterSource(dict(adapters), fetch_delay_s=delay)
    pool = AdapterPool(cfg, slots=3, source=source)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=4,
                                   lora_pool=pool)
    try:
        eng.generate(PROMPT, 2, adapter_id="t0")  # warm t0 + programs
        gaps = []
        stream = eng.stream(PROMPT, 28, adapter_id="t0")
        it = iter(stream)
        next(it)

        def cold_submit():
            eng.generate(PROMPT, 2, adapter_id="t1")  # pays the 0.5s

        th = threading.Thread(target=cold_submit)
        th.start()
        last = time.perf_counter()
        for _ in range(20):
            next(it)
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
        th.join()
        list(it)
        # no inter-token gap on the hot stream approaches the page-in
        # delay — the fetch never blocked the tick loop
        assert max(gaps) < delay * 0.8, max(gaps)
    finally:
        eng.stop()


# ----------------------------------------------------------- cancel_slot


def test_cancel_slot_frees_and_readmits(engines):
    eng, pool, _source, base = engines
    free0 = eng.free_slots
    stream = eng.stream(PROMPT, 80, adapter_id="t0")
    it = iter(stream)
    next(it)
    assert eng.cancel_slot(stream) is True
    leftover = list(it)  # ends promptly at the next tick boundary
    assert len(leftover) < 79
    deadline = time.monotonic() + 5.0
    while eng.free_slots < free0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng.free_slots == free0          # slot re-admittable
    assert eng.cancelled == 1
    assert eng.cancel_slot(stream) is False  # already finished
    # freed slot admits and still matches the base engine bit-for-bit
    assert eng.generate(PROMPT, 6) == base.generate(PROMPT, 6)


def test_cancel_decode_via_decode_server(tiny):
    from ray_tpu.serve.disagg import DecodeServer, PrefillServer

    cfg, params = tiny
    pf = PrefillServer(params, cfg)
    dec = DecodeServer(params, cfg, max_batch=2)
    try:
        rec = pf.prefill(PROMPT)
        hid = dec.start_decode(rec, 60)
        out = dec.next_tokens(hid, max_tokens=4)
        assert out["tokens"]
        assert dec.cancel_decode(hid) is True
        deadline = time.monotonic() + 5.0
        while dec.free_slots() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert dec.free_slots() == 2      # freed early, re-admittable
        assert dec.engine.cancelled == 1
        with pytest.raises(KeyError):
            dec.next_tokens(hid)
    finally:
        dec.stop()


# ------------------------------------------------------------ satellites


def test_chaos_reset_counts():
    from ray_tpu.resilience.chaos import ChaosPlan, ServeChaosMonkey

    fired = []
    plan = ChaosPlan.from_spec(
        '[{"action": "kill_replica", "role": "decode", '
        '"at": "request:2", "replica": 0}]')
    m = ServeChaosMonkey(plan, "decode", 0, exit_fn=fired.append)
    m.on_request()  # warm-up traffic
    m.on_request()  # would fire WITHOUT the reset...
    fired.clear()   # (it did — prove the reset starts a fresh count)
    m2 = ServeChaosMonkey(plan, "decode", 0, exit_fn=fired.append)
    m2.on_request()
    m2.reset_counts()  # measurement starts here
    m2.on_request()
    assert fired == []            # 1st measured request: no fire
    m2.on_request()
    assert fired == [137]         # 2nd measured request: fires


def test_proportional_scale_steps():
    from ray_tpu.serve.autoscale import DisaggPolicy, ScalingPolicy

    pol = DisaggPolicy(target_p99_ms=100.0)
    sig = {"decode_cap_per_replica": 4}
    # shallow backlog: classic +1
    d, why = pol.desired_decode(dict(sig, queue_depth_p99=6.0), 1)
    assert d == 2
    # deep backlog (> 2x one replica's capacity): proportional jump
    d, why = pol.desired_decode(dict(sig, queue_depth_p99=19.0), 1)
    assert d == 5 and "proportional" in why  # ceil(19/4)
    # bounds still clamp at decide/apply time
    sp = ScalingPolicy(min_replicas=1, max_replicas=3,
                       up_delay_s=0.0, cooldown_s=0.0)
    assert sp.decide(5, 1, now=100.0) == 3
    # hysteresis unchanged: an oscillating desired never flaps
    sp2 = ScalingPolicy(min_replicas=1, max_replicas=8,
                        up_delay_s=5.0, down_delay_s=5.0)
    cur = 2
    for i in range(20):
        cur = sp2.decide(5 if i % 2 == 0 else 1, cur, now=float(i))
    assert cur == 2


def test_router_tenant_isolation_and_affinity(tiny, adapters):
    from ray_tpu.serve.disagg import DisaggRouter, RequestShedError
    from ray_tpu.serve.lora import AdapterPool, LocalAdapterSource

    from ray_tpu.models.engine import ContinuousBatchingEngine

    cfg, params = tiny
    pool = AdapterPool(cfg, slots=3,
                       source=LocalAdapterSource(dict(adapters)))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                   lora_pool=pool)
    router = DisaggRouter(colocated=eng, max_queue_depth=0)
    try:
        router.generate(PROMPT, 2, tenant="t0")  # warm compile

        done = threading.Event()

        def slow_t0():
            router.generate(PROMPT, 14, tenant="t0",
                            token_sleep_s=0.04)
            done.set()

        th = threading.Thread(target=slow_t0, daemon=True)
        th.start()
        time.sleep(0.25)  # t0 occupies the single slot
        with pytest.raises(RequestShedError) as ei:
            router.generate(PROMPT, 2, tenant="t1")
        assert ei.value.cause == "capacity"
        done.wait(timeout=30.0)
        th.join(timeout=5.0)
        ts = router.tenant_stats()
        # the shed charged to t1 ONLY; t0's counters untouched by it
        assert ts["t1"]["shed"] == 1
        assert ts["t1"]["sheds_by_cause"] == {"capacity": 1}
        assert ts["t0"]["shed"] == 0
        assert ts["t0"]["completed"] == 2
        assert ts["t0"]["ttft_ms"]["n"] == 2
        # tenant-affinity bookkeeping engaged
        st = router.stats()
        assert st["tenant_affinity_total"] >= 2
        assert st["tenants"]["t0"]["dispatched"] == 2
        # an UNKNOWN tenant is a configuration error, not a serving
        # fault: it raises to the caller instead of shedding
        with pytest.raises(Exception, match="no adapter registered"):
            router.generate(PROMPT, 2, tenant="missing")
        assert router.tenant_stats().get("missing", {}).get("shed",
                                                            0) == 0
    finally:
        eng.stop()


# ------------------------------------------------------- cluster-backed


@pytest.fixture(scope="module")
def lora_cluster():
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                        _system_config={"log_to_driver": 0})
    yield info
    ray_tpu.shutdown()


def test_fabric_source_and_tenant_trainer(lora_cluster, tiny):
    """The weight-fabric paging path end-to-end: a per-tenant trainer
    publishes adapter deltas, a FabricAdapterSource-backed pool pages
    them on demand and hot-swaps on the publish notice."""
    from ray_tpu.online.lora import TenantLoraTrainer
    from ray_tpu.serve.lora import AdapterPool, FabricAdapterSource

    cfg, params = tiny
    trainer = TenantLoraTrainer(params, cfg, "fabt", rank=2,
                                publish_every=1, learning_rate=1e-2,
                                seed=0)
    rng = np.random.default_rng(0)
    batch = rng.integers(1, cfg.vocab_size, (2, 16)).astype(np.int32)
    res = trainer.fit([batch, batch], num_steps=2)
    assert res["published_versions"] == [1, 2]
    assert len(res["losses"]) == 2
    pool = AdapterPool(cfg, slots=2, source=FabricAdapterSource())
    row = pool.acquire("fabt")
    assert row != 0
    assert pool.resident_version("fabt") == 2
    assert pool.stats()["page_in_bytes"] > 0
    pool.release("fabt")
    # a THIRD publish marks the tenant dirty via pubsub; the next
    # acquire hot-swaps (bounded wait for the notice to land)
    trainer.step(batch)
    trainer.publish()
    deadline = time.monotonic() + 10.0
    while not pool.source.dirty("fabt") \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    pool.acquire("fabt")
    pool.release("fabt")
    assert pool.resident_version("fabt") == 3
    assert pool.stats()["swaps"] == 1
    pool.source.close()


def test_lora_surfaces_one_set_of_numbers(lora_cluster, tiny,
                                          adapters, capsys):
    """state API == CLI == dashboard == Prometheus == timeline."""
    import json

    from ray_tpu.dashboard import _ClusterData
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.scripts.cli import main as cli_main
    from ray_tpu.serve.disagg import DisaggRouter
    from ray_tpu.serve.lora import AdapterPool, LocalAdapterSource
    from ray_tpu.util import state

    cfg, params = tiny
    pool = AdapterPool(cfg, slots=2,
                       source=LocalAdapterSource(dict(adapters)))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                   lora_pool=pool)
    router = DisaggRouter(colocated=eng)
    try:
        for t in ("t0", "t1", "t0", "t2"):
            router.generate(PROMPT, 3, tenant=t)
        pool.publish_telemetry(force=True)
        router.publish_telemetry(force=True)
        st = state.lora_status()
        totals = st["totals"]
        # THIS pool's snapshot matches its own stats exactly (other
        # tests' pools may also be in the roster)
        mine = st["pools"][pool.pool_id]
        ps = pool.stats()
        for k in ("acquires", "hits", "misses", "evictions", "swaps",
                  "resident"):
            assert mine[k] == ps[k], k
        assert ps["evictions"] >= 1
        assert totals["acquires"] >= ps["acquires"]
        assert st["tenants"]["t0"]["dispatched"] == 2
        # CLI --json reports the same aggregate (address given
        # explicitly: a clean environment has no head-address file)
        cli_main(["lora", "--json", "--address", "ignored:0"])
        cli_out = json.loads(capsys.readouterr().out)
        assert cli_out["totals"] == totals
        # dashboard payload (same conductor call the /api route serves)
        from ray_tpu._private import worker as worker_mod

        dash = _ClusterData(
            worker_mod.global_worker.conductor_address).lora()
        assert dash["totals"] == totals
        assert any(e["kind"] == "page_in" for e in dash["events"])
        # Prometheus families
        prom = state.prometheus_metrics()
        assert "ray_tpu_lora_adapter_hits_total" in prom
        assert "ray_tpu_lora_adapter_misses_total" in prom
        assert "ray_tpu_lora_adapter_evictions_total" in prom
        assert "ray_tpu_lora_pool_utilization" in prom
        # merged-timeline lane
        trace = state.timeline(merged=True)
        lanes = [e for e in trace if e.get("pid") == "lora"]
        assert any(e["tid"] == "page_in" for e in lanes)
        assert any(e["tid"] == "evict" for e in lanes)
    finally:
        eng.stop()
