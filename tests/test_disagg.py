"""Disaggregated prefill/decode serving (ISSUE-9 acceptance surface):
cross-replica KV-block streaming over the chunk fabric (bit-identical
decode vs the colocated path for hit/partial/miss cache outcomes, with
the chunk accounting proving no process materialized a full KV copy and
the decode replica never compiling a prefill program), router admission
control + load shedding (bounded queue depth, reject-with-retry-after),
the open-loop load harness at tiny config, and the one-set-of-numbers
consistency check across state API / CLI / dashboard / Prometheus /
timeline.

The `disagg` marker tags the scenarios; everything here is tier-1-safe
on CPU — cluster tests run on a module-scoped cluster with
log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.disagg import DecodeServer, DisaggRouter, PrefillServer
from ray_tpu.serve.handle import RequestShedError

pytestmark = pytest.mark.disagg

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4  # KV block size: small enough for hit/partial/miss coverage


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def disagg_cluster():
    ray_tpu.init(num_cpus=6, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def _colocated_engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("kv_pool_blocks", 32)
    return ContinuousBatchingEngine(model, CFG, **kw)


def _kv_bytes(plen: int) -> int:
    """Exact payload bytes of one prompt's KV transfer: K and V, each
    [layers, plen, kv_heads, head_dim] in the float32 test dtype."""
    return 2 * CFG.num_layers * plen * CFG.num_kv_heads \
        * CFG.head_dim * 4


# -------------------------------------------- cross-replica roundtrip

def test_cross_replica_transfer_bit_identical_no_full_copy(
        disagg_cluster, model):
    """E2e at tiny config: prefill ACTOR -> KV blocks streamed ->
    decode ACTOR, bit-identical to the colocated engine for hit,
    partial, and miss cache outcomes; fetched bytes == exactly the
    prompts' KV bytes (shm path, rpc 0 on one host); the decode
    process never compiled a prefill program."""
    prefill = ray_tpu.remote(PrefillServer).options(
        max_concurrency=4).remote(model, CFG, kv_block_size=BS,
                                  kv_pool_blocks=32)
    decode = ray_tpu.remote(DecodeServer).options(
        max_concurrency=8).remote(model, CFG, max_batch=4)
    colo = _colocated_engine(model)
    router = DisaggRouter(decode=[decode], prefill=[prefill],
                          max_queue_depth=4, affinity_tokens=BS)
    base = [1, 2, 3, 4, 5, 6, 7, 8]                  # 2 aligned blocks
    prompts = [
        base,                          # miss (first sight)
        base,                          # hit (suffix within one block)
        base + [9, 10, 11, 12, 13],    # partial (5-token tail > BS)
        [5, 5, 5],                     # miss, sub-block prompt
    ]
    try:
        outcomes = []
        for p in prompts:
            want = colo.generate(p, 6)
            got = router.generate(p, 6)
            assert got == want, p
        # the router's post-decode ack is fire-and-forget; poll until
        # the last one lands rather than racing it on the first read
        deadline = time.monotonic() + 10.0
        while True:
            pf_stats = ray_tpu.get(prefill.stats.remote())
            if (pf_stats["acked"] >= len(prompts)
                    or time.monotonic() > deadline):
                break
            time.sleep(0.1)
        dec_stats = ray_tpu.get(decode.stats.remote())
        outcomes = pf_stats["prefix_cache"]
    finally:
        colo.stop()
        try:
            ray_tpu.get(decode.stop.remote(), timeout=30.0)
        finally:
            ray_tpu.kill(prefill)
            ray_tpu.kill(decode)

    # all three cache outcomes exercised on the prefill tier
    assert outcomes["hits"] >= 1
    assert outcomes["partial_hits"] >= 1
    assert outcomes["misses"] >= 2
    assert pf_stats["reused_tokens"] > 0      # shared prefix amortized

    # no-full-copy accounting: the bytes that crossed the object plane
    # are EXACTLY the prompts' KV rows — not a slab, not a pool — and
    # on one host they all rode shm, never RPC
    expect = sum(_kv_bytes(len(p)) for p in prompts)
    assert pf_stats["published_bytes"] == expect
    assert dec_stats["kv_fetched_bytes"] == expect
    assert dec_stats["shm_bytes"] == expect
    assert dec_stats["rpc_bytes"] == 0
    assert dec_stats["transfers"] == len(prompts)
    assert dec_stats["adopted"] == len(prompts)

    # decode ticks never ran a prefill: the decode PROCESS's
    # _prefill_paged compile cache stayed flat at zero
    assert dec_stats["prefill_programs"] == 0

    # sender-owned chunk lifetime: every transfer was acked and freed
    assert pf_stats["acked"] == len(prompts)
    assert pf_stats["held_transfers"] == 0


def test_colocated_fallback_is_the_plain_engine_path(model):
    """No prefill tier configured: the router degrades to the colocated
    engine path — same tokens, zero transfers, zero KV bytes."""
    eng = _colocated_engine(model)
    router = DisaggRouter(colocated=eng, max_queue_depth=4)
    try:
        p = [21, 22, 23, 24, 25]
        direct = eng.generate(p, 5)
        routed = router.generate(p, 5)
        assert routed == direct
        st = router.stats()
        assert st["mode"] == "colocated"
        assert st["dispatched"] == 1 and st["shed"] == 0
        # the colocated path has no transfer plane to account
        assert eng.adopted == 0
    finally:
        eng.stop()


# -------------------------------------------------- admission control

def test_disagg_router_sheds_before_queue_is_unbounded(model):
    """A single decode slot + queue depth 1: concurrent arrivals past
    the bound are rejected with retry-after, and the router's pending
    high-water never exceeds capacity + depth."""
    eng = _colocated_engine(model, max_batch=1)
    router = DisaggRouter(colocated=eng, max_queue_depth=1,
                          retry_after_s=0.25)
    router.generate([1, 2, 3], 2)  # warm the compile cache
    n = 6
    results = {"ok": 0, "shed": 0}
    retry_hints = []
    lock = threading.Lock()

    def one(i):
        try:
            router.generate([1, 2, 3 + i], 8)
            with lock:
                results["ok"] += 1
        except RequestShedError as e:
            with lock:
                results["shed"] += 1
                retry_hints.append(e.retry_after_s)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        eng.stop()
    st = router.stats()
    assert results["shed"] >= 1                 # shedding engaged...
    assert results["ok"] >= 1                   # ...without starving
    assert results["ok"] + results["shed"] == n
    assert st["shed"] == results["shed"]
    # the bound that keeps queue depth finite: capacity (1) + depth (1)
    assert st["max_pending"] <= 2
    assert all(h == 0.25 for h in retry_hints)


def test_serve_router_sheds_with_max_queued_requests(disagg_cluster):
    """The generic Serve router enforces the same knob: a deployment
    with max_ongoing=1, max_queued=0 rejects concurrent submits with
    RequestShedError instead of queueing them."""
    import time as time_mod

    from ray_tpu import serve

    @serve.deployment(max_ongoing_requests=1, max_queued_requests=0)
    def sleepy(x):
        time_mod.sleep(0.5)
        return x

    handle = serve.run(sleepy.bind(), name="shed-app")
    try:
        results = {"ok": 0, "shed": 0}
        lock = threading.Lock()

        def one(i):
            try:
                resp = handle.remote(i)
                assert resp.result(timeout_s=30.0) == i
                with lock:
                    results["ok"] += 1
            except RequestShedError as e:
                assert e.retry_after_s > 0
                with lock:
                    results["shed"] += 1

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results["ok"] >= 1
        assert results["shed"] >= 1
        assert results["ok"] + results["shed"] == 4
    finally:
        serve.shutdown()


# ------------------------------------------------ load harness smoke

def test_burst_past_capacity_sheds_with_cause_and_is_recorded(model):
    """16 callers released together against 2 slots + queue depth 1:
    the overflow sheds with an attributed cause while the rest
    complete, nothing errors, the pending high-water holds at capacity
    + depth, and the flight recorder's report over exactly these
    requests names a tail owner and the slowest request's phases."""
    from ray_tpu.observability import requests as reqtrace

    eng = _colocated_engine(model, max_batch=2)
    router = DisaggRouter(colocated=eng, max_queue_depth=1)
    n = 16
    lock = threading.Lock()
    completed, shed_causes, errors = [], [], []
    start = threading.Barrier(n)

    def one(i):
        start.wait(timeout=60)
        try:
            toks = router.generate([1, 2, 3, 4 + i], 4)
            with lock:
                completed.append(len(toks))
        except RequestShedError as e:
            with lock:
                shed_causes.append(getattr(e, "cause", None))
        except Exception as e:  # noqa: BLE001 — the assertion below
            with lock:
                errors.append(repr(e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    try:
        router.generate([1, 2, 3, 4], 2)  # compile before the burst
        seq0 = reqtrace.store().seq()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        eng.stop()
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(completed) + len(shed_causes) == n
    assert completed and all(c == 4 for c in completed)
    assert shed_causes and all(shed_causes), shed_causes
    st = router.stats()
    assert st["shed"] == len(shed_causes)
    assert sum(st["sheds_by_cause"].values()) == len(shed_causes)
    # shedding engaged BEFORE queue depth became unbounded
    assert st["max_pending"] <= 2 + 1
    traces = reqtrace.store().summaries_since(seq0)
    assert len(traces) >= len(completed)
    assert "tail_owner" in reqtrace.p99_attribution(traces)
    slowest = max(traces, key=lambda s: s.get("total_ms", 0.0))
    assert slowest["phase_ms"]


# ----------------------------------------------- e2e surface check

def test_all_surfaces_report_consistent_numbers(disagg_cluster, capsys):
    """disagg_status() / CLI / /api/disagg / Prometheus / timeline
    markers all report the SAME transfer/shed numbers for one
    router+tiers workload."""
    import time as time_mod
    import urllib.request

    from ray_tpu.dashboard import DashboardServer
    from ray_tpu.scripts import cli
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    model = llama_init(CFG, jax.random.PRNGKey(0))
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    # capacity 1 + queue depth 0: one in-flight request trips the bound
    dec = DecodeServer(model, CFG, max_batch=1)
    router = DisaggRouter(decode=[dec], prefill=[pf], max_queue_depth=0,
                          affinity_tokens=BS)
    shared = [31, 32, 33, 34, 35, 36, 37, 38]
    try:
        for i in range(3):
            router.generate(shared + [90 + i], 3)
        # queue depth 0: a concurrent second request must shed. The
        # hold request retries until IT is the admitted one (a probe
        # racing ahead of it would otherwise shed the holder itself),
        # signals admission, and drains slowly so the slot stays
        # occupied while the main thread probes for the shed.
        admitted = threading.Event()

        def _hold():
            while True:
                try:
                    router.generate(shared, 8,
                                    on_first_token=admitted.set,
                                    token_sleep_s=0.25)
                    return
                except RequestShedError:
                    time_mod.sleep(0.05)

        hold = threading.Thread(target=_hold)
        hold.start()
        assert admitted.wait(30.0)
        shed_seen = 0
        deadline = time_mod.monotonic() + 30.0
        while time_mod.monotonic() < deadline and not shed_seen:
            try:
                router.generate(shared, 2)
            except RequestShedError:
                shed_seen = 1
        hold.join(timeout=60)
        assert shed_seen == 1
    finally:
        dec.stop()
    pf.publish_telemetry(force=True)
    dec.publish_telemetry(force=True)
    router.publish_telemetry(force=True)
    metrics_mod.flush()
    local = {"transfers": dec.stats()["transfers"],
             "fetched": dec.stats()["kv_fetched_bytes"],
             "shed": router.stats()["shed"],
             "dispatched": router.stats()["dispatched"]}

    # state API (fire-and-forget notify: poll until the final
    # snapshots land at the conductor)
    deadline = time_mod.monotonic() + 10.0
    while True:
        st = state.disagg_status()
        mine = st["decode"].get(dec.server_id)
        rt = st["routers"].get(router.router_id)
        if mine is not None and rt is not None \
                and mine.get("transfers") == local["transfers"] \
                and rt.get("shed") == local["shed"]:
            break
        assert time_mod.monotonic() < deadline, st
        time_mod.sleep(0.1)
    assert mine["kv_fetched_bytes"] == local["fetched"]
    assert st["prefill"][pf.server_id]["published_transfers"] \
        == local["transfers"]
    assert st["totals"]["transfers"] >= local["transfers"]
    totals = st["totals"]

    # CLI (same conductor snapshot)
    w = disagg_cluster
    host, port = w.conductor_address
    cli.main(["disagg", "--json", "--address", f"{host}:{port}"])
    cli_out = json.loads(capsys.readouterr().out)
    assert cli_out["totals"] == totals

    # dashboard /api/disagg
    srv = DashboardServer(w.conductor_address, port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/api/disagg",
                                    timeout=10.0) as r:
            dash = json.loads(r.read())
    finally:
        srv.stop()
    assert dash["totals"] == totals
    transfer_events = [e for e in dash["events"]
                       if e.get("kind") == "kv_transfer"
                       and e.get("server") == dec.server_id]
    assert len(transfer_events) == local["transfers"]
    # event payload bytes match the prefill tier's published bytes
    assert sum(e["bytes"] for e in transfer_events) \
        == st["prefill"][pf.server_id]["published_bytes"]

    # Prometheus: the disagg families exist and cover this workload
    prom = state.prometheus_metrics()
    assert "ray_tpu_disagg_kv_bytes_total" in prom
    assert "ray_tpu_disagg_transfers_total" in prom
    assert "ray_tpu_serve_shed_total" in prom
    assert "ray_tpu_disagg_queue_depth" in prom
    transfer_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_disagg_transfers_total"))
    assert transfer_total >= local["transfers"]
    shed_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_serve_shed_total{"))
    assert shed_total >= local["shed"]

    # merged timeline: one instant marker per transfer + the shed
    trace = state.timeline(merged=True)
    markers = [e for e in trace if e.get("cat") == "disagg"
               and e.get("tid") == "kv_transfer"
               and e.get("args", {}).get("server") == dec.server_id]
    assert len(markers) == local["transfers"]
    assert all(m["ph"] == "i" and m["pid"] == "disagg" for m in markers)
    sheds = [e for e in trace if e.get("cat") == "disagg"
             and e.get("tid") == "shed"
             and e.get("args", {}).get("router") == router.router_id]
    assert len(sheds) == local["shed"]
