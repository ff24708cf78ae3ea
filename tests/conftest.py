"""Test fixtures — analog of the reference's python/ray/tests/conftest.py
(ray_start_regular / ray_start_cluster built on cluster_utils.Cluster).

TPU-specific: JAX tests run on a virtual 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8), the unit-test analog of
the reference's fake-GPU mode (SURVEY.md §4)."""
from __future__ import annotations

import os

# Tests run on a virtual 8-device CPU mesh, on any machine: both pins are
# set before jax is imported (the device count is read when the backend
# starts) and are inherited by every worker process a test spawns. A
# machine with a chip would otherwise hand it to whichever test process
# touched JAX first.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy tests excluded from the tier-1 "
        "`-m 'not slow'` smoke run")
    config.addinivalue_line(
        "markers", "chaos: scripted fault-injection scenarios "
        "(ray_tpu.resilience.chaos); the tier-1-safe smoke subset runs "
        "on a virtual cluster, heavier replays are also marked slow — "
        "select with `-m chaos`")
    config.addinivalue_line(
        "markers", "weights: live weight fabric scenarios "
        "(ray_tpu.weights); the tier-1-safe smoke subset runs on a "
        "virtual cluster with log_to_driver=0 — select with "
        "`-m weights`")
    config.addinivalue_line(
        "markers", "kvcache: paged KV prefix-cache scenarios "
        "(ray_tpu.models.kvcache + the batching engine); everything is "
        "tier-1-safe on CPU, the e2e surface check runs on a virtual "
        "cluster with log_to_driver=0 — select with `-m kvcache`")
    config.addinivalue_line(
        "markers", "mpmd: MPMD pipeline-parallelism scenarios "
        "(ray_tpu.mpmd: stage-gangs, 1F1B schedule, activation "
        "channels); the tier-1-safe smoke subset runs on a virtual "
        "cluster with log_to_driver=0 — select with `-m mpmd`")
    config.addinivalue_line(
        "markers", "online: online learning loop scenarios "
        "(ray_tpu.online: sampler/learner split, rollout buffer, "
        "delta weight publication); the tier-1-safe smoke subset runs "
        "on a module-scoped virtual-slice cluster with "
        "log_to_driver=0 — select with `-m online`")
    config.addinivalue_line(
        "markers", "disagg: disaggregated prefill/decode serving "
        "scenarios (serve/disagg.py: KV-block streaming over the "
        "chunk fabric, router admission control, the open-loop load "
        "harness); everything is tier-1-safe on CPU on a "
        "module-scoped cluster with log_to_driver=0 — select with "
        "`-m disagg`")
    config.addinivalue_line(
        "markers", "autoscale: SLO-driven serving-autoscaler scenarios "
        "(serve/autoscale.py: sliding-window signals, hysteresis "
        "policy, scale-up/drain against real disagg tiers); everything "
        "is tier-1-safe on CPU, the e2e surface check runs on a "
        "module-scoped cluster with log_to_driver=0 — select with "
        "`-m autoscale`")
    config.addinivalue_line(
        "markers", "servefault: serving-plane fault-tolerance "
        "scenarios (serve/disagg.py request failover + "
        "serve/autoscale.py tier self-healing + serving chaos ops): "
        "replica-death replay bit-identity, deadline/failover shed "
        "causes, breaker, drain/death race; everything is tier-1-safe "
        "on CPU, cluster tests run on a module-scoped cluster with "
        "log_to_driver=0 — select with `-m servefault`")
    config.addinivalue_line(
        "markers", "lora: multi-tenant LoRA serving scenarios "
        "(serve/lora.py paged adapter pool + cross-tenant batched "
        "decode + tenant-aware routing): pool refcount/LRU units, "
        "mixed-batch and base-slot bit-identity, tenant KV isolation, "
        "hot-swap and page-in no-stall checks; everything is "
        "tier-1-safe on CPU, cluster tests run on a module-scoped "
        "log_to_driver=0 cluster — select with `-m lora`")
    config.addinivalue_line(
        "markers", "speculate: speculative decoding + int8 KV "
        "scenarios (models/engine.py verify ticks + models/kvcache.py "
        "quantized pool): greedy bit-identity vs the unspeculated "
        "engine (full/partial/zero acceptance), refcount rollback "
        "leak-freedom, int8 pool equivalence + capacity doubling, "
        "disagg + LoRA mixed-batch paths; everything is tier-1-safe "
        "on CPU, the e2e surface check runs on a module-scoped "
        "log_to_driver=0 cluster — select with `-m speculate`")
    config.addinivalue_line(
        "markers", "gateway: OpenAI-compatible HTTP front-door "
        "scenarios (serve/gateway.py + serve/qos.py over REAL "
        "sockets): protocol errors as OpenAI error bodies, per-tenant "
        "token-bucket 429s with Retry-After, SSE-vs-non-streaming "
        "parity bit-identical to the engine oracle, interactive-"
        "preempts-batch resume identity, client-disconnect reaping, "
        "deadline propagation; everything is tier-1-safe on CPU, the "
        "telemetry surface check runs on a module-scoped "
        "log_to_driver=0 cluster — select with `-m gateway`")
    config.addinivalue_line(
        "markers", "requesttrace: per-request flight-recorder "
        "scenarios (observability/requests.py: phase-stamped trace "
        "spans through gateway/QoS/router/prefill/KV-transfer/decode, "
        "tail-based retention, p99 phase attribution, "
        "failover/preempt replay nesting, one-set-of-numbers across "
        "state API == CLI == dashboard == Prometheus == timeline); "
        "everything is tier-1-safe on CPU, cluster tests run on a "
        "module-scoped cluster with log_to_driver=0 — select with "
        "`-m requesttrace`")
    config.addinivalue_line(
        "markers", "kvplane: global-KV-plane scenarios "
        "(serve/kvplane.py tiered prefix cache: HBM -> host-arena "
        "spill/re-adopt bit-identity, tier-3 chunk-fabric "
        "publish/adopt, conductor prefix-directory atomic "
        "commit/TTL-reap/holder-death fallback, namespace isolation "
        "across tiers, eviction-storm chaos absorption, "
        "one-set-of-numbers across state API == CLI == dashboard == "
        "Prometheus == timeline); everything is tier-1-safe on CPU, "
        "cluster tests run on a module-scoped cluster with "
        "log_to_driver=0 — select with `-m kvplane`")
    config.addinivalue_line(
        "markers", "oracle: step-time oracle scenarios "
        "(observability.roofline: ICI/DCN roofline prediction, "
        "flight-recorder validation + calibration fit, bench "
        "regression attribution); everything is tier-1-safe on CPU, "
        "cluster tests run on a module-scoped cluster with "
        "log_to_driver=0 — select with `-m oracle`")


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Let go of what a test file compiled once the file is done. A
    worker of the tier-1 run goes through dozens of files, every program
    it compiles for the CPU keeps three mappings of machine code and data
    a kernel until JAX's caches drop it, and Linux gives a process 65,530
    mappings (`vm.max_map_count`): the worker that passes them dies
    inside its next compile (PR 49: twice in
    `test_yardstick_smallthinker.py`, whose eager decode compiles a
    program a position, at 62,000 mappings of which 61,000 were such
    triplets; 200 small programs are 3,700 mappings, and
    `jax.clear_caches()` gives all of them back)."""
    yield
    import gc
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        jax.clear_caches()
        gc.collect()


def _sweep_leaked_shm():
    """Chaos/kill tests SIGKILL workers, which cannot unlink their shm
    arena segments; sweep after every cluster so a leak in one test
    cannot degrade (or fail) the rest of the tier-1 run. Redundant with
    ray_tpu.shutdown()'s own sweep on the happy path — this one also
    runs when shutdown() raised before reaching its sweep."""
    from ray_tpu._private.object_store import cleanup_leaked_segments

    try:
        cleanup_leaked_segments()
    except Exception:  # noqa: BLE001 — sweep is best-effort
        pass


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()
    _sweep_leaked_shm()


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-scoped cluster for cheap tests."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()
    _sweep_leaked_shm()


@pytest.fixture
def cpu_mesh8():
    """8-device CPU mesh for sharding tests."""
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"expected >=8 virtual cpu devices, got {devices}"
    yield devices[:8]
