"""shardlint (ray_tpu.analysis): one seeded violation per rule asserting
the exact rule id fires, clean-pass assertions on every built-in dryrun
layout, and the CLI surface. Everything here is deviceless except the
from_mesh exact-DCN test, which uses the virtual 8-device CPU mesh under
RAY_TPU_VIRTUAL_SLICES."""
from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.analysis import (MeshLayout, abstract_mesh,
                              analyze_builtin_layouts, at_least,
                              check_collectives, check_specs, errors,
                              lint_source, scan_collectives)
from ray_tpu.parallel import MeshConfig, shard_map
from ray_tpu.parallel.multislice import (HybridMeshConfig,
                                         dcn_axis_factors,
                                         discover_slice_topology)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _rules(findings):
    return {f.rule for f in findings}


@pytest.fixture
def hybrid_layout():
    return MeshLayout.from_config(
        HybridMeshConfig(dp=-1, tp=2, dcn_dp=2), 8, num_slices=2)


# ------------------------------------------------- seeded shard violations


def test_unknown_axis_rule(hybrid_layout):
    fs = check_specs({"w": P("model")}, {"w": _sds((8, 8))},
                     hybrid_layout)
    assert _rules(fs) == {"unknown-axis"}
    assert fs[0].severity == "error"
    assert "MESH_AXES" in fs[0].fix_hint


def test_non_dividing_dim_rule(hybrid_layout):
    fs = check_specs({"w": P("tp")}, {"w": _sds((7, 4))}, hybrid_layout)
    assert _rules(fs) == {"non-dividing-dim"}


def test_rank_exceeds_ndim_rule(hybrid_layout):
    fs = check_specs({"w": P("dp", None, None)}, {"w": _sds((8, 8))},
                     hybrid_layout)
    assert _rules(fs) == {"rank-exceeds-ndim"}


def test_duplicate_axis_rule(hybrid_layout):
    fs = check_specs({"w": P("tp", "tp")}, {"w": _sds((8, 8))},
                     hybrid_layout)
    assert _rules(fs) == {"duplicate-axis"}


def test_replicated_large_param_rule(hybrid_layout):
    fs = check_specs({"w": P()}, {"w": _sds((8192, 8192))},
                     hybrid_layout)  # 256 MiB fp32, fully replicated
    assert _rules(fs) == {"replicated-large-param"}
    assert fs[0].severity == "warning"
    # axes of size 1 do not count as sharding: still a full copy each
    fs = check_specs({"w": P("sp")}, {"w": _sds((8192, 8192))},
                     hybrid_layout)
    assert "replicated-large-param" in _rules(fs)
    # genuinely sharded: clean
    fs = check_specs({"w": P("tp")}, {"w": _sds((8192, 8192))},
                     hybrid_layout)
    assert fs == []
    # typo'd axis: the unknown-axis error must NOT cascade into a
    # misdirecting "shard it" replication warning — the user tried
    fs = check_specs({"w": P("tpp")}, {"w": _sds((8192, 8192))},
                     hybrid_layout)
    assert _rules(fs) == {"unknown-axis"}


def test_clean_specs_pass(hybrid_layout):
    fs = check_specs({"w": P("fsdp", "tp"), "b": P()},
                     {"w": _sds((8, 8)), "b": _sds((8,))}, hybrid_layout)
    assert fs == []


# -------------------------------------------------------- DCN collectives


def test_tp_collective_over_dcn_warns_with_bytes():
    """A flat tp=8 mesh stretched over 2 slices routes the psum over DCN:
    the exact seeded violation the ISSUE names, with a nonzero
    bytes-over-DCN estimate."""
    layout = MeshLayout.from_config(MeshConfig(dp=1, tp=8), 8,
                                    num_slices=2, name="bad_tp")
    assert layout.dcn_factor("tp") == 2
    mesh = abstract_mesh(layout)
    fn = shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                   in_specs=P("tp"), out_specs=P(), check_vma=False)
    uses = scan_collectives(fn, _sds((1024,)))
    assert [u.primitive for u in uses] == ["psum"]
    assert uses[0].dcn_bytes(layout) > 0
    fs = check_collectives(layout, uses)
    assert _rules(fs) == {"collective-over-dcn"}
    assert fs[0].severity == "warning"
    assert "tp" in fs[0].message


def test_dcn_axis_collective_is_info_only():
    """psum over dp across slices is the hybrid design: info, not a
    warning."""
    layout = MeshLayout.from_config(HybridMeshConfig(dp=-1, dcn_dp=2), 8,
                                    num_slices=2)
    mesh = abstract_mesh(layout)
    fn = shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                   in_specs=P("dp"), out_specs=P(), check_vma=False)
    fs = check_collectives(layout, scan_collectives(fn, _sds((64,))))
    assert fs and all(f.severity == "info" for f in fs)


def test_dcn_axis_factors_flat_vs_hybrid():
    # hybrid: declared dcn sizes
    f = dcn_axis_factors(HybridMeshConfig(dp=-1, tp=2, dcn_dp=2), 8, 2)
    assert f["dp"] == 2 and f["tp"] == 1
    # flat tp stretched across slices: stride analysis catches it
    f = dcn_axis_factors(MeshConfig(dp=1, tp=8), 8, 2)
    assert f["tp"] == 2
    # flat dp-outermost: dp crosses, tp stays inside
    f = dcn_axis_factors(MeshConfig(dp=2, tp=4), 8, 2)
    assert f["dp"] == 2 and f["tp"] == 1
    # single slice: nothing crosses
    f = dcn_axis_factors(MeshConfig(dp=2, tp=4), 8, 1)
    assert all(v == 1 for v in f.values())
    # non-aligned spans: a tp line straddling the slice boundary is
    # still caught (dp=3 x tp=2 over 2 slices of 3 devices)
    f = dcn_axis_factors(MeshConfig(dp=3, tp=2), 6, 2)
    assert f["tp"] == 2 and f["dp"] == 2


def test_from_mesh_exact_dcn_factors(cpu_mesh8, monkeypatch):
    """MeshLayout.from_mesh counts slice membership on the real device
    array — exact for hybrid block assembly."""
    monkeypatch.setenv("RAY_TPU_VIRTUAL_SLICES", "2")
    topo = discover_slice_topology(cpu_mesh8)
    mesh = HybridMeshConfig(dp=-1, tp=2, dcn_dp=2).build(cpu_mesh8)
    layout = MeshLayout.from_mesh(mesh, topo)
    assert layout.dcn_factor("dp") == 2
    assert layout.dcn_factor("tp") == 1
    assert layout.dcn_axes() == ["dp"]
    # flat tp=8 over the same topology: tp crosses both slices
    flat = MeshConfig(dp=1, tp=8).build(cpu_mesh8)
    layout = MeshLayout.from_mesh(flat, topo)
    assert layout.dcn_factor("tp") == 2


# ------------------------------------------------------ AST lint fixtures


def test_blocking_in_async_rule():
    src = ("import time\n"
           "async def handler(self):\n"
           "    time.sleep(0.1)\n")
    fs = lint_source(src, "x.py")
    assert _rules(fs) == {"blocking-in-async"}
    assert fs[0].severity == "error" and "x.py:3" in fs[0].location


def test_blocking_in_async_queue_and_get():
    src = ("import queue\nimport ray_tpu\n"
           "async def h(self, ref):\n"
           "    q = queue.Queue()\n"
           "    a = q.get()\n"
           "    return ray_tpu.get(ref)\n")
    fs = lint_source(src, "x.py")
    assert len(fs) == 2
    assert _rules(fs) == {"blocking-in-async"}


def test_blocking_in_nested_sync_def_not_flagged():
    src = ("import time\n"
           "async def h(self):\n"
           "    def worker():\n"
           "        time.sleep(1)\n"
           "    return worker\n")
    assert lint_source(src, "x.py") == []


def test_host_sync_in_jit_rule():
    src = ("import jax\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    print('loss', x)\n"
           "    return x.item()\n")
    fs = lint_source(src, "x.py")
    assert _rules(fs) == {"host-sync-in-jit"}
    sev = {f.location: f.severity for f in fs}
    assert sev["x.py:4"] == "warning"  # print: trace-time only
    assert sev["x.py:5"] == "error"    # .item(): aborts tracing


def test_host_sync_in_jit_call_form():
    src = ("import jax\n"
           "def update(p):\n"
           "    return p.item()\n"
           "u = jax.jit(update)\n")
    assert _rules(lint_source(src, "x.py")) == {"host-sync-in-jit"}


def test_shardlint_suppression_comment():
    src = ("import time\n"
           "async def h(self):\n"
           "    time.sleep(0.1)  # shardlint: ok\n"
           "    time.sleep(0.2)  # shardlint: disable=blocking-in-async\n"
           "    time.sleep(0.3)  # shardlint: disable=unknown-axis\n")
    fs = lint_source(src, "x.py")
    assert len(fs) == 1 and "x.py:5" in fs[0].location


def test_undonated_pool_write_rule():
    """Seeded violations: copying writes into pool-named stacks — the
    .at[].set form and the bare dynamic_update_slice form — are
    flagged, while the same update inside a donate_argnums jit (the
    kvcache/lora write discipline) is exempt, donation-less jits
    included."""
    src = ("import functools\n"
           "import jax\n"
           "class Pool:\n"
           "    def write(self, bid, blk):\n"
           "        self._pool_k = self._pool_k.at[bid].set(blk)\n"
           "        self._pool_v = jax.lax.dynamic_update_slice(\n"
           "            self._pool_v, blk, (0, bid))\n"
           "@functools.partial(jax.jit, donate_argnums=(0,))\n"
           "def _ok(pool_k, bid, blk):\n"
           "    return jax.lax.dynamic_update_slice(pool_k, blk,\n"
           "                                        (0, bid))\n"
           "@functools.partial(jax.jit)\n"
           "def _undonated(pool_k, bid, blk):\n"
           "    return jax.lax.dynamic_update_slice(pool_k, blk,\n"
           "                                        (0, bid))\n")
    fs = [f for f in lint_source(src, "x.py")
          if f.rule == "undonated-pool-write"]
    assert {f.location for f in fs} == {"x.py:5", "x.py:6", "x.py:14"}
    assert all(f.severity == "warning" for f in fs)
    # non-pool receivers are not the rule's business
    clean = ("def f(cache, blk):\n"
             "    return cache.at[0].set(blk)\n")
    assert lint_source(clean, "y.py") == []


def test_undonated_pool_write_suppression():
    src = ("class P:\n"
           "    def w(self, b):\n"
           "        self._pool_k = self._pool_k.at[0].set(b)"
           "  # shardlint: disable=undonated-pool-write\n")
    assert lint_source(src, "x.py") == []


# ------------------------------------------- dryrun layouts analyze clean


def test_builtin_layouts_clean(monkeypatch):
    """Every dryrun layout (dcn_dp x tp, dcn_pp x fsdp, dp x pp, dp x sp,
    dp x ep) passes the analyzer with nothing above INFO — under the same
    RAY_TPU_VIRTUAL_SLICES the dryrun itself uses."""
    monkeypatch.setenv("RAY_TPU_VIRTUAL_SLICES", "2")
    results = analyze_builtin_layouts(8)
    assert set(results) == {"dcn_dp_tp", "dcn_pp_fsdp", "dp_pp", "dp_sp",
                            "dp_ep"}
    for name, findings in results.items():
        assert at_least(findings, "warning") == [], \
            f"layout {name} not clean: {[str(f) for f in findings]}"
    # the hybrid training layout reports its DCN traffic estimate
    assert any(f.rule == "collective-over-dcn"
               for f in results["dcn_dp_tp"])


def test_trainstep_rejects_bad_specs(cpu_mesh8):
    """TrainStep.init_state surfaces spec errors with the param named,
    before any compilation."""
    import optax

    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.trainer import TrainStep

    mesh = make_mesh(MeshConfig(dp=4, tp=2), devices=cpu_mesh8)
    step = TrainStep(lambda p, b: jnp.sum(p["w"]), optax.sgd(0.1), mesh,
                     {"w": P("model")})
    with pytest.raises(ValueError, match="unknown-axis"):
        step.init_state({"w": jnp.ones((8, 8))})


# ----------------------------------------------------------------- CLI


def test_cli_analyze_reports_and_exit_code(tmp_path, capsys):
    from ray_tpu.scripts.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\n"
                   "async def h():\n"
                   "    time.sleep(1)\n")
    with pytest.raises(SystemExit):
        main(["analyze", str(bad)])
    out = capsys.readouterr().out
    assert "blocking-in-async" in out and "1 error" in out

    clean = tmp_path / "clean.py"
    clean.write_text("import asyncio\n"
                     "async def h():\n"
                     "    await asyncio.sleep(1)\n")
    main(["analyze", str(clean)])  # exit 0 = no raise
    assert "0 error" in capsys.readouterr().out


def test_cli_analyze_json(tmp_path, capsys):
    from ray_tpu.scripts.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\n"
                   "async def h():\n"
                   "    time.sleep(1)\n")
    with pytest.raises(SystemExit):
        main(["analyze", "--json", str(bad)])
    import json

    findings = json.loads(capsys.readouterr().out)
    assert findings[0]["rule"] == "blocking-in-async"
    assert findings[0]["severity"] == "error"


# ------------------------------------------- serve async-blocking fixes


def test_router_pick_refuses_to_block_event_loop(monkeypatch):
    """The no-replica wait must not poll-sleep on a running event loop
    (the old behavior froze every coroutine for up to 30s)."""
    from ray_tpu.serve.handle import Router

    router = Router("d", "a")
    monkeypatch.setattr(Router, "_refresh",
                        lambda self, force=False: None)

    async def call():
        router._pick()

    with pytest.raises(RuntimeError, match="remote_async"):
        asyncio.run(call())
    # off-loop the same call waits, then times out cleanly
    monkeypatch.setattr(Router, "_PICK_TIMEOUT_S", 0.2)
    with pytest.raises(TimeoutError, match="no running replicas"):
        router._pick()


def test_router_assign_async_yields_loop(monkeypatch):
    """assign_async picks and submits without blocking the loop; the
    response carries the replica's ref."""
    from ray_tpu.serve.handle import RequestMetadata, Router

    class FakeMethod:
        def remote(self, meta, args, kwargs):
            return ("ref", meta["call_method"], tuple(args))

    class FakeReplica:
        handle_request = FakeMethod()

    router = Router("d", "a")
    monkeypatch.setattr(Router, "_refresh",
                        lambda self, force=False: None)
    monkeypatch.setattr(Router, "_start_metrics_push",
                        lambda self: None)
    router._replicas = [("r1", FakeReplica())]
    router._inflight = {"r1": 0}

    async def call():
        return await router.assign_async(
            RequestMetadata(call_method="m"), (1, 2), {})

    resp = asyncio.run(call())
    assert resp._object_ref == ("ref", "m", (1, 2))
    assert router._inflight["r1"] == 1  # held while the response lives
    resp._mark_done()
    assert router._inflight["r1"] == 0  # released on completion


def test_deployment_response_is_awaitable(monkeypatch):
    """`await resp` resolves off-loop (result + its dead-replica retry
    run on the executor, never blocking the caller's event loop)."""
    from ray_tpu.serve.handle import DeploymentResponse, Router

    router = Router("d", "a")
    resp = DeploymentResponse("fake-ref", router, "r1")
    monkeypatch.setattr(
        DeploymentResponse, "result",
        lambda self, timeout_s=None: ("resolved", timeout_s))

    async def call():
        return await resp

    assert asyncio.run(call()) == ("resolved", None)


def test_replica_drain_is_async():
    """prepare_for_shutdown is a coroutine (await asyncio.sleep drain) —
    the shardlint blocking-in-async fix for serve/replica.py."""
    import inspect

    from ray_tpu.serve.replica import ReplicaActor

    assert inspect.iscoroutinefunction(ReplicaActor.prepare_for_shutdown)

    import threading

    replica = ReplicaActor.__new__(ReplicaActor)
    replica._lock = threading.Lock()
    replica._inflight = 1  # never drains: exercises the await-sleep path
    replica._callable = object()

    async def run():
        return await replica.prepare_for_shutdown(timeout_s=0.2)

    assert asyncio.run(run()) is True


# ------------------------------------- cross-module invariants (v2 rules)


def test_lock_discipline_rule_fires():
    """Seeded race: one attribute mutated under `with self._lock` in one
    method and bare in another — the finding cites BOTH sites."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "    def locked(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n"
           "    def racy(self):\n"
           "        self._n += 1\n")
    fs = [f for f in lint_source(src, "x.py")
          if f.rule == "lock-discipline"]
    assert len(fs) == 1 and fs[0].severity == "warning"
    assert fs[0].location == "x.py:10"   # the unlocked site
    assert "x.py:8" in fs[0].message     # ... citing the locked one


def test_lock_discipline_constructor_and_convention_exempt():
    """Clean-after-fix shapes: __init__ writes (no concurrent aliases
    yet), `_locked`-suffixed helpers, and "caller holds self._lock"
    docstrings all count as disciplined — zero findings."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "    def _bump_locked(self):\n"
           "        self._n += 1\n"
           "    def helper(self):\n"
           "        \"\"\"Caller holds self._lock.\"\"\"\n"
           "        self._n += 1\n"
           "    def locked(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n")
    assert [f for f in lint_source(src, "x.py")
            if f.rule == "lock-discipline"] == []


def test_lock_discipline_condition_alias_counts_as_locked():
    """`with self._cv:` (a Condition wrapping the lock) and a local
    Condition alias are both the lock for discipline purposes."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.RLock()\n"
           "        self._cv = threading.Condition(self._lock)\n"
           "        self._n = 0\n"
           "    def a(self):\n"
           "        with self._cv:\n"
           "            self._n += 1\n"
           "    def b(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n")
    assert [f for f in lint_source(src, "x.py")
            if f.rule == "lock-discipline"] == []


def test_lock_discipline_suppression():
    """A deliberate lock-free write silences with `ok=lock-free`."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "    def locked(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n"
           "    def racy(self):\n"
           "        self._n += 1  # shardlint: ok=lock-free\n")
    assert [f for f in lint_source(src, "x.py")
            if f.rule == "lock-discipline"] == []


def test_undonated_jit_pool_arg_rule():
    """Donation auditor: a jitted function updating a pool-shaped ARG
    without donate_argnums is an O(pool)-copy warning; the donated twin
    is clean."""
    src = ("import functools\n"
           "import jax\n"
           "@jax.jit\n"
           "def write(pool, bid, blk):\n"
           "    return pool.at[bid].set(blk)\n"
           "@functools.partial(jax.jit, donate_argnums=(0,))\n"
           "def write_ok(pool, bid, blk):\n"
           "    return pool.at[bid].set(blk)\n")
    fs = [f for f in lint_source(src, "x.py")
          if f.rule == "undonated-jit-pool-arg"]
    assert len(fs) == 1 and fs[0].severity == "warning"
    assert fs[0].location == "x.py:5" and "'pool'" in fs[0].message
    # non-poolish args are not the rule's business even in a bare jit
    clean = ("import jax\n"
             "@jax.jit\n"
             "def f(state, x):\n"
             "    return state.at[0].set(x)\n")
    assert [f for f in lint_source(clean, "y.py")
            if f.rule == "undonated-jit-pool-arg"] == []


def test_undonated_jit_pool_arg_suppression():
    src = ("import jax\n"
           "@jax.jit\n"
           "def write(pool, bid, blk):\n"
           "    return pool.at[bid].set(blk)"
           "  # shardlint: disable=undonated-jit-pool-arg\n")
    assert [f for f in lint_source(src, "x.py")
            if f.rule == "undonated-jit-pool-arg"] == []


def _rule_ids(findings):
    return {f.rule for f in findings}


def test_env_knob_registry_rules(tmp_path):
    """Seeded violations for all three env-knob rules: a hot-loop parse
    without caching, two sites with different literal defaults, and a
    knob missing from the README text."""
    from ray_tpu.analysis import analyze_invariants

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import os\n"
        "def tick(stop):\n"
        "    while not stop.wait(1):\n"
        "        t = float(os.environ.get('RAY_TPU_T_INTERVAL', '1.0'))\n")
    (pkg / "b.py").write_text(
        "import os\n"
        "T = os.environ.get('RAY_TPU_T_INTERVAL', '2.0')\n")
    fs = analyze_invariants(str(pkg), readme_text="no knobs here")
    assert _rule_ids(fs) == {"env-knob-hot-path",
                             "env-knob-inconsistent-default",
                             "env-knob-undocumented"}
    assert all(f.severity == "warning" for f in fs)
    # documented + consistent + cached accessor: all three rules clean
    (pkg / "a.py").write_text(
        "from ray_tpu.util import envknobs\n"
        "def tick(stop):\n"
        "    while not stop.wait(1):\n"
        "        t = envknobs.get_float('RAY_TPU_T_INTERVAL', 1.0)\n")
    (pkg / "b.py").write_text("")
    fs = analyze_invariants(str(pkg),
                            readme_text="| `RAY_TPU_T_INTERVAL` |")
    assert fs == []


def test_env_knob_lru_cached_reader_is_cold(tmp_path):
    """An lru_cache'd reader is the other accepted cached-env shape."""
    from ray_tpu.analysis import analyze_invariants

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import functools, os\n"
        "@functools.lru_cache\n"
        "def interval():\n"
        "    return float(os.environ.get('RAY_TPU_T_INTERVAL', '1.0'))\n"
        "def tick(stop):\n"
        "    while not stop.wait(1):\n"
        "        t = interval()\n")
    fs = analyze_invariants(str(pkg),
                            readme_text="| `RAY_TPU_T_INTERVAL` |")
    assert fs == []


def test_env_knob_suppression(tmp_path):
    """Per-line suppressions silence invariant findings at the cited
    site, exactly like the per-file rules."""
    from ray_tpu.analysis import analyze_invariants

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import os\n"
        "def tick(stop):\n"
        "    while not stop.wait(1):\n"
        "        t = float(os.environ.get('RAY_TPU_T_INTERVAL', '1.0'))"
        "  # shardlint: disable=env-knob-hot-path\n")
    fs = analyze_invariants(str(pkg),
                            readme_text="| `RAY_TPU_T_INTERVAL` |")
    assert fs == []


def test_envknobs_accessor_caches_and_retunes(monkeypatch):
    """util/envknobs: the parse is memoized on the raw string — same
    raw returns the cached value, a changed env re-parses (live
    retuning and monkeypatching tests both keep working), and a bad
    value falls back to the call-site default."""
    from ray_tpu.util import envknobs

    monkeypatch.setenv("RAY_TPU_TEST_KNOB", "3")
    assert envknobs.get_int("RAY_TPU_TEST_KNOB", 7) == 3
    monkeypatch.setenv("RAY_TPU_TEST_KNOB", "5")
    assert envknobs.get_int("RAY_TPU_TEST_KNOB", 7) == 5
    monkeypatch.setenv("RAY_TPU_TEST_KNOB", "not-an-int")
    assert envknobs.get_int("RAY_TPU_TEST_KNOB", 7) == 7
    monkeypatch.delenv("RAY_TPU_TEST_KNOB")
    assert envknobs.get_int("RAY_TPU_TEST_KNOB", 7) == 7
    monkeypatch.setenv("RAY_TPU_TEST_BOOL", "yes")
    assert envknobs.get_bool("RAY_TPU_TEST_BOOL") is True
    monkeypatch.setenv("RAY_TPU_TEST_BOOL", "off")
    assert envknobs.get_bool("RAY_TPU_TEST_BOOL", True) is False


def test_cli_analyze_invariants_and_knob_table(tmp_path, capsys):
    """`analyze --invariants` folds cross-module findings into the
    report and exit code; `--knob-table --json` rides the wrapper
    object as env_knobs."""
    import json

    from ray_tpu.scripts.cli import main

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import os\n"
        "A = os.environ.get('RAY_TPU_T_KNOB', '1')\n"
        "B = os.environ.get('RAY_TPU_T_KNOB', '2')\n")
    with pytest.raises(SystemExit):
        main(["analyze", "--invariants", "--fail-on", "warning",
              str(pkg)])
    out = capsys.readouterr().out
    assert "env-knob-inconsistent-default" in out

    main(["analyze", "--invariants", "--knob-table", "--json",
          "--fail-on", "error", str(pkg)])
    payload = json.loads(capsys.readouterr().out)
    assert [r["knob"] for r in payload["env_knobs"]] == ["RAY_TPU_T_KNOB"]
    assert any(f["rule"] == "env-knob-inconsistent-default"
               for f in payload["findings"])
