"""Flight recorder (ray_tpu.observability): step telemetry, MFU/FLOPs
accounting, gang aggregation + straggler detection, and the unified
merged timeline — the ISSUE-3 acceptance surface."""
from __future__ import annotations

import json
import time

import pytest

import ray_tpu
from ray_tpu.observability import (StepTimer, find_stragglers, flops, gang,
                                   step_timer as step_timer_mod,
                                   summarize_run)


# ------------------------------------------------------------------ flops

def test_peak_flops_table():
    class FakeTpu:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    class FakeCpu:
        device_kind = "cpu"
        platform = "cpu"

    assert flops.device_peak_flops(FakeTpu()) == 197e12
    # an unknown TPU generation is an error, not an assumed peak
    FakeTpu.device_kind = "TPU v9x"
    with pytest.raises(ValueError, match="TPU v9x"):
        flops.device_peak_flops(FakeTpu())
    # non-TPU backends get the documented nominal constant (nonzero so
    # off-silicon MFU series stay meaningful)
    assert flops.device_peak_flops(FakeCpu()) == \
        flops.NOMINAL_PEAK_FLOPS["cpu"] > 0


def test_analytic_param_count_matches_pytree():
    import jax

    from ray_tpu.models import GPT2Config, gpt2_init

    cfg = GPT2Config.tiny()
    analytic = flops.param_count(cfg)
    actual = flops.params_size(gpt2_init(cfg, jax.random.PRNGKey(0)))
    # analytic 6N ignores layernorm/bias vectors: within a few percent
    assert abs(actual - analytic) / actual < 0.05
    assert flops.train_flops_per_token(cfg) > 6 * analytic


def test_analytic_flops_llama_and_moe():
    from ray_tpu.models import LlamaConfig, MoEConfig

    llama = flops.train_flops_per_token(LlamaConfig.tiny())
    assert llama > 0
    moe = MoEConfig(num_layers=2, num_heads=4, num_kv_heads=2,
                    d_model=128, d_ff=256, vocab_size=512,
                    max_seq_len=128, num_experts=4, top_k=2)
    # active-expert accounting: top_k=2 of 4 experts, so the MoE layer
    # costs 2x a dense d_ff MLP, not 4x
    dense_like = LlamaConfig(num_layers=2, num_heads=4, num_kv_heads=2,
                             d_model=128, d_ff=2 * 256, vocab_size=512,
                             max_seq_len=128)
    assert flops.param_count(moe) == flops.param_count(dense_like)


def test_mfu_math():
    assert flops.mfu(1e12, 1.0, 2e12) == pytest.approx(0.5)
    assert flops.mfu(None, 1.0, 2e12) is None
    assert flops.mfu(1e12, 1.0, None) is None


# -------------------------------------------------------------- StepTimer

def test_step_timer_record_shape(monkeypatch):
    from ray_tpu._private import worker as worker_mod

    monkeypatch.setattr(worker_mod, "global_worker", None)
    t = StepTimer("run-x", rank=3, world_size=8, enabled=True)
    t.set_tokens_per_step(1000)
    t.set_flops_per_step(5e9)
    t.set_peak_flops(1e12)
    with t.phase("data_wait"):
        time.sleep(0.01)
    t.record("device_step", 0.05)
    rec = t.end_step()
    assert rec["step"] == 0 and rec["rank"] == 3
    assert rec["data_wait_ms"] >= 10
    assert rec["device_step_ms"] == pytest.approx(50.0)
    assert rec["total_ms"] >= rec["data_wait_ms"]
    assert rec["tokens"] == 1000 and rec["tokens_per_sec"] > 0
    # mfu uses device time: 5e9 / 0.05s / 1e12 = 0.1
    assert rec["mfu"] == pytest.approx(0.1)
    assert rec["t_end"] >= rec["t_start"]
    # no cluster: the record stays buffered locally
    t.flush()
    assert t._pending and t._pending[0] is rec
    t.record("device_step", 0.01)
    assert t.end_step()["step"] == 1


def test_step_timer_disabled_is_free(monkeypatch):
    """Telemetry-off guard (microbench counter, not wall-clock): the
    disabled path makes ZERO clock reads and allocates no per-call
    context managers."""
    calls = {"n": 0}
    real_now = step_timer_mod._now

    def counting_now():
        calls["n"] += 1
        return real_now()

    monkeypatch.setattr(step_timer_mod, "_now", counting_now)
    t = StepTimer("run-x", enabled=False)
    cms = {t.phase("data_wait") for _ in range(100)}
    assert len(cms) == 1  # one shared no-op CM, no allocation per call
    with t.phase("device_step"):
        pass
    for _ in range(100):
        t.record("device_step", 0.01)
        assert t.end_step() is None
    t.set_tokens_per_step(10)
    t.set_flops_per_step(1.0)
    t.close()
    assert calls["n"] == 0, "disabled StepTimer touched the clock"
    assert t._pending == []


def test_step_timer_env_kill_switch(monkeypatch):
    monkeypatch.setenv("RAY_TPU_STEP_TELEMETRY", "0")
    assert StepTimer("r").enabled is False
    monkeypatch.delenv("RAY_TPU_STEP_TELEMETRY")
    assert StepTimer("r").enabled is True


# ------------------------------------------------- gang aggregation (unit)

def _simulated_steps(n_steps=12, world=4, slow_rank=2, slow_factor=2.5):
    steps = {}
    for s in range(n_steps):
        steps[s] = {}
        for r in range(world):
            ms = 100.0 * (slow_factor if r == slow_rank else 1.0)
            steps[s][r] = {"step": s, "rank": r, "total_ms": ms,
                           "device_step_ms": ms * 0.9,
                           "t_start": s * 0.1, "t_end": s * 0.1 + ms / 1e3}
    return steps


def test_straggler_detection_flags_slow_rank():
    steps = _simulated_steps(slow_rank=2)
    assert find_stragglers(steps, k=1.5) == [2]
    # a single hiccup is NOT a straggler
    steps2 = _simulated_steps(slow_rank=1, slow_factor=1.0)
    steps2[5][1]["device_step_ms"] = 900.0
    assert find_stragglers(steps2, k=1.5) == []
    # below-threshold skew is not flagged either
    assert find_stragglers(_simulated_steps(slow_factor=1.3), k=1.5) == []
    # too few samples: a rank is never judged on < STRAGGLER_MIN_STEPS
    # counted steps (a noisy first step must not page anyone)
    assert find_stragglers(_simulated_steps(n_steps=2, slow_rank=0),
                           k=1.5) == []
    assert find_stragglers(_simulated_steps(n_steps=3, slow_rank=0),
                           k=1.5) == [0]


def test_summarize_run_shape():
    run = summarize_run(_simulated_steps(), k=1.5)
    assert run["world"] == 4
    assert run["last_step"] == 11
    assert run["stragglers"] == [2]
    assert set(run["per_rank"]) == {0, 1, 2, 3}
    assert run["per_rank"][2]["mean_ms"] > run["per_rank"][0]["mean_ms"]
    skew = run["last_step_skew"]
    assert skew["max_ms"] >= skew["median_ms"] >= skew["min_ms"] > 0
    assert skew["max_over_median"] == pytest.approx(2.5, rel=0.01)
    assert "total_ms" in run["last_step_breakdown"]


def test_step_skew_empty_and_single():
    assert gang.step_skew({}) == {}
    s = gang.step_skew({0: {"total_ms": 50.0}})
    assert s["min_ms"] == s["max_ms"] == 50.0


# --------------------------------------------- cluster (virtual) coverage

@pytest.fixture(scope="module")
def traced_cluster():
    """ONE cluster for every cluster-backed test in this module — the
    tier-1 suite is timeout-bound, so fixture spins are dots lost."""
    import os

    from ray_tpu.util import tracing

    prev = os.environ.get("RAY_TPU_TRACING")
    os.environ["RAY_TPU_TRACING"] = "1"
    tracing._enabled = True
    # log_to_driver off: mirrored worker stderr lines interleave with
    # pytest's dot progress in the tier-1 log and corrupt its dot count
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 _system_config={"log_to_driver": 0})
    yield
    ray_tpu.shutdown()
    tracing._enabled = False
    if prev is None:
        os.environ.pop("RAY_TPU_TRACING", None)
    else:
        os.environ["RAY_TPU_TRACING"] = prev


def _gpt2_train_fn(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import (GPT2Config, gpt2_init, gpt2_loss,
                                gpt2_partition_specs)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import TrainStep, get_step_timer, report

    mcfg = GPT2Config.tiny()
    mesh = make_mesh(MeshConfig(dp=-1))
    step = TrainStep(
        lambda p, b: gpt2_loss(p, b["tokens"], b["targets"], mcfg),
        optax.adamw(1e-3), mesh, gpt2_partition_specs(mcfg))
    state_ = step.init_state(gpt2_init(mcfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    for _ in range(3):
        with get_step_timer().phase("data_wait"):
            raw = rng.integers(0, mcfg.vocab_size, (8, 65), dtype=np.int32)
            batch = {"tokens": jnp.asarray(raw[:, :-1]),
                     "targets": jnp.asarray(raw[:, 1:])}
        state_, m = step(state_, batch)
        report({"loss": float(m["loss"])})


def test_train_run_flight_recorder(traced_cluster, tmp_path):
    """ISSUE-3 acceptance: a virtual-cluster train run produces the
    per-step breakdown in Result.metrics_history, a nonzero MFU for a
    ray_tpu.models model, train_progress() with the (simulated-slow)
    straggler flagged, and `timeline --merged` with driver spans, worker
    task events, and step markers in one chrome trace."""
    from ray_tpu.train import JaxTrainer, RunConfig
    from ray_tpu.util import state, tracing

    @ray_tpu.remote
    def warm(x):  # a real task so the merged trace has task events
        return x + 1

    with tracing.span("fit-section"):
        assert ray_tpu.get(warm.remote(1), timeout=60.0) == 2
        result = JaxTrainer(
            _gpt2_train_fn,
            run_config=RunConfig(name="obs-accept",
                                 storage_path=str(tmp_path))).fit()
    assert result.error is None
    assert len(result.metrics_history) == 3
    first, later = result.metrics_history[0], result.metrics_history[-1]
    for key in ("step_time_ms", "data_wait_ms", "compile_ms",
                "device_step_ms", "tokens_per_sec"):
        assert key in first, sorted(first)
    assert first["compile_ms"] > 0          # first execution compiles
    assert later["compile_ms"] == 0.0       # later steps do not
    assert later["device_step_ms"] > 0
    assert later["tokens_per_sec"] > 0
    assert later.get("mfu", 0) > 0          # nonzero MFU estimate

    # the run's records reached the conductor's gang aggregation
    deadline = time.monotonic() + 10.0
    progress = {}
    while time.monotonic() < deadline:
        progress = {k: v for k, v in state.train_progress().items()
                    if k.startswith("obs-accept/")}
        if progress and list(progress.values())[0]["steps_buffered"] >= 3:
            break
        time.sleep(0.2)
    assert progress, state.train_progress().keys()
    run = list(progress.values())[0]
    assert run["per_rank"][0]["steps"] == 3
    assert run["per_rank"][0]["mfu"] is not None

    # seed a straggler gang (simulated ranks reporting through the same
    # conductor path the StepTimer uses) and see it flagged
    w = ray_tpu._private.worker.global_worker
    for rank in range(4):
        ms = 250.0 if rank == 3 else 100.0
        w.conductor.call(
            "report_train_steps", "straggler-run", rank,
            [{"step": s, "rank": rank, "total_ms": ms,
              "device_step_ms": ms, "t_start": time.time(),
              "t_end": time.time() + ms / 1e3} for s in range(10)],
            timeout=10.0)
    run = state.train_progress("straggler-run")["straggler-run"]
    assert run["world"] == 4
    assert run["stragglers"] == [3]
    assert run["last_step_skew"]["max_over_median"] > 2.0

    # unified timeline: all three sources in one chrome trace file
    out = tmp_path / "merged.json"
    trace = state.timeline(str(out), merged=True)
    cats = {e.get("cat") for e in trace}
    assert {"task", "span", "train_step"} <= cats, cats
    loaded = json.loads(out.read_text())
    assert any(e["cat"] == "train_step" and e["ph"] == "X"
               for e in loaded)
    assert any(e["name"].startswith("submit:") for e in loaded
               if e["cat"] == "span")
    # step markers carry the breakdown for Perfetto's args pane
    step_ev = next(e for e in loaded if e["cat"] == "train_step"
                   and e["ph"] == "X")
    assert "device_step_ms" in step_ev["args"]


def test_train_status_cli_and_dashboard_route(traced_cluster, capsys):
    """`python -m ray_tpu train-status` renders the gang view; the
    dashboard exposes the same data at /api/train (JSON-safe keys)."""
    from ray_tpu.scripts import cli

    w = ray_tpu._private.worker.global_worker
    for rank in range(2):
        ms = 300.0 if rank == 1 else 100.0
        w.conductor.call(
            "report_train_steps", "cli-run", rank,
            [{"step": s, "rank": rank, "total_ms": ms,
              "device_step_ms": ms, "tokens_per_sec": 1000.0 / ms,
              "t_start": time.time(), "t_end": time.time()}
             for s in range(5)], timeout=10.0)
    cli.main(["train-status", "--address", "ignored:0", "--run", "cli-run"])
    text = capsys.readouterr().out
    assert "cli-run" in text and "STRAGGLER" in text
    cli.main(["train-status", "--address", "ignored:0", "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert "cli-run" in parsed

    # dashboard data layer (route handler minus aiohttp): the payload
    # must survive json.dumps exactly as json_response applies it (int
    # rank keys are coerced to strings by dumps itself)
    from ray_tpu.dashboard import _ClusterData

    d = _ClusterData(w.conductor_address)
    payload = d.train_progress()
    assert "cli-run" in payload
    roundtripped = json.loads(json.dumps(payload))
    assert "1" in roundtripped["cli-run"]["per_rank"]


def test_conductor_train_ring_buffers(traced_cluster):
    """Per-run step window and run-count eviction are bounded."""
    handler = ray_tpu._conductor.handler
    recs = [{"step": s, "total_ms": 1.0, "t_start": 0.0, "t_end": 0.0}
            for s in range(1100)]
    handler.report_train_steps("big-run", 0, recs)
    assert len(handler._train_runs["big-run"]["steps"]) == 1024
    assert min(handler._train_runs["big-run"]["steps"]) == 1100 - 1024
    for i in range(20):
        handler.report_train_steps(f"run-{i}", 0,
                                   [{"step": 0, "total_ms": 1.0}])
    assert len(handler._train_runs) <= handler._TRAIN_RUNS_KEPT


# ------------------------------------------------------- serve telemetry

def test_replica_metrics_pipeline():
    """ReplicaActor records latency/outcome into the util.metrics
    registry (the conductor-push Prometheus pipeline)."""
    import cloudpickle

    from ray_tpu.serve.replica import ReplicaActor

    def handler(x):
        if x == "boom":
            raise ValueError(x)
        return x * 2

    rep = ReplicaActor("rep-1", "dep", "app",
                       cloudpickle.dumps(handler),
                       cloudpickle.dumps(((), {})))
    assert rep.handle_request({}, [3], {}) == 6
    with pytest.raises(Exception):
        rep.handle_request({}, ["boom"], {})
    m = rep.get_metrics()
    assert m["num_requests"] == 2 and m["num_errors"] == 1
    from ray_tpu.util.metrics import _registry

    snap = {s["name"]: s for s in _registry.snapshot()}
    assert "serve_request_latency_ms" in snap
    assert sum(snap["serve_request_latency_ms"]["counts"].values()) >= 2
    ok_and_err = snap["serve_requests_total"]["values"]
    assert len(ok_and_err) >= 2  # ok + error series


def test_batch_occupancy_metrics():
    from ray_tpu.serve.batching import batch
    from ray_tpu.util.metrics import _registry

    @batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    def double(items):
        return [x * 2 for x in items]

    assert double(21) == 42
    snap = {s["name"]: s for s in _registry.snapshot()}
    assert "serve_batch_size" in snap
    assert "serve_batch_occupancy" in snap
    occ = list(snap["serve_batch_occupancy"]["values"].values())
    assert occ and 0 < occ[0] <= 1.0
