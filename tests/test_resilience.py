"""ray_tpu.resilience: preemption-aware gangs, failure-domain
quarantine, and the chaos harness (ISSUE-4 acceptance surface).

The `chaos` marker tags scripted fault-injection scenarios; everything
here is the tier-1-safe smoke subset (virtual cluster, log_to_driver=0
per the established fixture pattern)."""
from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.resilience import (ChaosError, ChaosMonkey, ChaosPlan,
                                FailureDomainTracker, PreemptionWatcher,
                                backoff_delay, elastic_reform,
                                read_maintenance_event)

N_STEPS = 8


# ------------------------------------------------- failure-domain tracker

def test_tracker_threshold_decay_and_exempt():
    clock = [0.0]
    t = FailureDomainTracker(threshold=2.0, half_life_s=10.0,
                             exempt=("head",), clock=lambda: clock[0])
    assert t.score("h1") == 0.0 and not t.is_quarantined("h1")
    t.record("h1", "worker_death")
    assert not t.is_quarantined("h1")  # 1.0 < 2.0
    t.record("h1", "worker_death", detail="oom: greedy")
    assert t.is_quarantined("h1")
    # hysteresis: still quarantined at one half-life (score == thr/2)...
    clock[0] = 10.0
    assert t.score("h1") == pytest.approx(1.0)
    assert t.is_quarantined("h1")
    # ...released once the score decays below half the threshold
    clock[0] = 20.0
    assert not t.is_quarantined("h1")
    # the head is exempt from auto-quarantine no matter the score
    for _ in range(10):
        t.record("head", "worker_death")
    assert not t.is_quarantined("head")
    assert "head" not in t.excluded()


def test_tracker_drain_and_manual_quarantine():
    clock = [0.0]
    t = FailureDomainTracker(threshold=3.0, half_life_s=60.0,
                             clock=lambda: clock[0])
    t.begin_drain("h1", deadline=5.0, reason="preemption")
    assert t.is_draining("h1") and t.is_excluded("h1")
    assert not t.is_quarantined("h1")  # draining != quarantined
    clock[0] = 5.1  # grace window over: host serves again
    assert not t.is_excluded("h1")
    t.quarantine("h2", "operator")
    assert t.is_quarantined("h2")
    st = t.status()["domains"]["h2"]
    assert st["manual"] and st["quarantined"]
    assert t.clear("h2") and not t.is_quarantined("h2")
    # an operator pin beats the auto-quarantine exemption
    t2 = FailureDomainTracker(exempt=("head",), clock=lambda: clock[0])
    t2.quarantine("head", "operator")
    assert t2.is_quarantined("head") and "head" in t2.excluded()
    t2.clear("head")
    assert not t2.is_quarantined("head")


# ------------------------------------------------------- backoff / elastic

def test_backoff_delay_grows_and_caps():
    delays = [backoff_delay(a, base_s=1.0, cap_s=8.0, jitter_frac=0.0)
              for a in range(1, 7)]
    assert delays == [1.0, 2.0, 4.0, 8.0, 8.0, 8.0]
    # jitter stretches by at most the configured fraction
    d = backoff_delay(1, base_s=1.0, cap_s=8.0, jitter_frac=0.5,
                      rand=lambda: 1.0)
    assert d == pytest.approx(1.5)


def test_elastic_reform_flat_and_multislice():
    from ray_tpu.train import ScalingConfig, ShardingConfig

    # no floor -> never shrink
    assert elastic_reform(ScalingConfig(num_workers=4), None, 2) is None
    # flat gang shrinks to the available count, not below the floor
    sc = ScalingConfig(num_workers=4, min_workers=2)
    new_sc, _ = elastic_reform(sc, None, 3)
    assert new_sc.num_workers == 3
    assert elastic_reform(sc, None, 1) is None  # below the floor
    # multi-slice: shrink whole slices, dcn_dp follows
    sc = ScalingConfig(num_workers=8, num_slices=4, min_workers=2)
    sh = ShardingConfig(dcn_dp=4)
    new_sc, new_sh = elastic_reform(sc, sh, 5)
    assert (new_sc.num_workers, new_sc.num_slices) == (4, 2)
    assert new_sh.dcn_dp == 2
    # down to one slice lowers to a flat single-slice mesh
    new_sc, new_sh = elastic_reform(sc, sh, 3)
    assert (new_sc.num_workers, new_sc.num_slices) == (2, 1)
    assert new_sh.dcn_dp == 1 and not new_sh.is_hybrid


def test_pending_checkpoints_sort_attempt_major(tmp_path):
    """A restart resets the per-run report sequence, so the newest
    pending checkpoint must be picked attempt-major — a long first
    attempt must not out-sort a short second one."""
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.trainer import (_newest_pending_checkpoint,
                                       _persist_checkpoint)

    def make(attempt, seq):
        d = tmp_path / f"src-{attempt}-{seq}"
        d.mkdir()
        (d / "marker").write_text(f"{attempt}/{seq}")
        return _persist_checkpoint(Checkpoint(str(d)), str(tmp_path),
                                   rank=0, seq=seq, attempt=attempt)

    for seq in range(5):
        make(0, seq)          # attempt 0 reported 5 checkpoints...
    make(1, 0)                # ...attempt 1 only one before dying
    newest = _newest_pending_checkpoint(str(tmp_path))
    with open(os.path.join(newest.path, "marker")) as f:
        assert f.read() == "1/0"


# ----------------------------------------------------------- chaos plans

@pytest.mark.chaos
def test_chaos_plan_parse_and_matching(tmp_path):
    spec = json.dumps([
        {"action": "kill", "rank": 1, "at_step": 5},
        {"action": "preempt", "node": "h1", "grace_s": 3, "at_step": 2},
        {"action": "delay_heartbeats", "ms": 250},
        {"action": "bounce_conductor", "at_step": 7},
        {"action": "raise", "rank": 0, "at_step": 4, "attempt": "any"},
    ])
    plan = ChaosPlan.from_spec(spec)
    assert len(plan.actions) == 5 and bool(plan)
    assert plan.heartbeat_delay_s() == pytest.approx(0.25)
    # @file indirection
    p = tmp_path / "plan.json"
    p.write_text(spec)
    assert len(ChaosPlan.from_spec(f"@{p}").actions) == 5
    # matching: step+rank+attempt
    kill = plan.actions[0]
    assert kill.matches(5, 1, 0) and not kill.matches(5, 0, 0)
    assert not kill.matches(5, 1, 1)  # attempt-scoped by default
    anyat = plan.actions[4]
    assert anyat.matches(4, 0, 3)     # "attempt": "any"
    # external actions are the harness's job, not the monkey's
    assert [a.action for a in plan.external_actions(7)] == \
        ["bounce_conductor"]
    with pytest.raises(ValueError):
        ChaosPlan.from_spec(json.dumps([{"action": "meteor"}]))
    with pytest.raises(ValueError):
        ChaosPlan.from_spec(json.dumps([{"action": "kill"}]))  # no rank
    assert not ChaosPlan.from_spec(None) and not ChaosPlan.from_spec("")


@pytest.mark.chaos
def test_chaos_monkey_fires_once_and_reports():
    calls = []

    def fake_call(method, *args, **kwargs):
        calls.append((method, args))

    plan = ChaosPlan.from_spec(json.dumps(
        [{"action": "raise", "rank": 0, "at_step": 3}]))
    monkey = ChaosMonkey(plan, rank=0, attempt=0,
                         conductor_call=fake_call)
    monkey.on_step(1)
    monkey.on_step(2)
    with pytest.raises(ChaosError):
        monkey.on_step(3)
    monkey.on_step(3)  # fired already: exactly-once
    assert [m for m, _ in calls] == ["report_resilience_event"]
    # wrong rank never fires
    other = ChaosMonkey(plan, rank=1, attempt=0, conductor_call=fake_call)
    other.on_step(3)


# ----------------------------------------------------- preemption watcher

def test_maintenance_event_channel(tmp_path):
    spec = str(tmp_path / "maint.json")
    assert read_maintenance_event(spec) is None
    events = []
    w = PreemptionWatcher(events.append, spec=spec, poll_s=0.01)
    assert w.poll_once() is None
    with open(spec, "w") as f:
        json.dump({"grace_s": 7.5, "reason": "spot-reclaim"}, f)
    ev = w.poll_once()
    assert ev is not None and ev.grace_s == 7.5
    assert ev.reason == "spot-reclaim"
    assert w.poll_once() is None  # fires once per event
    os.unlink(spec)
    assert w.poll_once() is None  # channel cleared: re-armed
    open(spec, "w").close()       # empty file -> defaults apply
    ev = w.poll_once()
    assert ev is not None and ev.reason == "maintenance"
    assert events and events[0].grace_s == 7.5


# ------------------------------------------- conductor policy (no cluster)

@pytest.fixture
def handler(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_QUARANTINE_THRESHOLD", "1.0")
    from ray_tpu._private.conductor import ConductorHandler

    h = ConductorHandler({"CPU": 2.0}, str(tmp_path))
    h.register_node("flaky-host", {"CPU": 4.0}, None)
    yield h
    h._stopped = True


def test_conductor_preemption_drains_and_expires(handler):
    ev = handler.report_preemption(node_id="flaky-host", grace_s=0.25,
                                   reason="test")
    assert ev["kind"] == "preemption" and ev["grace_s"] == 0.25
    st = handler.get_resilience_status()
    assert st["excluded"] == ["flaky-host"]
    assert st["domains"]["flaky-host"]["draining"]
    assert st["counters"]["preemption"] == 1
    # schedulable capacity omits the draining host
    assert handler.schedulable_resources() == {"CPU": 2.0}
    time.sleep(0.3)
    assert handler.get_resilience_status()["excluded"] == []


def test_conductor_quarantine_excludes_from_leases_and_bundles(handler):
    from ray_tpu._private.conductor import WorkerRecord

    # an unexpected worker death on flaky-host crosses threshold 1.0
    dead = WorkerRecord(worker_id="w1", node_id=handler._head_node_id,
                        lease_node_id="flaky-host",
                        death_cause="oom: greedy")
    handler._on_worker_death(dead)
    st = handler.get_resilience_status()
    assert "flaky-host" in st["excluded"]
    assert st["domains"]["flaky-host"]["quarantined"]
    assert st["counters"]["worker_death"] == 1
    assert st["counters"]["quarantine"] == 1
    # gang formation: 3x1CPU STRICT_PACK fit only flaky-host (head has
    # 2) -> infeasible while quarantined, feasible after clearing
    with pytest.raises(ValueError):
        handler.create_placement_group([{"CPU": 1.0}] * 3, "STRICT_PACK")
    # lease grants: a 3-CPU lease can only come from flaky-host
    with pytest.raises(TimeoutError):
        handler.lease_worker({"CPU": 3.0}, timeout=0.3)
    assert handler.clear_quarantine("flaky-host")
    handler.create_placement_group([{"CPU": 1.0}] * 3, "STRICT_PACK")
    # EXPECTED deaths (ray_tpu.kill / node teardown) never charge
    gone = WorkerRecord(worker_id="w2", node_id=handler._head_node_id,
                        lease_node_id="flaky-host", expected_death=True)
    handler._on_worker_death(gone)
    assert handler.get_resilience_status()["excluded"] == []


def test_resilience_timeline_markers():
    from ray_tpu.observability.timeline import (merged_chrome_trace,
                                                resilience_trace_events)

    events = [{"kind": "preemption", "ts": 10.0, "node_id": "h1",
               "grace_s": 5.0},
              {"kind": "restart", "ts": 11.0, "name": "run",
               "attempt": 1},
              {"ts": None, "kind": "dropped"}]
    trace = resilience_trace_events(events)
    assert len(trace) == 2
    assert trace[0]["ph"] == "i" and trace[0]["cat"] == "resilience"
    assert trace[0]["name"] == "preemption:h1"
    assert trace[0]["args"]["grace_s"] == 5.0
    merged = merged_chrome_trace([], [], [], events)
    assert {e["tid"] for e in merged} == {"preemption", "restart"}


# ----------------------------------------- trainer retry loop (satellite)

_FAIL_COUNTS: dict = {}


def _flaky_then_ok(cfg):
    from ray_tpu.train import report

    key = cfg["key"]
    _FAIL_COUNTS[key] = _FAIL_COUNTS.get(key, 0) + 1
    if _FAIL_COUNTS[key] <= int(cfg.get("failures", 2)):
        raise RuntimeError(f"boom {_FAIL_COUNTS[key]}")
    report({"ok": 1, "attempts": _FAIL_COUNTS[key]})


def test_fit_retries_with_backoff_then_succeeds(tmp_path, monkeypatch):
    from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    monkeypatch.setenv("RAY_TPU_RESTART_BACKOFF_BASE_S", "0.01")
    monkeypatch.setenv("RAY_TPU_RESTART_BACKOFF_MAX_S", "0.05")
    t0 = time.monotonic()
    result = JaxTrainer(
        _flaky_then_ok, train_loop_config={"key": "retry", "failures": 2},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=3))).fit()
    assert result.error is None and result.metrics["attempts"] == 3
    assert time.monotonic() - t0 >= 0.02  # backoff actually slept
    # exhausted budget surfaces the last error instead of hot-looping
    result = JaxTrainer(
        _flaky_then_ok, train_loop_config={"key": "give-up",
                                           "failures": 99},
        run_config=RunConfig(storage_path=str(tmp_path / "g"),
                             failure_config=FailureConfig(
                                 max_failures=1))).fit()
    assert isinstance(result.error, RuntimeError)


def _interrupting(cfg):
    raise KeyboardInterrupt


def test_fit_does_not_swallow_keyboard_interrupt(tmp_path):
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig

    with pytest.raises(KeyboardInterrupt):
        JaxTrainer(_interrupting,
                   run_config=RunConfig(
                       storage_path=str(tmp_path),
                       failure_config=FailureConfig(max_failures=-1))
                   ).fit()


# ------------------------------------- resume correctness (chaos-scripted)

def _expected_losses(n_steps: int):
    """The deterministic SGD-on-sum(w^2) trajectory _sgd_train_fn walks."""
    w, out = np.full(4, 5.0), []
    for _ in range(n_steps):
        out.append(float((w ** 2).sum()))
        w = w - 0.2 * w
    return out


def _sgd_train_fn(cfg):
    import tempfile
    import time as _t

    import numpy as _np

    from ray_tpu.train import (Checkpoint, get_checkpoint, get_context,
                               preemption_requested, report)
    from ray_tpu.train.checkpoint import load_pytree, save_pytree

    ctx = get_context()
    step, w = 0, _np.full(4, 5.0)
    ck = get_checkpoint()
    if ck is not None:
        st = load_pytree(ck.path)
        step, w = int(st["step"]), _np.asarray(st["w"])
    graced = False
    while step < int(cfg["n_steps"]):
        step += 1
        loss = float((w ** 2).sum())
        w = w - 0.2 * w
        ckpt = None
        want_ckpt = bool(cfg.get("checkpoint_every_step"))
        if preemption_requested() is not None and not graced:
            graced, want_ckpt = True, True
        if want_ckpt:
            d = tempfile.mkdtemp(prefix="sgd_ckpt_")
            save_pytree({"step": _np.int64(step), "w": w}, d)
            ckpt = Checkpoint(d)
        report({"step": step, "loss": loss,
                "world": ctx.get_world_size()}, checkpoint=ckpt)
        if step == cfg.get("await_preemption_after_step"):
            # the notice rides pubsub: the next step starts when it has
            # landed here, not after a sleep a loaded host outlasts
            deadline = _t.monotonic() + 60.0
            while preemption_requested() is None \
                    and _t.monotonic() < deadline:
                _t.sleep(0.01)
        if cfg.get("step_sleep"):
            _t.sleep(float(cfg["step_sleep"]))


@pytest.mark.chaos
def test_resume_matches_uninterrupted_run(tmp_path, monkeypatch):
    """Kill a run mid-training via the chaos harness: the restart must
    resume from the step-4 checkpoint (not from scratch) and walk the
    exact loss/step trajectory of an uninterrupted run from the same
    seed (checkpoint-restart correctness, end-to-end)."""
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig

    monkeypatch.setenv("RAY_TPU_RESTART_BACKOFF_BASE_S", "0.01")
    monkeypatch.setenv("RAY_TPU_CHAOS_PLAN", json.dumps(
        [{"action": "raise", "rank": 0, "at_step": 4}]))
    result = JaxTrainer(
        _sgd_train_fn,
        train_loop_config={"n_steps": N_STEPS,
                           "checkpoint_every_step": True},
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=2))).fit()
    assert result.error is None
    expected = _expected_losses(N_STEPS)
    steps = [m["step"] for m in result.metrics_history]
    # resumed exactly at the post-checkpoint step — no replay, no gap
    assert steps == list(range(5, N_STEPS + 1)), steps
    for m in result.metrics_history:
        assert m["loss"] == pytest.approx(expected[m["step"] - 1],
                                          rel=1e-12)
    assert result.metrics["loss"] == pytest.approx(expected[-1],
                                                   rel=1e-12)


def _await_resilience_count(kind: str, n: int) -> None:
    """Block (60 s at most) until the conductor has counted `n`
    resilience events of `kind`. The ranks of a gang without collectives
    run unsynchronised, and a loaded host starts one a second after
    another: a scripted scenario waits on what it depends on, it does
    not sleep."""
    import time as _t

    from ray_tpu.util import state

    deadline = _t.monotonic() + 60.0
    while state.resilience_status()["counters"].get(kind, 0) < n \
            and _t.monotonic() < deadline:
        _t.sleep(0.05)


def _async_grace_train_fn(cfg):
    """_sgd_train_fn with the grace checkpoint taken through an
    AsyncCheckpointer whose artificial write delay far exceeds the test
    budget — only the preemption-driven expedite path can commit it in
    time."""
    import tempfile
    import time as _t

    import numpy as _np

    from ray_tpu.train import (get_checkpoint, get_context,
                               preemption_requested, report)
    from ray_tpu.train import async_checkpoint as _ac
    from ray_tpu.train.session import _report_resilience_event

    ctx = get_context()
    # a rank is subscribed to the preemption notice before its train fn
    # starts: rank 0's step 2 fires it only when every rank is here
    _report_resilience_event({"kind": "rank_ready",
                              "rank": ctx.get_world_rank()})
    _await_resilience_count("rank_ready", ctx.get_world_size())
    ckpter = _ac.AsyncCheckpointer()
    ckpter._test_write_delay = float(cfg.get("write_delay", 0.0))
    step, w = 0, _np.full(4, 5.0)
    ck = get_checkpoint()
    if ck is not None:
        st = _ac.restore(ck.path)
        step, w = int(st["step"]), _np.asarray(st["w"])
    graced = False
    while step < int(cfg["n_steps"]):
        step += 1
        loss = float((w ** 2).sum())
        w = w - 0.2 * w
        ckpt = None
        if preemption_requested() is not None and not graced:
            graced = True
            d = tempfile.mkdtemp(prefix="agrace_")
            ckpt = ckpter.save(d, {"step": _np.int64(step), "w": w})
        report({"step": step, "loss": loss,
                "world": ctx.get_world_size()}, checkpoint=ckpt)
        if step == 2:
            # the notice rides pubsub: step 3 starts when it has landed
            # here, not after a sleep a loaded host outlasts
            deadline = _t.monotonic() + 60.0
            while preemption_requested() is None \
                    and _t.monotonic() < deadline:
                _t.sleep(0.01)
        if step == 5:
            # and the step-6 kill lands when every rank's grace
            # checkpoint is committed and acked, not while a loaded
            # host's writer is still at it
            _await_resilience_count("grace_checkpoint",
                                    ctx.get_world_size())
        if cfg.get("step_sleep"):
            _t.sleep(float(cfg["step_sleep"]))


@pytest.mark.chaos
def test_async_grace_checkpoint_commits_within_window(tmp_path,
                                                      monkeypatch):
    """Async-checkpoint grace flow (ISSUE-5 satellite): an in-flight
    AsyncCheckpointer save at preemption time is expedited and committed
    promptly — persisted into pending/ from the commit hook BEFORE the
    chaos kill lands — so the restart resumes from the grace checkpoint
    instead of scratch. The 60s artificial write delay guards both
    halves: without expedite the fit would block out the assert budget,
    without commit-time persistence the resume would start at step 1."""
    from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                               ScalingConfig)
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=4, _system_config={
        "log_to_driver": 0,
        "restart_backoff_base_s": 0.1,
        "restart_backoff_max_s": 0.2,
    })
    try:
        # the train fn waits for every rank to have started, for the
        # step-2 preemption notice to land and, before the kill step,
        # for the grace checkpoints' acks: the newest one is step 3's on
        # any machine
        monkeypatch.setenv("RAY_TPU_CHAOS_PLAN", json.dumps([
            {"action": "preempt", "node": "head", "grace_s": 15.0,
             "at_step": 2},
            {"action": "kill", "rank": 1, "at_step": 6},
        ]))
        t0 = time.monotonic()
        result = JaxTrainer(
            _async_grace_train_fn,
            train_loop_config={"n_steps": N_STEPS, "step_sleep": 0.15,
                               "write_delay": 60.0},
            scaling_config=ScalingConfig(num_workers=2,
                                         setup_jax_distributed=False),
            run_config=RunConfig(name="async-grace",
                                 storage_path=str(tmp_path),
                                 failure_config=FailureConfig(
                                     max_failures=2)),
            mode="workers").fit()
        elapsed = time.monotonic() - t0
        assert result.error is None
        # expedite really cut the 60s write delay short
        assert elapsed < 45.0, elapsed
        # the restart resumed from the grace checkpoint (taken at the
        # step after the preemption broadcast), never from scratch
        expected = _expected_losses(N_STEPS)
        assert result.metrics["step"] == N_STEPS
        for m in result.metrics_history:
            assert m["loss"] == pytest.approx(expected[m["step"] - 1],
                                              rel=1e-12)
        first_resumed = result.metrics_history[0]["step"]
        assert first_resumed == 4, first_resumed
        st = state.resilience_status()
        assert st["counters"].get("grace_checkpoint", 0) >= 1
    finally:
        ray_tpu.shutdown()


# ------------------------------ end-to-end chaos scenario (tier-1 accept)

@pytest.fixture
def chaos_cluster():
    """Small head (2 CPU) + a 4-CPU accounting host the gang lands on,
    with a hair-trigger quarantine threshold and fast backoff."""
    ray_tpu.init(num_cpus=2, _system_config={
        "log_to_driver": 0,
        "quarantine_threshold": 1.0,
        "restart_backoff_base_s": 0.3,
        "restart_backoff_max_s": 0.6,
    })
    w = ray_tpu._private.worker.global_worker
    w.conductor.call("register_node", "flaky-host", {"CPU": 4.0}, None,
                     timeout=10.0)
    yield w
    ray_tpu.shutdown()


@pytest.mark.chaos
def test_preempt_quarantine_elastic_restart_scenario(chaos_cluster,
                                                     tmp_path,
                                                     monkeypatch):
    """ISSUE-4 acceptance: preempt one host with a grace window mid-run
    -> grace checkpoint taken -> host quarantined (visible in
    resilience_status()) -> gang restarts excluding it, elastically
    re-formed smaller -> final metrics match the uninterrupted
    trajectory; restart/preemption events appear in the merged timeline
    and the metrics counters."""
    from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                               ScalingConfig)
    from ray_tpu.util import state

    monkeypatch.setenv("RAY_TPU_CHAOS_PLAN", json.dumps([
        # maintenance notice for the gang's host, 10s grace, at step 2
        {"action": "preempt", "node": "flaky-host", "grace_s": 10.0,
         "at_step": 2},
        # ... then the host actually dies under rank 1 at step 5
        {"action": "kill", "rank": 1, "at_step": 5},
    ]))
    # 3 workers need 3 CPUs: STRICT_PACK can only land on flaky-host
    trainer = JaxTrainer(
        _sgd_train_fn,
        train_loop_config={"n_steps": N_STEPS, "step_sleep": 0.06,
                           "await_preemption_after_step": 2},
        scaling_config=ScalingConfig(num_workers=3, min_workers=2,
                                     setup_jax_distributed=False),
        run_config=RunConfig(name="chaos-accept",
                             storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=2)),
        mode="workers")
    result = trainer.fit()
    assert result.error is None

    # final metrics match the uninterrupted baseline trajectory
    expected = _expected_losses(N_STEPS)
    assert result.metrics["step"] == N_STEPS
    assert result.metrics["loss"] == pytest.approx(expected[-1],
                                                   rel=1e-12)
    for m in result.metrics_history:
        assert m["loss"] == pytest.approx(expected[m["step"] - 1],
                                          rel=1e-12)
    # the restart resumed from the grace checkpoint (taken at step 3,
    # the step after every rank has the preemption notice), not from
    # scratch and not from a later one
    first_resumed = result.metrics_history[0]["step"]
    assert first_resumed == 4, first_resumed
    # elastic re-form: capacity without flaky-host is the 2-CPU head
    assert result.metrics["world"] == 2
    assert trainer.scaling_config.num_workers == 2

    # host quarantined and visible in the state API
    st = state.resilience_status()
    assert "flaky-host" in st["excluded"]
    dom = st["domains"]["flaky-host"]
    assert dom["quarantined"] and dom["failures"] >= 1
    for kind in ("preemption", "worker_death", "quarantine", "restart",
                 "grace_checkpoint", "elastic_reform", "recovery",
                 "chaos"):
        assert st["counters"].get(kind, 0) >= 1, (kind, st["counters"])
    assert st["last_ttr_s"] is not None and st["last_ttr_s"] > 0

    # restart/preemption markers in the merged flight-recorder timeline
    trace = state.timeline(str(tmp_path / "merged.json"), merged=True)
    kinds = {e["tid"] for e in trace if e.get("cat") == "resilience"}
    assert {"preemption", "restart", "quarantine",
            "grace_checkpoint"} <= kinds, kinds

    # Prometheus surface: the event counter rode the metrics pipeline
    from ray_tpu.util import metrics as metrics_mod

    metrics_mod.flush()  # a notify: poll for what it carries
    deadline = time.monotonic() + 30.0
    while True:
        text = state.prometheus_metrics()
        if "ray_tpu_resilience_events_total" in text \
                and 'kind="preemption"' in text:
            break
        assert time.monotonic() < deadline, text[-2000:]
        time.sleep(0.05)


@pytest.mark.chaos
def test_resilience_status_cli_and_dashboard_payload(chaos_cluster,
                                                     capsys):
    """`python -m ray_tpu resilience-status` renders the view; the
    dashboard's /api/resilience payload is json-serializable as-is."""
    from ray_tpu.scripts import cli

    w = chaos_cluster
    w.conductor.call("quarantine_node", "flaky-host", "operator",
                     timeout=10.0)
    w.conductor.call("report_preemption", None, None, 5.0, "test",
                     timeout=10.0)
    cli.main(["resilience-status", "--address", "ignored:0"])
    text = capsys.readouterr().out
    assert "flaky-host" in text and "QUARANTINED" in text
    assert "counters:" in text
    cli.main(["resilience-status", "--address", "ignored:0", "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert "flaky-host" in parsed["excluded"]
    json.dumps(w.conductor.call("get_resilience_status", timeout=10.0))
    assert w.conductor.call("clear_quarantine", "flaky-host",
                            timeout=10.0)
