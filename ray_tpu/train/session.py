"""Train session: the API a `train_fn` sees while running under a trainer.

Mirrors the reference's _TrainSession
(python/ray/train/_internal/session.py — report :661, get_checkpoint :748,
get_dataset_shard :1054) with the same thread-local access pattern:
`ray_tpu.train.report(metrics, checkpoint=...)` from anywhere inside the
training function.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from .checkpoint import Checkpoint

_local = threading.local()


@dataclass
class TrainContext:
    world_size: int = 1
    rank: int = 0
    experiment_name: str = "default"
    trial_dir: str = ""
    dataset_shards: Dict[str, Any] = field(default_factory=dict)
    latest_checkpoint: Optional[Checkpoint] = None
    # rendezvous namespace for this gang (unique per fit); consumed by
    # parallel.distributed.setup_jax_distributed
    jax_dist_key: Optional[str] = None
    # multi-slice identity (ScalingConfig.num_slices > 1): which TPU
    # slice this rank's host belongs to; slice_map is filled in by
    # setup_jax_distributed after the slice rendezvous
    slice_id: Optional[int] = None
    num_slices: int = 1
    slice_map: Optional[Dict[int, Any]] = None
    # flight-recorder identity of this fit (observability.StepTimer
    # records ship to the conductor under this key)
    run_id: str = ""
    # restart generation (0 = first attempt); the trainer's retry loop
    # bumps it and the chaos harness scopes scripted faults to it
    attempt: int = 0
    # set by the trainer: called with (metrics, checkpoint)
    _report_fn: Optional[Callable[[Dict[str, Any], Optional[Checkpoint]],
                                  None]] = None
    _stop_requested: bool = False
    # per-rank step clock (observability.step_timer) the trainer creates;
    # TrainStep and report() feed it, users reach it via get_step_timer()
    _step_timer: Optional[Any] = None
    # the active preemption notice (conductor `resilience` pubsub): a
    # host this run touches announced it is going away — checkpoint now
    _preemption: Optional[Dict[str, Any]] = None
    _grace_acked: bool = False
    # resilience.chaos.ChaosMonkey for this attempt (scripted faults
    # fire at the report() step boundary); None = no chaos configured
    _chaos: Optional[Any] = None
    _report_count: int = 0

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.rank

    def get_trial_dir(self) -> str:
        return self.trial_dir

    def get_slice_id(self) -> int:
        return 0 if self.slice_id is None else self.slice_id


def _set_session(ctx: Optional[TrainContext]) -> None:
    _local.ctx = ctx


def _get_session() -> Optional[TrainContext]:
    return getattr(_local, "ctx", None)


def get_context() -> TrainContext:
    ctx = _get_session()
    if ctx is None:
        raise RuntimeError("No train session active — call inside a "
                           "train_fn run by JaxTrainer/Tuner")
    return ctx


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None, *,
           publish_weights: Any = None,
           weights_name: Optional[str] = None,
           weights_delta: bool = False,
           weights_version: Optional[int] = None) -> None:
    """Reference session.py:661. Reports metrics (and optionally a
    checkpoint) to the controlling trainer/tuner. Raises StopIteration-like
    control via the trainer if the trial was stopped (e.g. by a scheduler).

    report() is also the step boundary for the flight recorder: the
    session's StepTimer closes the current step here and its breakdown
    (data_wait/compile/device_step/checkpoint/report ms, tokens/sec, MFU)
    is merged into the reported metrics, so Result.metrics_history is
    self-describing. Time spent delivering the report itself (including
    synchronous checkpoint registration) lands in the NEXT step's
    "report"/"checkpoint" phase.

    ``publish_weights=params`` publishes this host's LOCAL shards of the
    pytree into the live weight fabric (ray_tpu.weights) as version
    `step` under ``weights_name`` (default: the experiment name) —
    serving replicas subscribed to that name hot-swap to it between
    decode ticks. Equivalent to ``weights.publish(params, step=step)``
    from inside the train_fn. Without a ``step`` metric the registry
    assigns latest+1 (single-host only — a multi-host gang must report
    a step so every host names the same version).
    ``weights_delta=True`` ships only the leaves whose content changed
    since this process's previous publish of the name (the online
    loop's per-step refresh path; full fallback when there is no usable
    base). ``weights_version`` overrides the version id (the online
    loop numbers publications consecutively so the staleness gauge
    counts PUBLICATIONS behind, decoupled from step numbering)."""
    ctx = get_context()
    metrics = dict(metrics)
    ctx._report_count += 1
    step = ctx._report_count
    explicit_step = False
    v = metrics.get("step")
    if v is not None:
        try:
            step = int(v)  # python/numpy/jax scalars alike
            explicit_step = True
        except (TypeError, ValueError):
            step = ctx._report_count
    timer = ctx._step_timer
    if timer is not None and timer.enabled:
        rec = timer.end_step()
        if rec is not None:
            for key in ("total_ms", "data_wait_ms", "bubble_wait_ms",
                        "compile_ms", "device_step_ms", "checkpoint_ms",
                        "report_ms", "other_ms", "tokens_per_sec",
                        "mfu"):
                if key in rec:
                    metrics.setdefault(
                        "step_time_ms" if key == "total_ms" else key,
                        rec[key])
    if publish_weights is not None:
        from ray_tpu import weights as _weights

        import time as _time

        t0 = _time.perf_counter()
        try:
            # version = the user's step metric when given (stable across
            # restarts); otherwise registry-assigned latest+1 — the
            # per-attempt report COUNT must not name versions, it resets
            # to 1 on every restart and would collide with (or sort
            # below) the previous attempt's publications
            _weights.publish(publish_weights,
                             name=weights_name or ctx.experiment_name,
                             step=(None if weights_version is not None
                                   else step if explicit_step else None),
                             version=weights_version,
                             run_id=ctx.run_id, delta=weights_delta)
        except ValueError as e:
            if "already committed" not in str(e):
                raise
            # a restarted attempt replaying an already-published step:
            # idempotent no-op, never a reason to kill the gang
        if timer is not None and timer.enabled:
            timer.record("report", _time.perf_counter() - t0)
    if ctx._report_fn is not None:
        if timer is not None and timer.enabled:
            import time as _time

            t0 = _time.perf_counter()
            try:
                ctx._report_fn(metrics, checkpoint)
            finally:
                timer.record(
                    "checkpoint" if checkpoint is not None else "report",
                    _time.perf_counter() - t0)
        else:
            ctx._report_fn(metrics, checkpoint)
    if checkpoint is not None and ctx._preemption is not None \
            and not ctx._grace_acked:
        # The grace flow: the preemption broadcast asked for a
        # step-fresh checkpoint NOW. An async save must actually be ON
        # DISK before we ack — expedite every in-flight writer and
        # block on this one's commit (the host may die right after the
        # grace window; a checkpoint still in the writer queue when it
        # does is no checkpoint at all).
        committed = True
        if hasattr(checkpoint, "future"):
            import time as _time

            from .async_checkpoint import expedite_all

            expedite_all()
            # bounded by the broadcast's own deadline: a stuck writer
            # must not pin the worker in report() past the grace window
            # it was trying to beat (then the gang would die mid-wait
            # with nothing committed AND nothing else attempted)
            deadline = ctx._preemption.get("deadline")
            budget = (max(1.0, float(deadline) - _time.time())
                      if deadline is not None
                      else float(ctx._preemption.get("grace_s") or 30.0))
            try:
                checkpoint.future.result(timeout=budget)
            except Exception:  # noqa: BLE001 — torn or still-writing
                committed = False  # save: don't ack; a later report
                #                    may still land one
            else:
                if ctx.world_size > 1 and ctx.trial_dir:
                    # workers mode persists async saves into
                    # {trial_dir}/pending from a commit hook — and hook
                    # failures are swallowed by design. A path still in
                    # the worker tempdir means the checkpoint dies with
                    # this host: acking it would record a grace
                    # checkpoint the restart cannot find.
                    import os as _os

                    pending_root = _os.path.abspath(_os.path.join(
                        ctx.trial_dir, "pending")) + _os.sep
                    committed = _os.path.abspath(
                        checkpoint.path).startswith(pending_root)
        if committed:
            ctx._grace_acked = True
            _report_resilience_event({
                "kind": "grace_checkpoint", "run_id": ctx.run_id,
                "rank": ctx.rank, "step": step,
                "node_id": ctx._preemption.get("node_id")})
    if ctx._chaos is not None:
        # scripted faults fire AFTER the report is delivered, so "kill
        # rank R at step S" leaves step S's metrics/checkpoint as the
        # deterministic resume point
        ctx._chaos.on_step(step)
    if ctx._stop_requested:
        raise StopTrial()


def get_step_timer():
    """The active session's flight-recorder StepTimer — use it to
    attribute data-loading or checkpoint time from inside a train_fn:

        with ray_tpu.train.get_step_timer().phase("data_wait"):
            batch = next(batches)

    Always returns a timer: outside a session (or with telemetry off) it
    is a shared disabled instance whose phase() is a no-op."""
    ctx = _get_session()
    if ctx is not None and ctx._step_timer is not None:
        return ctx._step_timer
    global _disabled_timer
    if _disabled_timer is None:
        from ray_tpu.observability.step_timer import StepTimer

        _disabled_timer = StepTimer(enabled=False)
    return _disabled_timer


_disabled_timer = None


def preemption_requested() -> Optional[Dict[str, Any]]:
    """Inside a train_fn: the active preemption notice, or None.

    When a host this run touches announces a maintenance event /
    preemption, the conductor broadcasts "checkpoint now, grace N
    seconds" and this returns the notice::

        {"node_id": ..., "grace_s": 30.0, "deadline": <unix ts>,
         "reason": "maintenance"}

    React by reporting a checkpoint promptly — the restarted run then
    resumes from a step-fresh checkpoint instead of the last periodic
    one. Outside a session this returns None."""
    ctx = _get_session()
    return ctx._preemption if ctx is not None else None


def _report_resilience_event(event: Dict[str, Any]) -> None:
    """Best-effort event to the conductor's resilience log (driver or
    worker process; silently a no-op without a cluster)."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        return
    try:
        w.conductor.notify("report_resilience_event", event)
    except Exception:  # noqa: BLE001 — telemetry only
        pass


def get_checkpoint() -> Optional[Checkpoint]:
    """Reference session.py:748 — resume checkpoint, if any."""
    return get_context().latest_checkpoint


def get_dataset_shard(name: str = "train"):
    """Reference session.py:1054 — this worker's dataset shard."""
    return get_context().dataset_shards.get(name)


class StopTrial(Exception):
    """Raised inside train_fn when the controller stops the trial (analog
    of the reference's session-finish control flow)."""
