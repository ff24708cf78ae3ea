"""JaxTrainer: the Train-equivalent (reference TorchTrainer →
DataParallelTrainer → BackendExecutor → WorkerGroup, SURVEY.md §3.4),
redesigned for single-controller SPMD on TPU meshes.

Where the reference runs N actor processes each owning one GPU and
rendezvousing an NCCL group (train/torch/config.py:64-117), a TPU host
drives all its chips from one process and XLA owns the collectives; the
N-process shape only reappears across hosts. So:

- mode="spmd" (default): train_fn runs in-process against the global mesh
  built from ShardingConfig. Zero serialization on the step path; the
  trainer contributes session plumbing (report/checkpoint/datasets),
  retention, and failure retries from the last checkpoint.
- mode="workers": ScalingConfig.num_workers actor processes (gang-placed
  via a STRICT_PACK placement group) each run train_fn with
  rank/world_size, mirroring BackendExecutor.start_training
  (backend_executor.py:427) for host-side (CPU) data/eval work and
  multi-host topologies. Worker reports stream back to the driver through
  the actor channel; rank 0's checkpoints win (reference semantics).

TrainStep builds the jitted SPMD update: shard params by the model's
PartitionSpec tree, batch by ('dp','fsdp'), donate the state, and let XLA
insert psum/reduce-scatter — the step the reference delegates to torch DDP
(train_loop_utils.py:158 prepare_model).
"""
from __future__ import annotations

import logging
import os
import time
import traceback
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .checkpoint import Checkpoint, CheckpointManager
from .config import (CheckpointConfig, FailureConfig, RunConfig,
                     ScalingConfig, ShardingConfig)
from .session import (StopTrial, TrainContext, _report_resilience_event,
                      _set_session)

logger = logging.getLogger(__name__)


@dataclass
class Result:
    """Reference air/result.py Result."""

    metrics: Dict[str, Any] = field(default_factory=dict)
    checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    path: str = ""
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    # the trial's hyperparameter config (reference Result.config —
    # populated by Tune, empty for plain Trainer fits)
    config: Dict[str, Any] = field(default_factory=dict)


def _subscribe_preemption(ctx: TrainContext):
    """Route the conductor's `resilience` pubsub into the session so
    `ray_tpu.train.preemption_requested()` sees the notice. Returns an
    unsubscribe token (None without a cluster)."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        return None

    def on_msg(msg, _ctx=ctx):
        if isinstance(msg, dict) and msg.get("kind") == "preemption":
            _ctx._preemption = msg
            # commit in-flight async saves promptly: the grace
            # checkpoint must land on disk inside the grace window, not
            # at gang completion (async_checkpoint grace flow)
            from .async_checkpoint import expedite_all

            expedite_all()

    w.subscribe_channel("resilience", on_msg)
    return (w, on_msg)


def _unsubscribe_preemption(token) -> None:
    if token is None:
        return
    try:
        token[0].unsubscribe_channel("resilience", token[1])
    except Exception:  # noqa: BLE001 — worker already torn down
        pass


def _persist_checkpoint(ck: Checkpoint, trial_dir: str, rank: int,
                        seq: int, attempt: int = 0) -> Checkpoint:
    """Move a reported checkpoint into `{trial_dir}/pending` NOW, on
    the worker, at report time — not when the gang run returns. A gang
    that dies mid-training (preemption, chaos kill) must leave its
    step-fresh checkpoints on shared storage for the restart to resume
    from; a checkpoint sitting in the dead worker's tempdir is lost.

    Names sort attempt-major: `seq` (the per-run report count) resets
    to 0 on every restart, so without the attempt prefix a long first
    attempt would out-sort a short second one and
    `_newest_pending_checkpoint` would resume attempt 3 from attempt
    1's stale state."""
    import shutil

    pending = os.path.join(trial_dir, "pending")
    os.makedirs(pending, exist_ok=True)
    dst = os.path.join(pending, f"{attempt:04d}-{seq:06d}-rank{rank}")
    if os.path.abspath(ck.path) == dst:
        return ck
    if os.path.exists(dst):
        shutil.rmtree(dst)
    try:
        os.replace(ck.path, dst)
    except OSError:  # cross-filesystem tempdir
        shutil.copytree(ck.path, dst)
        shutil.rmtree(ck.path, ignore_errors=True)
    ck.path = dst
    return ck


def _newest_pending_checkpoint(storage: str) -> Optional[Checkpoint]:
    """Latest worker-persisted checkpoint under `{storage}/pending`
    (names sort as {attempt:04d}-{seq:06d}-rank{r}, newest last)."""
    pending = os.path.join(storage, "pending")
    try:
        names = sorted(os.listdir(pending))
    except OSError:
        return None
    for name in reversed(names):
        path = os.path.join(pending, name)
        if os.path.isdir(path):
            return Checkpoint(path)
    return None


def _batch_tokens(batch) -> int:
    """Tokens per step from a batch pytree: the first leaf with >= 2
    dims contributes batch x seq (the LM convention throughout
    ray_tpu.models); 0 when no such leaf exists."""
    for leaf in jax.tree.leaves(batch):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 2:
            return int(shape[0]) * int(shape[1])
    return 0


class TrainStep:
    """Jitted SPMD train step over a mesh.

    loss_fn(params, batch) -> scalar; optimizer is an optax
    GradientTransformation. param_specs is a PartitionSpec pytree matching
    params (e.g. models.gpt2_partition_specs); data axes default to
    ('dp','fsdp') batch sharding.

    flops_per_token is the analytic MFU fallback (e.g.
    observability.flops.train_flops_per_token(cfg)) used when the
    backend cannot report per-execution FLOPs through cost_analysis();
    when XLA does report them, the exact number wins.
    """

    def __init__(self, loss_fn: Callable, optimizer, mesh: Mesh,
                 param_specs: Any, data_spec: P = P(("dp", "fsdp")),
                 flops_per_token: Optional[float] = None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.param_specs = param_specs
        self.data_spec = data_spec
        self.flops_per_token = flops_per_token

        def step(state, batch):
            def loss_of(p):
                return loss_fn(p, batch)

            loss, grads = jax.value_and_grad(loss_of)(state["params"])
            updates, opt_state = optimizer.update(
                grads, state["opt_state"], state["params"])
            import optax

            params = optax.apply_updates(state["params"], updates)
            new_state = {"params": params, "opt_state": opt_state,
                         "step": state["step"] + 1}
            return new_state, {"loss": loss}

        self._step = step
        self._jitted = None
        # AOT-compiled executable (jit.lower().compile()): built at first
        # execution when a flight-recorder session is active, both to
        # time compilation explicitly and to read XLA's cost_analysis
        # FLOPs for MFU.
        self._compiled = None

    @property
    def compiled(self):
        """The AOT-compiled step (`jax.stages.Compiled`: as_text(),
        cost_analysis()), or None before the first call made under a
        session whose step telemetry is on."""
        return self._compiled

    def init_state(self, params: Any) -> Dict[str, Any]:
        """Shard params onto the mesh and build optimizer state with
        matching sharding (optimizer moments inherit the param layout).

        The param specs are shardlint-validated against the mesh first:
        spec errors (unknown axis, non-dividing dim, duplicate axis)
        raise HERE with the offending param named, instead of surfacing
        as an opaque XLA error minutes into compilation; HBM warnings
        (large replicated params) go through `warnings.warn`."""
        from ray_tpu.analysis import (MeshLayout, check_specs, errors,
                                      format_report)

        findings = check_specs(self.param_specs, params,
                               MeshLayout.from_mesh(self.mesh))
        if errors(findings):
            raise ValueError(
                "invalid param sharding for this mesh:\n"
                + format_report(errors(findings)))
        if findings:
            import warnings

            warnings.warn("shardlint: " + format_report(findings),
                          stacklevel=2)
        params = jax.device_put(params, self._shardings(self.param_specs))
        with jax.set_mesh(self.mesh):
            opt_state = jax.jit(
                self.optimizer.init,
                in_shardings=(self._shardings(self.param_specs),))(params)
        return {"params": params, "opt_state": opt_state,
                "step": jax.device_put(np.int64(0))}

    def _shardings(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    def __call__(self, state, batch):
        from .session import _get_session

        ctx = _get_session()
        timer = ctx._step_timer if ctx is not None else None
        if timer is not None and not timer.enabled:
            timer = None
        first = self._jitted is None
        if first:
            batch_sh = jax.tree.map(
                lambda _: NamedSharding(self.mesh, self.data_spec), batch)
            self._jitted = jax.jit(self._step, donate_argnums=(0,),
                                   in_shardings=(None, batch_sh),
                                   )
        sharding = NamedSharding(self.mesh, self.data_spec)

        def put(x):
            # already resident with the right sharding -> zero-copy no-op;
            # avoids a host->HBM round trip on the hot step path.
            if getattr(x, "sharding", None) == sharding:
                return x
            return jax.device_put(x, sharding)

        t0 = time.perf_counter() if timer is not None else 0.0
        batch = jax.tree.map(put, batch)
        if timer is not None:
            timer.record("data_wait", time.perf_counter() - t0)
            if first:
                t0 = time.perf_counter()
                self._instrument(timer, state, batch)
                timer.record("compile", time.perf_counter() - t0)
            t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            if self._compiled is not None:
                try:
                    out = self._compiled(state, batch)
                except (TypeError, ValueError):
                    # signature/shape mismatch the AOT executable cannot
                    # absorb — raised BEFORE execution (buffers not yet
                    # donated), so retracing via jit is safe. Runtime
                    # failures (e.g. RESOURCE_EXHAUSTED) propagate: the
                    # state may already be donated and a retry would
                    # mask the real error with "Array has been deleted".
                    self._compiled = None
                    out = self._jitted(state, batch)
            else:
                out = self._jitted(state, batch)
        if timer is not None:
            # jax dispatch is async (TPU and CPU): without a sync here
            # device_step_ms would record ~1ms of dispatch while the
            # real step time leaked into other_ms and MFU exploded.
            # The sync is the flight recorder's measurement cost — it
            # trades host/device overlap for honest per-phase numbers,
            # and the telemetry-off path stays fully asynchronous.
            jax.block_until_ready(out)
            timer.record("device_step", time.perf_counter() - t0)
        return out

    def _instrument(self, timer, state, batch) -> None:
        """First-execution flight-recorder hookup: AOT-compile the step
        (so compile time is attributed explicitly, not smeared into the
        first device step), read XLA's per-execution FLOPs, and register
        tokens-per-step + the mesh's aggregate peak FLOPs for MFU."""
        from ray_tpu.observability import flops as _flops

        with jax.set_mesh(self.mesh):
            self._compiled = self._jitted.lower(state, batch).compile()
        per_device = _flops.compiled_flops(self._compiled)
        if per_device:
            # cost_analysis reports the PER-DEVICE partitioned
            # program; the MFU denominator aggregates peak over the
            # whole mesh, so scale the numerator to match (verified:
            # an 8-way sharded matmul reports 1/8th the flops)
            timer.set_flops_per_step(
                per_device * int(self.mesh.devices.size))
        timer.set_peak_flops(_flops.total_peak_flops(self.mesh.devices))
        tokens = _batch_tokens(batch)
        if tokens:
            timer.set_tokens_per_step(tokens)
            if timer.flops_per_step is None and self.flops_per_token:
                # analytic 6N fallback: cost_analysis was unavailable
                timer.set_flops_per_step(self.flops_per_token * tokens)


class JaxTrainer:
    """fit() runs train_fn under a session (reference BaseTrainer.fit,
    base_trainer.py:567)."""

    def __init__(self, train_fn: Callable[[Dict[str, Any]], None], *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 sharding_config: Optional[ShardingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 mode: str = "spmd"):
        self.train_fn = train_fn
        self.train_loop_config = dict(train_loop_config or {})
        self.scaling_config = scaling_config or ScalingConfig()
        self.sharding_config = sharding_config or ShardingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = dict(datasets or {})
        self.resume_from_checkpoint = resume_from_checkpoint
        self.mode = mode

    # ------------------------------------------------------------------ fit

    def fit(self) -> Result:
        storage = self.run_config.resolved_storage_path()
        os.makedirs(storage, exist_ok=True)
        cc = self.run_config.checkpoint_config
        manager = CheckpointManager(
            os.path.join(storage, "checkpoints"),
            num_to_keep=cc.num_to_keep,
            score_attribute=cc.checkpoint_score_attribute,
            score_order=cc.checkpoint_score_order)
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        latest = self.resume_from_checkpoint
        first_failure_ts: Optional[float] = None
        # chaos plans ride the env on the driver; workers get the spec
        # forwarded explicitly (their spawn env predates the plan)
        chaos_spec = os.environ.get("RAY_TPU_CHAOS_PLAN")
        while True:
            try:
                if self.mode == "workers" and \
                        self.scaling_config.num_workers > 1:
                    result = self._fit_workers(manager, latest, storage,
                                               attempt, chaos_spec)
                else:
                    result = self._fit_spmd(manager, latest, storage,
                                            attempt, chaos_spec)
                result.path = storage
                if attempt and first_failure_ts is not None:
                    # time-to-recovery: first failure -> successful fit
                    _report_resilience_event({
                        "kind": "recovery",
                        "name": self.run_config.name or "default",
                        "attempts": attempt,
                        "ttr_s": round(time.time() - first_failure_ts, 3)})
                return result
            except (KeyboardInterrupt, SystemExit):
                # deliberate stops are not failures: Ctrl-C must kill
                # the run, not trigger a checkpoint-restart
                raise
            except Exception as e:  # noqa: BLE001
                attempt += 1
                if first_failure_ts is None:
                    first_failure_ts = time.time()
                # elastic story = checkpoint-restart (SURVEY.md §7): the
                # newest registered checkpoint wins; a gang that died
                # mid-run leaves worker-persisted checkpoints in
                # pending/ (the preemption grace flow lands there)
                latest = (manager.latest_checkpoint
                          or _newest_pending_checkpoint(storage) or latest)
                if max_failures >= 0 and attempt > max_failures:
                    return Result(error=e, checkpoint=latest, path=storage,
                                  metrics={})
                from ray_tpu.resilience import backoff_delay

                delay = backoff_delay(attempt)
                logger.warning(
                    "train attempt %d failed with %s: %s — restarting "
                    "from %s in %.2fs", attempt, type(e).__name__, e,
                    latest.path if latest else "scratch", delay)
                _report_resilience_event({
                    "kind": "restart",
                    "name": self.run_config.name or "default",
                    "attempt": attempt,
                    "cause": f"{type(e).__name__}: {e}"[:500],
                    "backoff_s": round(delay, 3),
                    "resume_from": latest.path if latest else None})
                time.sleep(delay)
                self._maybe_elastic_reform()

    def _maybe_elastic_reform(self) -> None:
        """Before a workers-mode restart: if schedulable capacity shrank
        below the gang (dead host quarantined, slice preempted) and the
        user set ScalingConfig.min_workers, re-form smaller — shrinking
        whole slices and the dcn_dp axis with them."""
        if self.mode != "workers" or \
                self.scaling_config.min_workers is None:
            return
        from ray_tpu._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is None:
            return
        try:
            avail = w.conductor.call("schedulable_resources", timeout=10.0)
        except Exception:  # noqa: BLE001 — older/mid-restart conductor
            return
        per_worker = dict(self.scaling_config.resources_per_worker
                          or {"CPU": 1.0})
        per_worker.setdefault("CPU", 1.0)
        cap = min((int(avail.get(k, 0.0) // v)
                   for k, v in per_worker.items() if v > 0), default=0)
        from ray_tpu.resilience import elastic_reform

        reformed = elastic_reform(self.scaling_config,
                                  self.sharding_config, cap)
        if reformed is None:
            return
        old_n = self.scaling_config.num_workers
        old_slices = self.scaling_config.num_slices
        self.scaling_config, self.sharding_config = reformed
        logger.warning(
            "elastic re-form: capacity shrank to %d worker slot(s); "
            "gang %d workers/%d slices -> %d workers/%d slices",
            cap, old_n, old_slices, self.scaling_config.num_workers,
            self.scaling_config.num_slices)
        _report_resilience_event({
            "kind": "elastic_reform",
            "name": self.run_config.name or "default",
            "from_workers": old_n, "to_workers":
                self.scaling_config.num_workers,
            "from_slices": old_slices,
            "to_slices": self.scaling_config.num_slices})

    # ----------------------------------------------------------- spmd mode

    def _fit_spmd(self, manager: CheckpointManager,
                  latest: Optional[Checkpoint], storage: str,
                  attempt: int = 0,
                  chaos_spec: Optional[str] = None) -> Result:
        history: List[Dict[str, Any]] = []
        last_metrics: Dict[str, Any] = {}
        pending_ckpts: List[Any] = []

        def report_fn(metrics: Dict[str, Any],
                      checkpoint: Optional[Checkpoint]) -> None:
            nonlocal last_metrics
            metrics = dict(metrics)
            metrics.setdefault("_time", time.time())
            history.append(metrics)
            last_metrics = metrics
            if checkpoint is not None:
                from .async_checkpoint import AsyncCheckpoint

                if isinstance(checkpoint, AsyncCheckpoint):
                    # in-flight async save: report() must not block on
                    # the disk write — reserve the recency slot NOW and
                    # register at commit time on the writer thread
                    snap = dict(metrics)
                    idx = manager.reserve_index()
                    checkpoint.add_commit_hook(
                        lambda c: manager.register(c, snap, index=idx))
                    pending_ckpts.append(checkpoint)
                else:
                    manager.register(checkpoint, metrics)

        from ray_tpu.observability.step_timer import StepTimer

        run_id = (f"{self.run_config.name or 'default'}"
                  f"/{uuid.uuid4().hex[:8]}")
        timer = StepTimer(run_id, rank=0, world_size=1)
        from ray_tpu.resilience.chaos import monkey_from_spec

        ctx = TrainContext(
            world_size=1, rank=0,
            experiment_name=self.run_config.name or "default",
            trial_dir=storage,
            dataset_shards=self._shard_datasets(0, 1),
            latest_checkpoint=latest,
            run_id=run_id,
            attempt=attempt,
            _report_fn=report_fn,
            _step_timer=timer,
            _chaos=(monkey_from_spec(chaos_spec, rank=0, attempt=attempt)
                    if chaos_spec else None))
        cfg = dict(self.train_loop_config)
        cfg["sharding_config"] = self.sharding_config
        preempt_sub = _subscribe_preemption(ctx)
        _set_session(ctx)
        try:
            self.train_fn(cfg)
        except StopTrial:
            pass
        finally:
            _set_session(None)
            _unsubscribe_preemption(preempt_sub)
            timer.close()  # flush the tail of the step-record batch
            # drain in-flight async saves before declaring the result —
            # best/latest must reflect every reported checkpoint
            for c in pending_ckpts:
                try:
                    c.wait()
                except Exception:  # noqa: BLE001 — failed save ≠ failed fit
                    pass
        return Result(metrics=last_metrics,
                      checkpoint=manager.best_checkpoint
                      or manager.latest_checkpoint or latest,
                      metrics_history=history)

    # --------------------------------------------------------- worker mode

    def _fit_workers(self, manager: CheckpointManager,
                     latest: Optional[Checkpoint], storage: str,
                     attempt: int = 0,
                     chaos_spec: Optional[str] = None) -> Result:
        import ray_tpu

        n = self.scaling_config.num_workers
        bundles = [dict(self.scaling_config.resources_per_worker or
                        {"CPU": 1.0}) for _ in range(n)]
        from ..util.placement_group import placement_group, \
            remove_placement_group

        pg = placement_group(bundles, strategy="STRICT_PACK")
        pg.wait()

        @ray_tpu.remote
        class _TrainWorker:
            """One rank of the group (reference WorkerGroup worker,
            _internal/worker_group.py:102)."""

            def __init__(self, rank: int, world: int):
                self.rank, self.world = rank, world
                self.reports: List[Any] = []

            def run(self, fn_bytes: bytes, cfg: Dict[str, Any],
                    trial_dir: str, shards: Dict[str, Any],
                    latest_path: Optional[str],
                    dist_key: Optional[str] = None,
                    slice_id: Optional[int] = None,
                    num_slices: int = 1,
                    run_id: str = "",
                    attempt: int = 0,
                    chaos_spec: Optional[str] = None) -> List[Any]:
                from ray_tpu._private import serialization
                from ray_tpu.observability.step_timer import StepTimer
                from ray_tpu.resilience.chaos import monkey_from_spec
                from ray_tpu.train.session import (TrainContext,
                                                   _set_session, StopTrial)
                from ray_tpu.train.checkpoint import Checkpoint as Ckpt
                from ray_tpu.train.trainer import (_persist_checkpoint,
                                                   _subscribe_preemption,
                                                   _unsubscribe_preemption)

                fn = serialization.loads(fn_bytes)
                out: List[Any] = []

                def report_fn(metrics, checkpoint):
                    if checkpoint is not None:
                        # durable at REPORT (or, async, COMMIT) time: a
                        # gang killed mid-training must leave its
                        # step-fresh checkpoints behind for the restart.
                        # Async saves persist from the writer thread's
                        # commit hook — strictly before wait() returns,
                        # so the grace flow's report-side wait implies
                        # the checkpoint is already in pending/.
                        if hasattr(checkpoint, "add_commit_hook"):
                            seq = len(out)
                            checkpoint.add_commit_hook(
                                lambda c, _seq=seq: _persist_checkpoint(
                                    c, trial_dir, self.rank, _seq,
                                    attempt))
                        else:
                            checkpoint = _persist_checkpoint(
                                checkpoint, trial_dir, self.rank,
                                len(out), attempt)
                    out.append((metrics, checkpoint))

                # each rank records its own steps; the conductor
                # aggregates the gang view (straggler detection)
                timer = StepTimer(run_id, rank=self.rank,
                                  world_size=self.world)
                ctx = TrainContext(
                    world_size=self.world, rank=self.rank,
                    trial_dir=trial_dir, dataset_shards=shards,
                    latest_checkpoint=(Ckpt(latest_path)
                                       if latest_path else None),
                    jax_dist_key=dist_key,
                    slice_id=slice_id, num_slices=num_slices,
                    run_id=run_id,
                    attempt=attempt,
                    _report_fn=report_fn,
                    _step_timer=timer,
                    _chaos=(monkey_from_spec(chaos_spec, rank=self.rank,
                                             attempt=attempt)
                            if chaos_spec else None))
                preempt_sub = _subscribe_preemption(ctx)
                _set_session(ctx)
                try:
                    if dist_key is not None and self.world > 1:
                        # form the gang's global jax mesh FOR the user —
                        # the reference does process-group setup in the
                        # backend (train/torch/config.py:64-117), train_fn
                        # should see the world already assembled
                        from ray_tpu.parallel.distributed import \
                            setup_jax_distributed
                        setup_jax_distributed()
                    fn(cfg)
                except StopTrial:
                    pass
                finally:
                    _set_session(None)
                    _unsubscribe_preemption(preempt_sub)
                    timer.close()  # ship this rank's tail records
                # In-flight async saves must hit disk before run() returns
                # (the driver registers these paths and then kills this
                # worker, its writer thread with it) — and a save that
                # FAILED must come back as path=None, not as a torn
                # directory the driver would register as a checkpoint.
                resolved: List[Any] = []
                import os as _os

                pending_root = _os.path.abspath(
                    _os.path.join(trial_dir, "pending")) + _os.sep
                for metrics, ck in out:
                    path = None
                    if ck is not None:
                        ok = True
                        if hasattr(ck, "future"):
                            try:
                                ck.wait()
                            except Exception:  # noqa: BLE001 — torn
                                ok = False
                            else:
                                # commit hooks swallow their own errors
                                # (a bad hook must not fail the save):
                                # a path still in the worker tempdir
                                # means the persist-to-pending/ hook
                                # FAILED — that checkpoint dies with
                                # this worker and must not be reported
                                # as durable
                                ok = _os.path.abspath(ck.path).startswith(
                                    pending_root)
                        path = ck.path if ok else None
                    resolved.append((metrics, path))
                return resolved

        from .._private import serialization

        fn_bytes = serialization.dumps(self.train_fn)
        cfg = dict(self.train_loop_config)
        cfg["sharding_config"] = self.sharding_config
        dist_key = None
        if n > 1 and getattr(self.scaling_config,
                             "setup_jax_distributed", True):
            dist_key = f"train-gang/{uuid.uuid4().hex}"
        # multi-slice gangs: the rendezvous groups process ids
        # slice-major for hybrid DCN meshes.
        from .config import assign_worker_slices

        num_slices = max(1, getattr(self.scaling_config, "num_slices", 1))
        slice_ids = assign_worker_slices(n, num_slices)
        run_id = (f"{self.run_config.name or 'default'}"
                  f"/{uuid.uuid4().hex[:8]}")
        # lease the bundle's actual resources (not the 0-CPU actor
        # default): the gang then occupies its reserved capacity and
        # each rank's lease is charged to the host its bundle lives on
        # (failure-domain accounting under ray_tpu.resilience)
        rpw = dict(self.scaling_config.resources_per_worker
                   or {"CPU": 1.0})
        opts: Dict[str, Any] = {"placement_group": pg,
                                "num_cpus": rpw.pop("CPU", 1.0)}
        if rpw:
            opts["resources"] = rpw
        workers = [_TrainWorker.options(**opts)
                   .remote(rank=i, world=n) for i in range(n)]
        from ray_tpu.resilience import GangSupervisor

        try:
            refs = [w.run.remote(
                fn_bytes, cfg, storage, self._shard_datasets(i, n),
                latest.path if latest else None, dist_key,
                slice_ids[i], num_slices, run_id, attempt, chaos_spec)
                for i, w in enumerate(workers)]
            # gang supervision: one dead rank -> cancel the survivors
            # (their collectives can never complete) so this get fails
            # fast and the fit-level retry restarts from checkpoint
            with GangSupervisor(workers, run_id=run_id):
                all_reports = ray_tpu.get(refs)
        finally:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass
            remove_placement_group(pg)
        # Aggregate EVERY rank's reports per step: the lowest reporting
        # rank's metrics are the headline (rank 0 whenever it reported),
        # and "rank_metrics" is ALWAYS present with one entry per rank
        # that reported that step — a stable schema even when ranks
        # report unequal step counts. A checkpoint path from ANY rank
        # registers (first one wins).
        history, last_metrics = [], {}
        n_steps = max((len(r) for r in all_reports), default=0)
        for i in range(n_steps):
            per_rank = [(rank, r[i]) for rank, r in enumerate(all_reports)
                        if len(r) > i]
            metrics = dict(per_rank[0][1][0] or {})
            metrics["rank_metrics"] = [m for _, (m, _p) in per_rank]
            history.append(metrics)
            last_metrics = metrics
            ckpt_path = next((p for _, (_m, p) in per_rank if p), None)
            if ckpt_path:
                manager.register(Checkpoint(ckpt_path), metrics)
        return Result(metrics=last_metrics,
                      checkpoint=manager.best_checkpoint
                      or manager.latest_checkpoint or latest,
                      metrics_history=history)

    # ------------------------------------------------------------ datasets

    def _shard_datasets(self, rank: int, world: int) -> Dict[str, Any]:
        shards: Dict[str, Any] = {}
        for name, ds in self.datasets.items():
            if hasattr(ds, "streaming_split"):
                shards[name] = ds.streaming_split(world)[rank]
            elif world > 1 and hasattr(ds, "__getitem__"):
                shards[name] = ds[rank::world]
            else:
                shards[name] = ds
        return shards
