"""StepTimer: the flight recorder's per-step clock.

Partitions each training step's wall time into named phases —
``data_wait`` / ``compile`` / ``device_step`` / ``checkpoint`` /
``report`` — and turns the result into tokens/sec and MFU (see
``observability.flops``). ``train/session.report()`` closes the current
step automatically, so a train_fn that uses ``TrainStep`` gets compile /
device-step accounting for free and only opts into finer phases with::

    timer = ray_tpu.train.get_step_timer()
    with timer.phase("data_wait"):
        batch = next(it)

Closed step records are buffered and shipped to the conductor in batches
(``report_train_steps``), riding the same flush cadence as metric/span
batches, where the gang-wide aggregation (``observability.gang``) builds
per-rank skew and straggler views.

Telemetry-off cost: a disabled timer's ``phase()`` returns one shared
no-op context manager (no allocation) and every other entry point is a
single attribute check — asserted by a counter microbench in tier-1, so
the hot step path never pays for a recorder nobody is reading.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

# Indirection so tests can count clock reads (the no-op path must make
# zero of them) without monkeypatching the global time module.
_now = time.perf_counter

# bubble_wait: blocked on a pipeline channel waiting for an upstream
# stage's activation / downstream stage's gradient (ray_tpu.mpmd) — the
# per-stage pipeline bubble, distinct from data_wait (input pipeline).
PHASES = ("data_wait", "bubble_wait", "compile", "device_step",
          "checkpoint", "report")

_FLUSH_EVERY = 16          # records per conductor batch
_FLUSH_INTERVAL_S = 2.0    # matches the metric/span flush cadence
_PENDING_CAP = 4096        # clusterless runs keep only this many records


def telemetry_enabled() -> bool:
    """Step telemetry defaults ON; RAY_TPU_STEP_TELEMETRY=0 disables."""
    return os.environ.get("RAY_TPU_STEP_TELEMETRY", "1") != "0"


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an ascending list — THE percentile
    of the flight-recorder stack (gang aggregation, summarize_records,
    the oracle validation harness all share it)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


_EMA_ALPHA = 0.3  # trailing EMA weight of the newest step


def summarize_records(records, ema_alpha: float = _EMA_ALPHA
                      ) -> Dict[str, Any]:
    """Per-phase summary over a window of step records (the StepTimer
    record schema: ``<phase>_ms`` keys plus ``other_ms``/``total_ms``):
    mean / p50 / p99 plus a trailing EMA in record order — the ONE
    derivation shared by the oracle validation harness and the
    conductor's train_progress aggregation, instead of each
    re-deriving stats from raw records."""
    phases: Dict[str, Dict[str, float]] = {}
    for name in (*PHASES, "other", "total"):
        key = f"{name}_ms"
        vals = [float(r[key]) for r in records
                if isinstance(r.get(key), (int, float))]
        if not vals:
            continue
        ordered = sorted(vals)
        ema = vals[0]
        for v in vals[1:]:
            ema = ema_alpha * v + (1.0 - ema_alpha) * ema
        phases[name] = {
            "mean_ms": sum(vals) / len(vals),
            "p50_ms": percentile(ordered, 0.5),
            "p99_ms": percentile(ordered, 0.99),
            "ema_ms": ema,
            "last_ms": vals[-1],
        }
    return {"steps": len(records), "phases": phases}


class _NoopCM:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP_CM = _NoopCM()


class _PhaseCM:
    __slots__ = ("_timer", "_name", "_t0")

    def __init__(self, timer: "StepTimer", name: str):
        self._timer = timer
        self._name = name

    def __enter__(self):
        self._timer.ensure_step_open()
        self._t0 = _now()
        return self

    def __exit__(self, *exc):
        self._timer.record(self._name, _now() - self._t0)
        return False


class StepTimer:
    """Per-rank step clock; one instance per training session."""

    def __init__(self, run_id: str = "", rank: int = 0,
                 world_size: int = 1, enabled: Optional[bool] = None):
        self.run_id = run_id or "default"
        self.rank = rank
        self.world_size = world_size
        self.enabled = telemetry_enabled() if enabled is None else enabled
        self._step_index = 0
        self._step_start: Optional[float] = None
        self._step_start_wall: Optional[float] = None
        self._acc: Dict[str, float] = {}
        self._pending: List[Dict[str, Any]] = []
        self._last_flush = 0.0
        # MFU inputs, usually filled in by TrainStep at first execution
        self.tokens_per_step: Optional[int] = None
        self.flops_per_step: Optional[float] = None
        self.peak_flops_total: Optional[float] = None

    # ------------------------------------------------------------- phases

    def phase(self, name: str):
        """Context manager accumulating wall time into phase `name`."""
        if not self.enabled:
            return _NOOP_CM
        return _PhaseCM(self, name)

    def record(self, name: str, seconds: float) -> None:
        """Directly account `seconds` to phase `name` in the open step.
        Recording into a not-yet-open step backdates the step start by
        `seconds` — the work clearly happened inside it."""
        if not self.enabled:
            return
        if self._step_start is None:
            self._begin_step()
            self._step_start -= seconds
            self._step_start_wall -= seconds
        self._acc[name] = self._acc.get(name, 0.0) + seconds

    def ensure_step_open(self) -> None:
        """Start the step clock now if no step is open (phase entry)."""
        if self.enabled and self._step_start is None:
            self._begin_step()

    def _begin_step(self) -> None:
        self._step_start = _now()
        self._step_start_wall = time.time()
        self._acc = {}

    # -------------------------------------------------------- MFU inputs

    def set_tokens_per_step(self, n: int) -> None:
        if self.enabled:
            self.tokens_per_step = int(n)

    def set_flops_per_step(self, f: Optional[float]) -> None:
        if self.enabled and f:
            self.flops_per_step = float(f)

    def set_peak_flops(self, f: Optional[float]) -> None:
        if self.enabled and f:
            self.peak_flops_total = float(f)

    # ------------------------------------------------------- step closing

    def end_step(self) -> Optional[Dict[str, Any]]:
        """Close the open step and return its record (None when disabled
        or nothing was recorded). Called by train.session.report()."""
        if not self.enabled or self._step_start is None:
            return None
        now, wall = _now(), time.time()
        total_s = now - self._step_start
        rec: Dict[str, Any] = {
            "step": self._step_index,
            "rank": self.rank,
            "t_start": self._step_start_wall,
            "t_end": wall,
            "total_ms": total_s * 1e3,
        }
        accounted = 0.0
        for name in PHASES:
            s = self._acc.get(name, 0.0)
            accounted += s
            rec[f"{name}_ms"] = s * 1e3
        rec["other_ms"] = max(0.0, total_s - accounted) * 1e3
        if self.tokens_per_step:
            rec["tokens"] = self.tokens_per_step
            rec["tokens_per_sec"] = self.tokens_per_step / max(total_s, 1e-9)
        # MFU against device time when we have it (total time includes
        # data wait, which is goodput, not device utilization)
        from . import flops as _flops

        device_s = self._acc.get("device_step", 0.0) or total_s
        m = _flops.mfu(self.flops_per_step, device_s, self.peak_flops_total)
        if m is not None:
            rec["mfu"] = m
        self._step_index += 1
        self._step_start = None
        self._step_start_wall = None
        self._acc = {}
        self._pending.append(rec)
        if len(self._pending) >= _FLUSH_EVERY or \
                now - self._last_flush > _FLUSH_INTERVAL_S:
            self.flush()
        return rec

    # ------------------------------------------------------------- flush

    def flush(self) -> None:
        """Ship pending records to the conductor (best-effort: a driver
        without a cluster keeps records local for direct inspection)."""
        if not self._pending:
            return
        self._last_flush = _now()
        batch, self._pending = self._pending, []
        from ray_tpu._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is None:
            # no cluster: keep a bounded tail for local readers — a long
            # clusterless run (spmd trainer without ray_tpu.init) must
            # not accumulate one dict per step forever
            self._pending = batch[-_PENDING_CAP:]
            return
        try:
            w.conductor.notify("report_train_steps", self.run_id,
                               self.rank, batch)
        except Exception:  # noqa: BLE001 — cluster shutting down
            pass

    def close(self) -> None:
        """Session teardown: flush the record tail. A partially-open
        step (e.g. the report-phase stub the last report() left behind)
        is dropped, not closed — a teardown-length pseudo-step would
        poison the gang's mean/p99 stats."""
        self._step_start = None
        self._acc = {}
        self.flush()
