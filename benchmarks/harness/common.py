"""What the training and the serving cell share: the device check, the
traced window, and how the observations become the last line."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

from . import trace_reduce
from .peaks import peaks_for
from .readers import load_reader
from .traffic import REPO_ROOT

OUT_DIR = os.path.join(REPO_ROOT, "chiprun_out")
# one directory a process: two runs in one checkout do not meet
TRACE_DIR = os.path.join(REPO_ROOT, ".bench_trace", str(os.getpid()))
REHEARSAL_OP = "bench_rehearsal_op"


def log(message: str) -> None:
    sys.stderr.write(f"benchmark: {message}\n")
    sys.stderr.flush()


def mark(run: Dict[str, Any], name: str) -> None:
    """Where set-up time goes: seconds since the process started."""
    run.setdefault("marks", []).append(
        (name, round(time.perf_counter() - run["t_start"], 3)))


def require_devices(run: Dict[str, Any]) -> Dict[str, Any]:
    """The devices as JAX reports them; anything but the chips the cell
    asks for ends the run (the rehearsal alone may use the CPU)."""
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    log(f"devices {facts}, jax {jax.__version__}")
    mark(run, "devices")
    if run["rehearsal"]:
        run["peaks"] = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1.0,
                        "hbm_bytes": 1.0}
        return facts
    if facts["platform"] != "tpu":
        raise RuntimeError(
            f"JAX found platform {facts['platform']!r}, not 'tpu': a cell "
            "runs on the chip or not at all")
    if facts["count"] < run["cell"]["chips"]:
        raise RuntimeError(
            f"the cell asks for {run['cell']['chips']} chip(s), JAX "
            f"found {facts['count']}")
    if os.environ.get("RAY_TPU_PALLAS_INTERPRET", "0") == "1":
        raise RuntimeError("RAY_TPU_PALLAS_INTERPRET is set: the kernels "
                           "would not run through Mosaic")
    run["peaks"] = peaks_for(facts["kind"])
    return facts


def memory_peak_bytes(extra: int = 0) -> int:
    """The allocator's peak on the fullest device. `extra` is for what
    the allocator's figure is known to leave out (see train_cell)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max(peak, int(extra))


class TracedWindow:
    """A few seconds of profiler trace inside the run, between two
    markers of the run's own. `samples` are read at each marker, on the
    host, so that counters can be cut to the same window."""

    def __init__(self, run: Dict[str, Any]):
        self.run = run
        self.wall_t0 = self.wall_t1 = 0.0
        self.samples: List[Dict[str, Any]] = []
        self.length_s = min(3.0, max(0.5, run["seconds"] / 3.0))
        self.delay_s = max(0.0, min(run["seconds"] / 3.0,
                                    run["seconds"] - self.length_s - 1.0))

    def record(self, sample=None) -> None:
        """Blocks for `length_s` plus the profiler's start and stop."""
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.MARK_T0):
                time.sleep(0.001)
            self.wall_t0 = time.time()
            if sample is not None:
                self.samples.append(sample())
            if self.run["rehearsal"]:
                with jax.profiler.TraceAnnotation(REHEARSAL_OP):
                    time.sleep(0.01)
            time.sleep(self.length_s)
            if sample is not None:
                self.samples.append(sample())
            self.wall_t1 = time.time()
            with jax.profiler.TraceAnnotation(trace_reduce.MARK_T1):
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()

    def reduce(self, host_spans_wall: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, Any]:
        """The reduced trace, its gaps labelled with what the host was
        doing. `host_spans_wall` are (name, start, end) in seconds of
        `time.time()`; the marker t0 ties that clock to the trace's."""
        files = sorted(glob.glob(os.path.join(
            TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise trace_reduce.TraceError(
                f"the profiler wrote no .xplane.pb under {TRACE_DIR}")
        planes = trace_reduce.load_xplane(files[-1])
        t0_ns = trace_reduce.find_window(planes)[0]
        shift = t0_ns - self.wall_t0 * 1e9
        spans = [(n, s * 1e9 + shift, e * 1e9 + shift)
                 for n, s, e in host_spans_wall
                 if e >= self.wall_t0 and s <= self.wall_t1]
        plane_prefix = "/device:TPU:"
        if self.run["rehearsal"]:
            # the CPU backend has no device plane: the events record()
            # put between the markers stand in for one, whatever
            # threads the trace happens to hold
            plane_prefix = "/rehearsal:"
            planes.append((plane_prefix + "0", [("XLA Ops", [
                ev for _p, lines in planes for _l, evs in lines
                for ev in evs if ev[0] == REHEARSAL_OP])]))
        trace = trace_reduce.reduce_trace(
            planes, plane_prefix=plane_prefix, ops_line="XLA Ops",
            modules_line="XLA Modules", host_spans=spans)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return trace


def assemble(run: Dict[str, Any], obs: Dict[str, Any], *,
             end_to_end: Dict[str, float], correct: bool, attempted: int,
             failed: int, device: Dict[str, Any], memory_extra: int = 0
             ) -> Dict[str, Any]:
    """The contract's object: end-to-end metrics in a plain run, the
    per-layer metrics and the device's busy time in a traced one."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if run["trace"]:
        for m in run["per_layer"]:
            value = load_reader(m["name"])(obs)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in run["end_to_end"]:
            if m["name"] not in end_to_end:
                raise RuntimeError(f"the cell did not measure {m['name']}")
            metrics[m["name"]] = {"value": float(end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak_bytes(memory_extra))
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": dev}
    if run["trace"]:
        trace = obs["trace"]
        dev["busy_s"] = float(trace["busy_s"])
        dev["window_s"] = float(trace["window_s"])
        result["breakdown"] = trace["breakdown"]
    return result


def write_record(run: Dict[str, Any], record: Dict[str, Any]) -> None:
    """What else is worth keeping: a line on stderr and a file under
    chiprun_out/ (git-ignored), never the last line."""
    import json

    os.makedirs(OUT_DIR, exist_ok=True)
    name = (f"bench_{run['cell']['name']}_s{run['seed']}"
            f"_t{int(run['trace'])}.json")
    with open(os.path.join(OUT_DIR, name), "w") as f:
        json.dump(record, f, default=str)
    log("record " + json.dumps(record, default=str)[:4000])
