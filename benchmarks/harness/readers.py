"""What the per-layer readers share: how they are found, and the small
reductions most of them are one call of. A reader is a file
`benchmarks/layer_metrics/<metric name>.py` with a `read(obs)` that
returns a number, or None where the run holds nothing to read (the
harness then leaves the metric out of the line).

`obs`, the observations of one run:
  obs["trace"]     reduce_trace()'s result (traced runs), else None
  obs["host"]      labelled gaps: [(start_ns, dur_ns, prev, next, host)]
  obs["requests"]  the client's record of every request (serving)
  obs["numbers"]   reduce_requests()'s statistics of the window (serving)
  obs["phases"]    flight recorder: one {phase: ms} per request (serving)
  obs["counters"]  program counters sampled by the harness
  obs["train"]     {"step_s": [...], "data_wait_s": [...]} (training)
  obs["cell"]      {"conf", "traffic", "peaks", "seconds", ...}
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .traffic import ROOT


def load_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = os.path.join(ROOT, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def mean(values: Sequence[float]) -> Optional[float]:
    if not len(values):
        return None
    return float(np.mean(np.asarray(values, np.float64)))


def phase_ms(obs: Dict[str, Any], names: Sequence[str]) -> List[float]:
    """Per request, the sum of the named flight-recorder phases."""
    return [sum(float(p.get(n, 0.0)) for n in names)
            for p in obs.get("phases") or []]


def program_events(obs: Dict[str, Any], name: str) -> List[Any]:
    trace = obs.get("trace")
    if not trace:
        return []
    return trace["programs"].get(name, [])


def program_mean_ms(obs: Dict[str, Any], name: str) -> Optional[float]:
    return mean([d / 1e6 for _n, _s, d in program_events(obs, name)])


def op_seconds(obs: Dict[str, Any], substrings: Sequence[str]) -> float:
    """Summed device time of the operations whose name holds any of the
    substrings, over the events that START in the traced window."""
    trace = obs.get("trace")
    if not trace:
        return 0.0
    return sum(d for name, evs in trace["ops"].items()
               if any(s in name for s in substrings)
               for _n, _s, d in evs) / 1e9


def op_count(obs: Dict[str, Any], substrings: Sequence[str]) -> int:
    """How many of those operations START in the traced window. A
    kernel's work is reckoned per event of its own: the window cuts
    through a step, so the steps that start in it and the kernel events
    that start in it differ by one in ten."""
    trace = obs.get("trace")
    if not trace:
        return 0
    return sum(len(evs) for name, evs in trace["ops"].items()
               if any(s in name for s in substrings))


def idle_share(obs: Dict[str, Any]) -> Optional[float]:
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def counter(obs: Dict[str, Any], name: str) -> Optional[float]:
    v = (obs.get("counters") or {}).get(name)
    return None if v is None else float(v)


def ttft_ms(obs: Dict[str, Any]) -> List[float]:
    return [1e3 * (r["token_t"][0] - r["due_t"])
            for r in obs.get("requests") or [] if r.get("token_t")]
