"""Step-time oracle: roofline prediction, validation, attribution.

Shardlint prices a layout in bytes-over-DCN (`analysis.collectives`);
the flight recorder prices a run in milliseconds (`step_timer`). This
module is the bridge: a per-generation ICI/DCN bandwidth+latency table
(the comms twin of ``flops.PEAK_FLOPS_BF16``) turns a layout's traced
collectives into a predicted ``{device_step, ici_wait, dcn_wait}``
step-time breakdown, and a validation harness replays flight-recorder
measurements against the prediction so the model stays falsifiable.

Model ("Exploring the limits of Concurrency in ML Training on Google
TPUs", arXiv:2011.03641 — the roofline shape per parallelism mix):

- compute roofline:  ``flops_per_step / peak_flops_total``;
- comms roofline:    per collective, the ring traffic is split by link
  class — the DCN share from ``CollectiveUse.dcn_bytes`` and the ICI
  remainder from ``CollectiveUse.ring_bytes`` — and each class pays
  ``bytes / bandwidth + hops * latency``;
- the prediction is the SERIAL sum of the three phases: an upper bound
  (real programs overlap comms with compute), which is exactly what the
  fitted calibration factor absorbs.

Constants are approximate public spec figures (per chip, one direction).
They do not need to be exact to be useful: the validation harness fits a
scalar calibration factor against measured steps, so the table only has
to get the SHAPE right (ICI ≫ DCN, newer generations faster). Non-TPU
backends get documented nominal constants — on the CPU tier-1 cluster
the oracle validates plumbing and calibration math, not absolute
numbers.

Runtime surface (the repo's full treatment): predictions and validation
records push to the conductor (``util.state.oracle_status()``, CLI
``ray_tpu oracle``, dashboard ``/api/oracle``), lazy Prometheus gauges
``ray_tpu_oracle_predicted_step_ms{layout}`` /
``ray_tpu_oracle_residual_ratio{phase}``, and a ``predicted_step_ms``
counter track in the merged timeline.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private.telemetry import (ORACLE_PREDICTION,
                                        ORACLE_VALIDATION)
from ray_tpu.util.telemetry import Pusher

from . import flops as _flops
from .step_timer import summarize_records

# ------------------------------------------------------- constants table

@dataclass(frozen=True)
class LinkConstants:
    """Per-chip interconnect constants of one device generation.

    ``*_bw`` in bytes/s (one direction, per chip — the divisor for the
    PER-CHIP ring traffic ``CollectiveUse`` computes), ``*_latency_s``
    per ring hop.
    """

    ici_bw: float
    ici_latency_s: float
    dcn_bw: float
    dcn_latency_s: float


# Keyed exactly like flops.PEAK_FLOPS_BF16 (longest prefix wins) — the
# property tests pin the two tables together: every generation with a
# peak-FLOPs entry has link constants, and within each generation the
# link classes are ordered (ICI faster than DCN, ICI hop latency lower).
# ICI figures follow the public per-chip interconnect specs; DCN is the
# per-chip share of the host NIC crossing the slice boundary.
LINK_CONSTANTS: Dict[str, LinkConstants] = {
    "TPU v2": LinkConstants(6.2e10, 1e-6, 3.1e9, 3.0e-5),
    "TPU v3": LinkConstants(8.2e10, 1e-6, 3.1e9, 3.0e-5),
    "TPU v4": LinkConstants(2.4e11, 1e-6, 6.2e9, 2.5e-5),
    "TPU v5 lite": LinkConstants(1.0e11, 1e-6, 6.2e9, 2.5e-5),  # v5e
    "TPU v5e": LinkConstants(1.0e11, 1e-6, 6.2e9, 2.5e-5),
    "TPU v5p": LinkConstants(4.8e11, 1e-6, 1.2e10, 2.5e-5),
    "TPU v5": LinkConstants(4.8e11, 1e-6, 1.2e10, 2.5e-5),
    "TPU v6 lite": LinkConstants(3.6e11, 1e-6, 1.2e10, 2.5e-5),  # v6e
    "TPU v6e": LinkConstants(3.6e11, 1e-6, 1.2e10, 2.5e-5),
}

# Nominal constants for non-TPU backends (the flops.NOMINAL_PEAK_FLOPS
# pattern): off-silicon predictions are only meaningful as a relative
# series, so these just need to be stable, documented, and shaped right.
NOMINAL_LINK_CONSTANTS: Dict[str, LinkConstants] = {
    "cpu": LinkConstants(1.0e10, 1e-6, 1.0e9, 5.0e-5),
    "gpu": LinkConstants(6.0e11, 1e-6, 2.5e10, 2.5e-5),  # NVLink / IB
}


def device_link_constants(device: Any = None) -> LinkConstants:
    """Link constants of one device (jax Device or None for the first
    local device) — longest-prefix match, mirroring
    ``flops.device_peak_flops``."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    for name, links in sorted(LINK_CONSTANTS.items(),
                              key=lambda kv: -len(kv[0])):
        if kind.startswith(name):
            return links
    platform = getattr(device, "platform", "") or ""
    if platform == "tpu":
        raise ValueError(
            f"no link constants for TPU device_kind {kind!r}: add it to "
            "observability.roofline.LINK_CONSTANTS with its source")
    return NOMINAL_LINK_CONSTANTS.get(platform,
                                      NOMINAL_LINK_CONSTANTS["cpu"])


# ------------------------------------------------------------ prediction

#: phases the oracle models; the measured counterpart of their sum is
#: the recorder's device_step (collectives run inside the jitted step).
PREDICTED_PHASES = ("device_step", "ici_wait", "dcn_wait")


def predict_step_time(layout: Any, uses: Sequence[Any],
                      flops_per_step: float,
                      peak_flops_total: float,
                      links: Optional[LinkConstants] = None,
                      calibration: float = 1.0,
                      name: str = "") -> Dict[str, Any]:
    """Analytic roofline prediction for one layout.

    ``layout`` is an ``analysis.shardcheck.MeshLayout``; ``uses`` the
    traced ``CollectiveUse`` list. Returns the compile-excluded
    breakdown {device_step_ms, ici_wait_ms, dcn_wait_ms} plus the
    serial total and the inputs that produced it, all pre-scaled by
    ``calibration`` (1.0 = the raw analytic model).
    """
    links = links or device_link_constants()
    compute_s = (flops_per_step / peak_flops_total
                 if flops_per_step and peak_flops_total else 0.0)
    ici_s = dcn_s = 0.0
    ici_bytes = dcn_bytes = 0.0
    unmodeled: List[str] = []
    for use in uses:
        n, d = use.spans(layout)
        if n <= 1:
            continue
        if not use.modeled():
            unmodeled.append(use.primitive)
        i_b, d_b = use.link_bytes(layout)
        ici_bytes += i_b
        dcn_bytes += d_b
        if i_b > 0:
            ici_s += i_b / links.ici_bw \
                + links.ici_latency_s * max(1, n - d)
        if d_b > 0:
            dcn_s += d_b / links.dcn_bw \
                + links.dcn_latency_s * max(1, d - 1)
    c = float(calibration)
    pred = {
        "layout": name or getattr(layout, "name", "layout"),
        "device_step_ms": compute_s * 1e3 * c,
        "ici_wait_ms": ici_s * 1e3 * c,
        "dcn_wait_ms": dcn_s * 1e3 * c,
        "predicted_step_ms": (compute_s + ici_s + dcn_s) * 1e3 * c,
        "flops_per_step": float(flops_per_step or 0.0),
        "peak_flops_total": float(peak_flops_total or 0.0),
        "ici_bytes": ici_bytes,
        "dcn_bytes": dcn_bytes,
        "n_collectives": len(uses),
        "calibration": c,
    }
    if unmodeled:
        # the oracle names its own blind spots (satellite: collectives
        # emits the matching INFO finding)
        pred["unmodeled_collectives"] = sorted(set(unmodeled))
    return pred


def predict_builtin_layouts(n_devices: int = 8,
                            device: Any = None,
                            calibration: float = 1.0
                            ) -> Dict[str, Dict[str, Any]]:
    """Predictions for every built-in dryrun layout (the
    ``analyze --predict-step-time`` backend). Deviceless apart from the
    local device used to pick constants — layouts trace against
    AbstractMesh exactly as the shardlint gate does."""
    from ..analysis.layouts import trace_builtin_layouts

    peak = _flops.device_peak_flops(device) * n_devices
    links = device_link_constants(device)
    out: Dict[str, Dict[str, Any]] = {}
    for lname, trace in trace_builtin_layouts(n_devices).items():
        out[lname] = predict_step_time(
            trace.layout, trace.uses, trace.flops_per_step, peak,
            links=links, calibration=calibration, name=lname)
        if trace.tokens_per_step:
            out[lname]["tokens_per_step"] = trace.tokens_per_step
    return out


# ------------------------------------------------------------ validation

def calibration_fit(pairs: Sequence[Tuple[float, float]]) -> float:
    """Least-squares-through-origin scale factor over (predicted_ms,
    measured_ms) pairs: the alpha minimizing Σ(m - alpha·p)². 1.0 when
    the pairs carry no signal."""
    num = sum(p * m for p, m in pairs)
    den = sum(p * p for p, _ in pairs)
    return num / den if den > 0 else 1.0


def phase_residuals(prediction: Dict[str, Any],
                    measured: Dict[str, Any]) -> Dict[str, float]:
    """measured/predicted ratios per comparable phase. The recorder's
    device_step lumps compute + comms (collectives run inside the jitted
    step), so it compares against the predicted serial total; total_ms
    additionally carries the unmodeled host phases (data_wait /
    checkpoint / report)."""
    res: Dict[str, float] = {}
    p_total = prediction.get("predicted_step_ms") or 0.0
    m_dev = measured.get("device_step_ms")
    if p_total > 0 and isinstance(m_dev, (int, float)) and m_dev > 0:
        res["device_step"] = m_dev / p_total
    m_total = measured.get("total_ms")
    if p_total > 0 and isinstance(m_total, (int, float)) and m_total > 0:
        res["total"] = m_total / p_total
    return res


def validate_records(prediction: Dict[str, Any],
                     records: Sequence[Dict[str, Any]]
                     ) -> Dict[str, Any]:
    """Pure validation core: replay flight-recorder step records against
    a prediction. Per-phase residuals come from the measured p50s
    (``step_timer.summarize_records`` — one summary implementation for
    the harness, train_progress, and bench), the calibration factor from
    a least-squares fit over every (predicted, measured device_step)
    pair."""
    summary = summarize_records(records)
    phases = summary.get("phases", {})
    p50s = {f"{name}_ms": st["p50_ms"] for name, st in phases.items()}
    measured = {"device_step_ms": p50s.get("device_step_ms"),
                "total_ms": p50s.get("total_ms")}
    p_total = prediction.get("predicted_step_ms") or 0.0
    pairs = [(p_total, float(r["device_step_ms"]))
             for r in records
             if isinstance(r.get("device_step_ms"), (int, float))
             and r["device_step_ms"] > 0] if p_total > 0 else []
    return {
        "layout": prediction.get("layout"),
        "predicted": {k: prediction.get(k)
                      for k in ("device_step_ms", "ici_wait_ms",
                                "dcn_wait_ms", "predicted_step_ms")},
        "measured": {"summary": phases, **measured},
        "residuals": phase_residuals(prediction, measured),
        "calibration": calibration_fit(pairs),
        "n_steps": summary.get("steps", 0),
    }


def validate_run(prediction: Dict[str, Any],
                 run_id: Optional[str] = None,
                 records: Optional[Sequence[Dict[str, Any]]] = None,
                 persist_path: Optional[str] = None) -> Dict[str, Any]:
    """The validation harness: pull a run's flight-recorder records from
    the conductor (or take them directly), compute residuals + the
    fitted calibration factor, record the result on every oracle
    surface, and optionally persist it as JSON so the model's score
    survives the cluster."""
    if records is None:
        from ray_tpu._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is None:
            raise RuntimeError(
                "no records given and no cluster: call ray_tpu.init() "
                "or pass records= explicitly")
        all_recs = w.conductor.call("get_train_steps", 10_000,
                                    timeout=30.0)
        records = [r for r in all_recs
                   if run_id is None or r.get("run_id") == run_id]
    # Multi-rank runs flatten to one record per rank per step; validate
    # against the LEAD rank only (gang.summarize_run's convention) so a
    # straggler rank cannot skew the p50s and the calibration fit, and
    # n_steps counts steps, not step-rank samples.
    ranks = {r.get("rank") for r in records if r.get("rank") is not None}
    if len(ranks) > 1:
        lead = min(ranks)
        records = [r for r in records if r.get("rank") == lead]
    if not records:
        # also guards an explicitly-passed empty list: a vacuous
        # validation (n_steps=0, calibration=1.0) would read as a
        # perfect fit on every surface
        raise ValueError(
            f"no flight-recorder step records for run {run_id!r}")
    rec = validate_records(prediction, records)
    if not rec["residuals"]:
        # records that carry none of the modeled phases (a train_fn
        # reporting without TrainStep: no device_step_ms) must not land
        # as a calibration=1.0 "perfect fit" on every surface
        raise ValueError(
            f"records for run {run_id!r} carry no comparable phase "
            "(device_step_ms / total_ms) — nothing to validate")
    rec["run_id"] = run_id
    record_validation(rec)
    if persist_path:
        with open(persist_path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


# ----------------------------------------------------- prometheus (lazy)
# Created on first record_*() call, never at import (the weights /
# kvcache / disagg pattern — rebound ONCE to a complete dict).

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def oracle_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Gauge

            _metrics = dict(
                predicted=Gauge(
                    "ray_tpu_oracle_predicted_step_ms",
                    "roofline-predicted step time per layout "
                    "(compile-excluded; device_step + ici_wait + "
                    "dcn_wait)",
                    tag_keys=("layout",)),
                residual=Gauge(
                    "ray_tpu_oracle_residual_ratio",
                    "measured/predicted ratio per phase from the last "
                    "oracle validation (1.0 = the model was right)",
                    tag_keys=("phase",)))
    return _metrics


def record_prediction(layout: str, prediction: Dict[str, Any]) -> None:
    """Publish one layout's prediction to every oracle surface: the
    Prometheus gauge, the conductor aggregate (state API / CLI /
    dashboard), and the merged timeline's predicted-step-time counter
    track. Best-effort without a cluster (the gauge still updates)."""
    layout = str(layout)
    oracle_metrics()["predicted"].set(
        float(prediction.get("predicted_step_ms", 0.0)),
        tags={"layout": layout})
    event = {"kind": "prediction", "layout": layout}
    for key in ("predicted_step_ms", "device_step_ms", "ici_wait_ms",
                "dcn_wait_ms"):
        event[key] = prediction.get(key)
    Pusher("oracle", ORACLE_PREDICTION + layout).push(
        dict(prediction, layout=layout), (event,), force=True)


def record_validation(rec: Dict[str, Any]) -> None:
    """Publish a validation record (residuals + calibration) to every
    oracle surface."""
    m = oracle_metrics()
    for phase, ratio in (rec.get("residuals") or {}).items():
        m["residual"].set(float(ratio), tags={"phase": str(phase)})
    event = {"kind": "validation"}
    for key in ("layout", "run_id", "calibration", "residuals",
                "n_steps"):
        event[key] = rec.get(key)
    # validations are a log: each record is a component of its own
    Pusher("oracle", f"{ORACLE_VALIDATION}{time.time_ns()}").push(
        dict(rec), (event,), force=True)


__all__ = ["LINK_CONSTANTS", "LinkConstants", "NOMINAL_LINK_CONSTANTS",
           "PREDICTED_PHASES", "calibration_fit", "device_link_constants",
           "oracle_metrics", "phase_residuals", "predict_builtin_layouts",
           "predict_step_time", "record_prediction", "record_validation",
           "validate_records", "validate_run"]
