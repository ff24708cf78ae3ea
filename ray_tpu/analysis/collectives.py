"""Static collective-cost analysis — which collectives a function runs,
over which mesh axes, and how many bytes would cross the slow DCN links.

The trace is abstract: `jax.make_jaxpr` over `jax.ShapeDtypeStruct`
inputs never touches a device, and `shard_map` programs trace against a
`jax.sharding.AbstractMesh` built from the `MeshLayout`, so a multi-slice
pod layout is analyzable on a dev box with zero accelerators. Explicit
collectives (`psum` / `all_gather` / `all_to_all` / `ppermute` /
`reduce_scatter` — the shard_map vocabulary this repo's pipeline, ring
attention, and MoE paths use) appear as jaxpr primitives carrying their
axis names; the walker recurses through pjit/scan/cond sub-jaxprs to find
them all.

Cost model (ring algorithms, DCN share only): for a collective over axes
with total size n and DCN span d (product of `MeshLayout.dcn_factors`),
the bytes that must cross a slice boundary are

    psum            2 * B * (d-1)/d      (reduce-scatter + all-gather)
    all_gather      B * n * (d-1)/d      (output is n times the input)
    reduce_scatter  B * (d-1)/d
    all_to_all      B * (d-1)/d          (uniform shuffle)
    ppermute        B                    (upper bound: every hop DCN)

"Exploring the limits of Concurrency in ML Training on Google TPUs"
(arXiv:2011.03641) measures the ICI/DCN bandwidth asymmetry that makes
these bytes dominate multi-slice step time — hence severity: collectives
over the declared DCN axes (dp/fsdp/pp, `multislice.DCN_AXES`) are INFO
(that placement is the hybrid design), while tp/sp/ep spanning DCN is a
WARNING: those axes are ICI-bandwidth-hungry and a layout that stretches
them across slices is almost always a mistake.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

from ..parallel.multislice import DCN_AXES
from .findings import Finding, INFO, WARNING
from .shardcheck import MeshLayout, _nbytes

# Mesh axes whose collectives must stay on ICI (model parallelism).
HEAVY_AXES = ("tp", "sp", "ep")

#: primitive name -> fn(bytes, n, d) -> bytes over DCN
_COST_MODEL: Dict[str, Callable[[float, int, int], float]] = {
    "psum": lambda b, n, d: 2.0 * b * (d - 1) / d,
    # what a psum inside shard_map traces as under check_vma
    "psum_invariant": lambda b, n, d: 2.0 * b * (d - 1) / d,
    "all_gather": lambda b, n, d: float(b) * n * (d - 1) / d,
    "all_gather_invariant": lambda b, n, d: float(b) * n * (d - 1) / d,
    "reduce_scatter": lambda b, n, d: float(b) * (d - 1) / d,
    "all_to_all": lambda b, n, d: float(b) * (d - 1) / d,
    "ppermute": lambda b, n, d: float(b),
    "pmin": lambda b, n, d: 2.0 * b * (d - 1) / d,
    "pmax": lambda b, n, d: 2.0 * b * (d - 1) / d,
}

# Named-axis primitives that move no payload (VMA bookkeeping and index
# queries): never collected, never costed.
_NON_COMM = frozenset({"pvary", "axis_index"})


@dataclass(frozen=True)
class CollectiveUse:
    """One collective equation found in the trace."""

    primitive: str
    axes: Tuple[str, ...]
    in_bytes: int

    def modeled(self) -> bool:
        """False for a collective the cost table doesn't cover — its
        byte estimates fall back to the raw input size (an upper-ish
        bound with no ring discount), and `check_collectives` emits an
        `unmodeled-collective` INFO finding naming it so oracle
        predictions surface the blind spot instead of absorbing it."""
        return self.primitive in _COST_MODEL

    def spans(self, layout: MeshLayout) -> Tuple[int, int]:
        """(n, d): total participant count over this use's axes and its
        DCN span (1 = entirely on ICI)."""
        n = int(np.prod([layout.axis_size(a) for a in self.axes],
                        dtype=np.int64)) or 1
        d = int(np.prod([layout.dcn_factor(a) for a in self.axes],
                        dtype=np.int64)) or 1
        return n, d

    def link_bytes(self, layout: MeshLayout) -> Tuple[float, float]:
        """(ici_bytes, dcn_bytes): the per-chip ring traffic split by
        link class — one spans() evaluation for both shares (the
        oracle's comms numerators)."""
        n, d = self.spans(layout)
        if n <= 1:
            return 0.0, 0.0
        total = self._ring_share(n, n)  # span=n makes every hop count
        dcn = self._ring_share(n, d) if d > 1 else 0.0
        return max(0.0, total - dcn), dcn

    def dcn_bytes(self, layout: MeshLayout) -> float:
        return self.link_bytes(layout)[1]

    def ring_bytes(self, layout: MeshLayout) -> float:
        """Total per-chip ring traffic over ALL links."""
        ici, dcn = self.link_bytes(layout)
        return ici + dcn

    def _ring_share(self, n: int, span: int) -> float:
        model = _COST_MODEL.get(self.primitive)
        return model(self.in_bytes, n, span) if model \
            else float(self.in_bytes)


def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    raw = params.get("axes", params.get("axis_name", ()))
    if raw is None:
        return ()
    if isinstance(raw, (tuple, list)):
        return tuple(a for a in raw if isinstance(a, str))
    return (raw,) if isinstance(raw, str) else ()


def _walk_jaxpr(jaxpr: Any, out: List[CollectiveUse]) -> None:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def _sub_jaxprs(params):
        subs = []
        for v in params.values():
            for item in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(item, ClosedJaxpr):
                    subs.append(item.jaxpr)
                elif isinstance(item, Jaxpr):
                    subs.append(item)
        return subs

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn.params)
        # Any primitive carrying named mesh axes is a collective to the
        # walker — including ones the cost table does not model yet
        # (collected so `check_collectives` can NAME the blind spot
        # instead of the byte estimate silently falling back). Call-like
        # primitives (pjit / scan / xla_pmap — anything wrapping a
        # sub-jaxpr) are NOT collectives even when they carry an
        # axis_name: their bodies are priced by the recursion below,
        # counting the wrapper too would double-charge the whole input.
        if name not in _NON_COMM and not subs:
            axes = _axis_names(eqn.params)
            if axes:
                nbytes = sum(_nbytes(v.aval) for v in eqn.invars
                             if hasattr(v, "aval"))
                out.append(CollectiveUse(name, axes, nbytes))
        for sub in subs:
            _walk_jaxpr(sub, out)


def scan_collectives(fn: Callable, *abstract_args: Any,
                     **abstract_kwargs: Any) -> List[CollectiveUse]:
    """Trace `fn` abstractly and return every collective it runs.
    Arguments are abstract (ShapeDtypeStruct / eval_shape outputs); no
    device is touched."""
    import jax

    uses: List[CollectiveUse] = []
    jaxpr = jax.make_jaxpr(fn)(*abstract_args, **abstract_kwargs)
    _walk_jaxpr(jaxpr.jaxpr, uses)
    return uses


def abstract_mesh(layout: MeshLayout) -> Any:
    """A `jax.sharding.AbstractMesh` with the layout's axis names/sizes —
    shard_map programs trace against it with no devices."""
    import jax

    return jax.sharding.AbstractMesh(tuple(layout.axis_sizes.values()),
                                     tuple(layout.axis_sizes.keys()))


def _fmt_bytes(n: float) -> str:
    for unit, div in (("GiB", 2 ** 30), ("MiB", 2 ** 20), ("KiB", 2 ** 10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} B"


def check_collectives(layout: MeshLayout, uses: Sequence[CollectiveUse],
                      where: str = "") -> List[Finding]:
    """Findings for collectives that cross DCN. Heavy axes (tp/sp/ep)
    over DCN are warnings; the declared DCN axes (dp/fsdp/pp) are info —
    routing those over DCN is the hybrid-mesh design, the finding just
    carries the bytes estimate."""
    findings: List[Finding] = []
    loc = where or layout.name
    for use in uses:
        if not use.modeled():
            findings.append(Finding(
                "unmodeled-collective", INFO, loc,
                f"{use.primitive} over {use.axes} has no entry in the "
                "collective cost model — byte estimates fall back to "
                f"its raw input size ({_fmt_bytes(float(use.in_bytes))})"
                " and oracle step-time predictions treat it as opaque",
                "add the primitive to analysis.collectives._COST_MODEL"))
        dcn_axes = [a for a in use.axes if layout.dcn_factor(a) > 1]
        if not dcn_axes:
            continue
        heavy = [a for a in dcn_axes if a in HEAVY_AXES]
        cost = _fmt_bytes(use.dcn_bytes(layout))
        if heavy:
            findings.append(Finding(
                "collective-over-dcn", WARNING, loc,
                f"{use.primitive} over {use.axes} crosses DCN on the "
                f"ICI-hungry axis(es) {tuple(heavy)} — est. {cost} "
                "over DCN per call",
                f"keep {tuple(heavy)} inside a slice: put the cross-"
                f"slice parallelism on {tuple(DCN_AXES)} "
                "(HybridMeshConfig dcn_dp/dcn_fsdp/dcn_pp)"))
        elif layout.declared_dcn:
            findings.append(Finding(
                "collective-over-dcn", INFO, loc,
                f"{use.primitive} over {use.axes} rides DCN by design — "
                f"est. {cost} over DCN per call"))
        else:
            # data-like axis crossing slices on a FLAT mesh: acceptable
            # placement, but nobody declared it — say so
            findings.append(Finding(
                "collective-over-dcn", INFO, loc,
                f"{use.primitive} over {use.axes} crosses DCN on a flat "
                f"mesh (nothing declared this placement) — est. {cost} "
                "over DCN per call",
                "declare the cross-slice placement explicitly: "
                "HybridMeshConfig dcn_dp/dcn_fsdp/dcn_pp"))
    return findings


def estimate_training_dcn_traffic(layout: MeshLayout,
                                  abstract_params: Any) -> float:
    """Per-step gradient-sync bytes over DCN for a data-parallel training
    layout: every param's gradient is psum'd over the data axes, so the
    DCN share is 2 * bytes * (d-1)/d with d the dp/fsdp DCN span (the
    total ring-allreduce traffic is independent of how the params
    themselves are sharded)."""
    import jax

    d = layout.dcn_factor("dp") * layout.dcn_factor("fsdp")
    if d <= 1:
        return 0.0
    total = sum(_nbytes(leaf)
                for leaf in jax.tree_util.tree_leaves(abstract_params))
    return 2.0 * total * (d - 1) / d


__all__ = ["CollectiveUse", "HEAVY_AXES", "abstract_mesh",
           "check_collectives", "estimate_training_dcn_traffic",
           "scan_collectives"]
