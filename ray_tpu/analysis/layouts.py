"""Layout-level analysis: run the shard + collective checks against a
whole mesh layout, including the repo's built-in dryrun layouts.

`analyze_layout` is the general entry point the ISSUE describes: a
`MeshConfig`/`HybridMeshConfig`, a PartitionSpec tree + abstract params
(from `jax.eval_shape`), and optionally a function + abstract inputs to
trace for collectives — all deviceless, so a v4 pod layout lints on a
laptop. `analyze_builtin_layouts` applies it to every layout the driver's
`dryrun_multichip` exercises (dcn_dp x tp, dcn_pp x fsdp, dp x pp,
dp x sp, dp x ep); the dryrun path refuses to run a layout that does not
come back clean.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..parallel.mesh import MeshConfig
from ..parallel.multislice import HybridMeshConfig
from .collectives import (CollectiveUse, abstract_mesh, check_collectives,
                          estimate_training_dcn_traffic, scan_collectives)
from .findings import Finding, INFO
from .shardcheck import (DEFAULT_REPLICATED_THRESHOLD, MeshLayout,
                         _nbytes, check_specs)


@dataclass
class LayoutTrace:
    """One dryrun layout's oracle inputs: the deviceless mesh layout,
    the traced collectives, and rough analytic work terms. The roofline
    model (observability.roofline) prices these; the findings-based
    analyzers below reuse the same traces so both surfaces describe one
    program."""

    layout: MeshLayout
    uses: List[CollectiveUse] = field(default_factory=list)
    flops_per_step: float = 0.0
    tokens_per_step: int = 0


def analyze_layout(config: MeshConfig, n_devices: int,
                   num_slices: int = 1, *,
                   param_specs: Any = None,
                   abstract_params: Any = None,
                   data_specs: Any = None,
                   abstract_batch: Any = None,
                   fn: Optional[Callable] = None,
                   abstract_args: Sequence[Any] = (),
                   replicated_threshold: int =
                   DEFAULT_REPLICATED_THRESHOLD,
                   name: str = "") -> List[Finding]:
    """Lint one layout: spec validation + HBM replication check for the
    params, spec validation for the batch, collective/DCN-cost scan for
    `fn(*abstract_args)`. Any piece may be omitted."""
    layout = MeshLayout.from_config(config, n_devices, num_slices,
                                    name=name)
    findings: List[Finding] = []
    if param_specs is not None and abstract_params is not None:
        findings += check_specs(param_specs, abstract_params, layout,
                                replicated_threshold,
                                where=f"{layout.name}/params")
        dcn_bytes = estimate_training_dcn_traffic(layout, abstract_params)
        if dcn_bytes > 0:
            findings.append(Finding(
                "collective-over-dcn", INFO, f"{layout.name}/grad-sync",
                f"est. gradient allreduce over DCN: "
                f"{dcn_bytes / 2 ** 20:.2f} MiB per step"))
    if data_specs is not None and abstract_batch is not None:
        findings += check_specs(data_specs, abstract_batch, layout,
                                replicated_threshold,
                                where=f"{layout.name}/batch")
    if fn is not None:
        findings += check_collectives(
            layout, scan_collectives(fn, *abstract_args),
            where=f"{layout.name}/collectives")
    return findings


# ------------------------------------------------------- builtin layouts


def _abstract_gpt2(cfg) -> Any:
    """Abstract GPT-2 param tree — eval_shape never materializes it."""
    import jax
    import jax.numpy as jnp

    from ..models.gpt2 import gpt2_init

    return jax.eval_shape(
        functools.partial(gpt2_init, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def _sds(shape, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), dtype or jnp.float32)


def analyze_dcn_dp_tp(n_devices: int = 8,
                      replicated_threshold: int =
                      DEFAULT_REPLICATED_THRESHOLD) -> List[Finding]:
    """The dryrun's dcn_dp x tp hybrid GPT-2 training layout: data
    parallelism across 2 slices over DCN, tensor parallelism on ICI."""
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    from ..models.gpt2 import GPT2Config, gpt2_partition_specs

    cfg = GPT2Config.tiny()
    config = HybridMeshConfig(dp=-1, tp=2, dcn_dp=2)
    dp_total = n_devices // 2
    batch = {"tokens": _sds((2 * dp_total, 32), jnp.int32),
             "targets": _sds((2 * dp_total, 32), jnp.int32)}
    data_spec = P(("dp", "fsdp"))
    return analyze_layout(
        config, n_devices, num_slices=2,
        param_specs=gpt2_partition_specs(cfg),
        abstract_params=_abstract_gpt2(cfg),
        data_specs={k: data_spec for k in batch}, abstract_batch=batch,
        replicated_threshold=replicated_threshold, name="dcn_dp_tp")


def _trace_dcn_dp_tp(n_devices: int = 8) -> LayoutTrace:
    """Oracle inputs for the hybrid GPT-2 training layout. The data-
    parallel gradient sync IS a psum of the full param pytree over the
    data axes (the same model `estimate_training_dcn_traffic` prices),
    so it appears here as one explicit CollectiveUse."""
    import jax

    from ..models.gpt2 import GPT2Config
    from ..observability.flops import train_flops_per_token

    cfg = GPT2Config.tiny()
    seq = 32
    layout = MeshLayout.from_config(HybridMeshConfig(dp=-1, tp=2,
                                                     dcn_dp=2),
                                    n_devices, num_slices=2,
                                    name="dcn_dp_tp")
    param_bytes = sum(_nbytes(leaf) for leaf in
                      jax.tree_util.tree_leaves(_abstract_gpt2(cfg)))
    tokens = 2 * (n_devices // 2) * seq
    return LayoutTrace(
        layout=layout,
        uses=[CollectiveUse("psum", ("dp", "fsdp"), param_bytes)],
        flops_per_step=train_flops_per_token(cfg, seq) * tokens,
        tokens_per_step=tokens)


def _trace_pipeline(config: MeshConfig, n_devices: int,
                    num_slices: int, pp: int, data_parallel: int,
                    name: str) -> LayoutTrace:
    """Trace the toy GPipe pipeline (ppermute ring + final-stage psum
    over 'pp') over an abstract mesh."""
    import jax.numpy as jnp

    from ..parallel.pipeline import make_pipeline_fn

    m = 4 * pp
    layout = MeshLayout.from_config(config, n_devices, num_slices,
                                    name=name)
    mesh = abstract_mesh(layout)
    d, batch = 16, data_parallel * m
    # toy tanh-matmul "model": ~6 flops per param per row (fwd+bwd)
    flops = 6.0 * (pp * d * d + pp * d) * batch
    pipe = make_pipeline_fn(
        lambda p, h: jnp.tanh(h @ p[0] + p[1]), mesh, num_microbatches=m)
    params = (_sds((pp, d, d)), _sds((pp, d)))
    uses = scan_collectives(pipe, params, _sds((batch, d)))
    return LayoutTrace(layout=layout, uses=uses, flops_per_step=flops,
                       tokens_per_step=batch)


def _pipeline_findings(config: MeshConfig, n_devices: int,
                       num_slices: int, pp: int, data_parallel: int,
                       name: str) -> List[Finding]:
    """Lint the traced GPipe pipeline's collectives plus the schedule's
    analytic bubble estimate (rule pipeline-bubble). The microbatch
    count follows the M = 4*S sizing rule, so the builtin layouts' own
    estimates stay at INFO."""
    from .pipelines import check_pipeline_schedule

    findings = check_pipeline_schedule(pp, 4 * pp, "gpipe",
                                       where=f"{name}/schedule")
    trace = _trace_pipeline(config, n_devices, num_slices, pp,
                            data_parallel, name)
    return findings + check_collectives(trace.layout, trace.uses,
                                        where=f"{name}/collectives")


def analyze_dcn_pp_fsdp(n_devices: int = 8, **_) -> List[Finding]:
    """The dryrun's dcn_pp x fsdp hybrid: one pipeline stage per slice
    (activations cross DCN — by design), fsdp inside each slice."""
    fsdp = n_devices // 2
    return _pipeline_findings(
        HybridMeshConfig(fsdp=fsdp, dcn_pp=2), n_devices, num_slices=2,
        pp=2, data_parallel=fsdp, name="dcn_pp_fsdp")


def analyze_dp_pp(n_devices: int = 8, **_) -> List[Finding]:
    """The dryrun's flat dp x pp GPipe layout (single slice)."""
    pp = 4
    dp = max(1, n_devices // pp)
    return _pipeline_findings(MeshConfig(dp=dp, pp=pp), n_devices,
                              num_slices=1, pp=pp, data_parallel=dp,
                              name="dp_pp")


def _trace_dp_sp(n_devices: int = 8) -> LayoutTrace:
    """The dryrun's dp x sp ring-attention trace (ppermute over 'sp')."""
    from jax.sharding import PartitionSpec as P

    from ..ops.ring_attention import ring_attention
    from ..parallel.mesh import shard_map

    sp = 4
    dp = max(1, n_devices // sp)
    layout = MeshLayout.from_config(MeshConfig(dp=dp, sp=sp), n_devices,
                                    name="dp_sp")
    batch, seq, heads, hd = 2 * dp, 32, 4, 8
    # causal attention score+value matmuls, fwd only: 2·B·T²·H·hd
    flops = 2.0 * batch * seq * seq * heads * hd
    mesh = abstract_mesh(layout)
    if mesh is None:
        return LayoutTrace(layout=layout, flops_per_step=flops,
                           tokens_per_step=batch * seq)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh, in_specs=(P("dp", "sp"),) * 3,
        out_specs=P("dp", "sp"), check_vma=False)
    qkv = _sds((batch, seq, heads, hd))
    uses = scan_collectives(ring, qkv, qkv, qkv)
    return LayoutTrace(layout=layout, uses=uses, flops_per_step=flops,
                       tokens_per_step=batch * seq)


def analyze_dp_sp(n_devices: int = 8, **_) -> List[Finding]:
    """The dryrun's dp x sp ring-attention layout (ppermute over 'sp')."""
    trace = _trace_dp_sp(n_devices)
    if not trace.uses:
        return []
    return check_collectives(trace.layout, trace.uses,
                             where="dp_sp/collectives")


def _trace_dp_ep(n_devices: int = 8) -> LayoutTrace:
    """The dryrun's dp x ep MoE trace (all_to_all over 'ep')."""
    from jax.sharding import PartitionSpec as P

    from ..ops import moe_ffn
    from ..parallel.mesh import shard_map

    ep = 4
    dp = max(1, n_devices // ep)
    layout = MeshLayout.from_config(MeshConfig(dp=dp, ep=ep), n_devices,
                                    name="dp_ep")
    t_local, d, f, e, k = 8, 16, 32, 8, 2
    tokens = dp * ep * t_local
    # top_k experts x 3 matmuls (gate/up/down) x 2·d·f, fwd only
    flops = 6.0 * d * f * k * tokens
    mesh = abstract_mesh(layout)
    if mesh is None:
        return LayoutTrace(layout=layout, flops_per_step=flops,
                           tokens_per_step=tokens)
    fn = shard_map(
        functools.partial(moe_ffn, top_k=k, capacity_factor=float(e),
                          axis_name="ep"),
        mesh=mesh, in_specs=(P(("dp", "ep")), P(), P("ep"), P("ep")),
        out_specs=P(("dp", "ep")), check_vma=False)
    uses = scan_collectives(fn, _sds((tokens, d)),
                            _sds((d, e)), _sds((e, d, f)),
                            _sds((e, f, d)))
    return LayoutTrace(layout=layout, uses=uses, flops_per_step=flops,
                       tokens_per_step=tokens)


def analyze_dp_ep(n_devices: int = 8, **_) -> List[Finding]:
    """The dryrun's dp x ep MoE layout (all_to_all over 'ep')."""
    trace = _trace_dp_ep(n_devices)
    if not trace.uses:
        return []
    return check_collectives(trace.layout, trace.uses,
                             where="dp_ep/collectives")


BUILTIN_LAYOUTS: Dict[str, Callable[..., List[Finding]]] = {
    "dcn_dp_tp": analyze_dcn_dp_tp,
    "dcn_pp_fsdp": analyze_dcn_pp_fsdp,
    "dp_pp": analyze_dp_pp,
    "dp_sp": analyze_dp_sp,
    "dp_ep": analyze_dp_ep,
}


def analyze_builtin_layouts(
        n_devices: int = 8) -> Dict[str, List[Finding]]:
    """Findings per built-in dryrun layout. All of them must come back
    with nothing above INFO — the dryrun path asserts exactly that before
    running a single step."""
    return {name: fn(n_devices) for name, fn in BUILTIN_LAYOUTS.items()}


def trace_builtin_layouts(n_devices: int = 8) -> Dict[str, LayoutTrace]:
    """Oracle inputs (layout + traced collectives + rough work terms)
    for every built-in dryrun layout — the backend of
    ``observability.roofline.predict_builtin_layouts`` and
    ``ray_tpu analyze --predict-step-time``."""
    fsdp = n_devices // 2
    pp_flat = 4
    return {
        "dcn_dp_tp": _trace_dcn_dp_tp(n_devices),
        "dcn_pp_fsdp": _trace_pipeline(
            HybridMeshConfig(fsdp=fsdp, dcn_pp=2), n_devices,
            num_slices=2, pp=2, data_parallel=fsdp, name="dcn_pp_fsdp"),
        "dp_pp": _trace_pipeline(
            MeshConfig(dp=max(1, n_devices // pp_flat), pp=pp_flat),
            n_devices, num_slices=1, pp=pp_flat,
            data_parallel=max(1, n_devices // pp_flat), name="dp_pp"),
        "dp_sp": _trace_dp_sp(n_devices),
        "dp_ep": _trace_dp_ep(n_devices),
    }


__all__ = ["BUILTIN_LAYOUTS", "LayoutTrace", "analyze_builtin_layouts",
           "analyze_layout", "analyze_dcn_dp_tp", "analyze_dcn_pp_fsdp",
           "analyze_dp_ep", "analyze_dp_pp", "analyze_dp_sp",
           "trace_builtin_layouts"]
