"""ray_tpu.analysis — shardlint: static sharding, collective-cost, and
actor-code analysis.

The runtime's thesis makes distributed bugs statically decidable: sharding
is declarative (named mesh axes + PartitionSpecs), multi-slice placement
is declarative (`HybridMeshConfig` / `multislice.DCN_AXES`), and actor
code is plain Python. So before a single chip is reserved, this package
catches:

- PartitionSpecs that cannot work: unknown axis names, rank overflow,
  axis sizes that do not divide array dims, one axis on two dims
  (`shardcheck`, via `jax.eval_shape` — no devices needed);
- HBM blow-ups: large params left fully replicated (`shardcheck`);
- bandwidth-heavy collectives routed over slow DCN links, with a
  bytes-over-DCN estimate per layout (`collectives`, jaxpr inspection
  against an `AbstractMesh`);
- event-loop stalls: blocking calls inside `async def` actor/serve
  methods, and host syncs inside jitted functions (`astlint`);
- cross-module invariants (`invariants`): lock-discipline races
  (a `self._*` attr mutated both under `with self._lock` and bare),
  the env-knob registry (`RAY_TPU_*` reads — hot-path re-parses,
  inconsistent defaults, undocumented knobs), and jitted pool updaters
  missing `donate_argnums`.

Surfaces: `python -m ray_tpu analyze` (CLI), the dryrun path in
`__graft_entry__.py` (every hybrid layout is linted before it runs), and
`TrainStep.init_state` (spec errors raise before compilation).

`findings` and `astlint` are dependency-free (pure stdlib): the AST lint
runs even where jax is broken or absent. The jax-backed halves
(shardcheck/collectives/layouts) load lazily on first attribute access
(PEP 562), so `from ray_tpu.analysis import lint_path` costs no jax
import.
"""
from .findings import (  # noqa: F401
    ERROR,
    Finding,
    INFO,
    RULES,
    SEVERITIES,
    WARNING,
    at_least,
    errors,
    format_report,
    max_severity,
    sort_findings,
)
from .astlint import lint_file, lint_path, lint_source  # noqa: F401
from .invariants import (  # noqa: F401
    analyze_invariants,
    check_env_knobs,
    collect_env_reads,
    format_knob_table,
    knob_table,
    scan_env_reads,
)
from .pipelines import (  # noqa: F401
    BUBBLE_WARN_FRACTION,
    PIPELINE_SCHEDULES,
    check_pipeline_schedule,
    estimate_bubble_fraction,
)

# name -> submodule for the jax-dependent surface, resolved on demand.
_LAZY = {
    "DEFAULT_REPLICATED_THRESHOLD": "shardcheck",
    "MeshLayout": "shardcheck",
    "check_spec": "shardcheck",
    "check_specs": "shardcheck",
    "CollectiveUse": "collectives",
    "HEAVY_AXES": "collectives",
    "abstract_mesh": "collectives",
    "check_collectives": "collectives",
    "estimate_training_dcn_traffic": "collectives",
    "scan_collectives": "collectives",
    "BUILTIN_LAYOUTS": "layouts",
    "LayoutTrace": "layouts",
    "analyze_builtin_layouts": "layouts",
    "analyze_layout": "layouts",
    "trace_builtin_layouts": "layouts",
}


def __getattr__(name):
    submodule = _LAZY.get(name)
    if submodule is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module("." + submodule, __name__),
                   name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
