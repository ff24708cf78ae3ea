"""Config dataclasses — API surface of the reference's
python/ray/air/config.py (ScalingConfig/RunConfig/CheckpointConfig/
FailureConfig) plus the TPU-native ShardingConfig the reference cannot
express (SURVEY.md §2.3: reference parallelism is DP-only; TP/PP/SP/EP
delegated to wrapped frameworks)."""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class ScalingConfig:
    """Reference air/config.py ScalingConfig: num_workers + resources.
    Here: worker processes for host-side work; chips belong to the mesh.
    A mode="workers" rank computes on the CPU unless
    resources_per_worker asks for chips ({"TPU": n}): only a lease with a
    TPU resource is bound to one (conductor chip workers)."""

    num_workers: int = 1
    resources_per_worker: Dict[str, float] = field(default_factory=dict)
    # mode="workers": rendezvous the gang into one jax.distributed job
    # BEFORE train_fn runs (the reference does process-group setup for
    # the user — train/torch/config.py:64-117). Opt out for gangs doing
    # pure host-side work with no jax in the loop.
    setup_jax_distributed: bool = True
    # mode="workers" on a multi-slice pod: how many TPU slices the gang
    # spans. Workers are assigned slice ids contiguously (the trainer's
    # gang placement packs a slice's hosts together) and the
    # jax.distributed rendezvous groups process ids slice-major so DCN
    # axes of a HybridMeshConfig land across slices.
    num_slices: int = 1
    # elastic floor (ray_tpu.resilience): when a restart finds less
    # schedulable capacity than num_workers (host quarantined / slice
    # preempted), the gang re-forms at the largest feasible size >= this
    # — multi-slice gangs shrink by whole slices and a ShardingConfig
    # whose dcn_dp equals num_slices follows. None = never shrink.
    min_workers: Optional[int] = None
    # MPMD pipeline parallelism (ray_tpu.mpmd.PipelineTrainer): how many
    # separately-compiled pipeline stages the job runs, one stage-gang
    # per slice. 1 = no MPMD pipeline (single-program SPMD; the `pp`
    # mesh axis remains the in-program GPipe alternative).
    num_stages: int = 1


def assign_worker_slices(num_workers: int, num_slices: int) -> list:
    """Contiguous balanced slice assignment for a worker gang: rank
    order == host order under STRICT_PACK, so contiguous ranks share a
    slice's hosts. Returns one slice id per rank, or all-None for
    single-slice gangs (no slice rendezvous needed). Used as the
    fallback when the TPU runtime advertises no slice identity
    (parallel.distributed.detect_slice_id)."""
    if num_slices <= 1:
        return [None] * num_workers
    if num_workers % num_slices != 0:
        raise ValueError(
            f"num_workers={num_workers} not divisible by "
            f"num_slices={num_slices}")
    return [i * num_slices // num_workers for i in range(num_workers)]


@dataclass
class ShardingConfig:
    """Named mesh axis sizes (new capability; -1 fills remaining devices).
    Maps 1:1 onto parallel.MeshConfig."""

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1
    remat: bool = False  # jax.checkpoint the model forward
    # DCN (cross-slice) axis sizes for multi-slice pods; the plain axes
    # above then size the ICI mesh WITHIN one slice. All 1 = single
    # slice, lowers to a flat MeshConfig exactly as before.
    dcn_dp: int = 1
    dcn_fsdp: int = 1
    dcn_pp: int = 1

    @property
    def is_hybrid(self) -> bool:
        return any(v != 1 for v in (self.dcn_dp, self.dcn_fsdp,
                                    self.dcn_pp))

    def mesh_config(self):
        if self.is_hybrid:
            from ..parallel.multislice import HybridMeshConfig

            return HybridMeshConfig(
                dp=self.dp, fsdp=self.fsdp, pp=self.pp, sp=self.sp,
                ep=self.ep, tp=self.tp, dcn_dp=self.dcn_dp,
                dcn_fsdp=self.dcn_fsdp, dcn_pp=self.dcn_pp)
        from ..parallel.mesh import MeshConfig

        return MeshConfig(dp=self.dp, fsdp=self.fsdp, pp=self.pp,
                          sp=self.sp, ep=self.ep, tp=self.tp)

    def build_mesh(self, devices=None):
        """Lower to a jax Mesh: hybrid (slice-topology discovery + DCN
        block assembly) when any dcn_* axis is set, flat otherwise."""
        return self.mesh_config().build(devices)


@dataclass
class CheckpointConfig:
    """Reference air/config.py CheckpointConfig (keep top-K by metric)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclass
class FailureConfig:
    """Reference air/config.py FailureConfig."""

    max_failures: int = 0


@dataclass
class RunConfig:
    """Reference air/config.py RunConfig."""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    verbose: int = 1

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.join(
            os.path.expanduser("~"), "ray_tpu_results")
        return os.path.join(base, self.name or "experiment")
