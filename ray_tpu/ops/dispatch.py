"""Shared dispatch for the Pallas kernels: which implementation ran,
interpret mode, and per-shard execution.

A kernel entry point (`flash_attention`, `linear_cross_entropy`) chooses
between its Mosaic kernel and its XLA reference once per traced shape.
That choice is recorded here so a caller reads it (`kernel_choices()`)
instead of inferring it from speed: `chip_smoke.py` and the benchmark
fail when a shape they expected on the kernel took the reference.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import shard_map

# Batch-like mesh axes (TrainStep's default data_spec) and the head axis
# (megatron column split) of the canonical mesh, parallel/mesh.MESH_AXES.
DATA_AXES: Tuple[str, ...] = ("dp", "fsdp")
HEAD_AXIS = "tp"

_lock = threading.Lock()
_choices: Dict[Tuple[str, Tuple[int, ...]], Dict[str, Any]] = {}
_interpret_depth = 0


def record_choice(op: str, shape: Sequence[int], choice: str,
                  reason: str = "", shards: int = 1, **chosen: Any) -> None:
    """Called at trace time by a kernel entry point: `choice` is
    "pallas" or "reference", `reason` says why a reference was taken,
    `shards` is how many per-device pieces `per_shard` cut the call
    into (1: the call sees the global shape), `chosen` what else the
    entry point selected from the shape (flash attention: each kernel's
    blocks and the share of the square they compute)."""
    key = (op, tuple(int(s) for s in shape))
    with _lock:
        _choices[key] = {"op": op, "shape": key[1], "choice": choice,
                         "reason": reason, "shards": int(shards), **chosen}


def kernel_choices(op: Optional[str] = None) -> list:
    """One entry per (op, traced global shape), newest trace winning."""
    with _lock:
        return [dict(v) for v in _choices.values()
                if op is None or v["op"] == op]


def reset_kernel_choices() -> None:
    with _lock:
        _choices.clear()


@contextlib.contextmanager
def pallas_interpret():
    """Trace the kernels in Pallas interpret mode inside this block: how
    CPU tests drive the TPU code path (same effect as
    RAY_TPU_PALLAS_INTERPRET=1, scoped to the caller)."""
    global _interpret_depth
    with _lock:
        _interpret_depth += 1
    try:
        yield
    finally:
        with _lock:
            _interpret_depth -= 1


def interpret_forced() -> bool:
    return (_interpret_depth > 0
            or os.environ.get("RAY_TPU_PALLAS_INTERPRET", "0") == "1")


def backend_reason() -> str:
    """Why no Mosaic kernel can run in this process; "" on a TPU backend
    or in interpret mode."""
    if interpret_forced() or jax.default_backend() == "tpu":
        return ""
    return f"backend is {jax.default_backend()}, not tpu"


def live_axes(names: Sequence[str]) -> Tuple[str, ...]:
    """The axes among `names` that the ambient mesh (`jax.set_mesh`,
    which TrainStep establishes) splits over more than one device; ()
    without a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    sizes = dict(mesh.shape)
    return tuple(n for n in names if sizes.get(n, 1) > 1)


def shard_axes(dim: int, names: Sequence[str]) -> Tuple[str, ...]:
    """`live_axes(names)` when their product divides `dim`, else ()."""
    axes = live_axes(names)
    return axes if dim % axes_size(axes) == 0 else ()


def axes_size(axes: Sequence[str]) -> int:
    """Number of shards the ambient mesh cuts along `axes` together."""
    sizes = dict(jax.sharding.get_abstract_mesh().shape)
    return math.prod(sizes[n] for n in axes)


def per_shard(fn: Callable, args: Sequence[jax.Array],
              in_specs: Sequence[P], out_specs: Any):
    """Run `fn` on each device's shard of `args`.

    A Mosaic custom call has no sharding rule: left to the partitioner,
    a kernel inside a jitted step with sharded inputs is all-gathered and
    every chip runs it on the GLOBAL batch - right answer, n_chips times
    the work. Under an ambient mesh this wraps the call in `shard_map`
    over the axes the specs name (built from `shard_axes`, so only axes
    the kernel is independent along and the mesh really splits). Without
    an ambient mesh, or with nothing to split, `fn` runs as is."""
    if not any(e is not None for s in in_specs for e in s):
        return fn(*args)
    return shard_map(fn, mesh=jax.sharding.get_abstract_mesh(),
                     in_specs=tuple(in_specs), out_specs=out_specs,
                     check_vma=False)(*args)
