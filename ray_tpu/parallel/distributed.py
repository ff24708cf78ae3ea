"""Multi-host mesh rendezvous: conductor-KV-driven jax.distributed init.

The reference bootstraps its data plane with a NCCL rendezvous
(MASTER_ADDR + torch dist.init_process_group — train/torch/config.py:64-117);
the TPU-native equivalent is `jax.distributed.initialize(coordinator,
num_processes, process_id)`, after which every process sees the GLOBAL
device set and a single jitted SPMD program spans hosts with XLA
collectives over ICI/DCN (SURVEY.md §5.8, §7 step 4).

Rank 0 picks a free port on its host, publishes `host:port` under a
group key in the conductor KV; other ranks poll the key. This is the
same pattern as the reference's `NCCLUniqueIDStore` named actor
(util/collective/collective_group/nccl_collective_group.py:28-50), minus
the actor: the KV is already the cluster's rendezvous plane.
"""
from __future__ import annotations

import json
import logging
import os
import socket
import time
from typing import Callable, Dict, List, Optional, Tuple

_NAMESPACE = "_jax_distributed"


def _free_port(host: str) -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _local_ip(peer_host: str = "8.8.8.8") -> str:
    """Best-effort address other hosts can reach us on: the source IP of
    the route to `peer_host`. Pass the conductor's host — gang members
    must reach each other on the network they reach the head on (a
    public-internet probe can return an unroutable interface)."""
    env = os.environ.get("RAY_TPU_NODE_IP")
    if env:
        return env
    if peer_host in ("127.0.0.1", "localhost", "::1"):
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect((peer_host, 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def rendezvous_coordinator(kv_put: Callable, kv_get: Callable,
                           group_key: str, rank: int,
                           timeout: float = 120.0,
                           host: Optional[str] = None) -> str:
    """Agree on a coordinator address for a worker gang. Rank 0 claims
    it; everyone returns `host:port`."""
    key = f"{group_key}/coordinator".encode()
    if rank == 0:
        host = host or _local_ip()
        addr = f"{host}:{_free_port('0.0.0.0')}"
        kv_put(key, addr.encode(), namespace=_NAMESPACE)
        return addr
    deadline = time.monotonic() + timeout
    sleep = 0.01
    while time.monotonic() < deadline:
        got = kv_get(key, namespace=_NAMESPACE)
        if got:
            return got.decode()
        time.sleep(sleep)
        sleep = min(sleep * 2, 0.5)
    raise TimeoutError(f"no coordinator published for {group_key} "
                       f"within {timeout}s")


# ------------------------------------------------------ slice rendezvous

def detect_slice_id() -> Optional[int]:
    """This process's TPU slice id from the runtime env, or None when no
    slice identity is advertised (single-slice / non-megascale jobs).
    RAY_TPU_SLICE_ID is the explicit override; MEGASCALE_SLICE_ID is what
    the multislice TPU runtime exports on every worker VM."""
    for var in ("RAY_TPU_SLICE_ID", "MEGASCALE_SLICE_ID"):
        v = os.environ.get(var)
        if v is not None and v != "":
            return int(v)
    return None


def rendezvous_slices(kv_put: Callable, kv_get: Callable, group_key: str,
                      rank: int, world: int, slice_id: Optional[int],
                      timeout: float = 120.0
                      ) -> Optional[Dict[int, List[int]]]:
    """Each rank publishes its slice id (or a "none" marker) under the
    group key; rank 0 polls the per-rank keys, assembles the slice map
    {slice_id: sorted ranks}, and publishes it under one assembled key
    that the other ranks poll — O(world) conductor RPCs total instead of
    every rank polling every other rank. Same KV-rendezvous pattern as
    the coordinator claim above — the conductor KV is the cluster's
    rendezvous plane.

    Slice identity must be all-or-none across the gang: mixed
    some-ranks-have-a-slice-id gangs (env leak, heterogeneous hosts)
    raise ValueError on EVERY rank instead of deadlocking with
    mismatched process ids. Returns None when no rank has a slice id
    (single-slice gang, no grouping needed)."""
    kv_put(f"{group_key}/slice/{rank}".encode(),
           ("none" if slice_id is None else str(int(slice_id))).encode(),
           namespace=_NAMESPACE)
    assembled_key = f"{group_key}/slice_assembled".encode()
    deadline = time.monotonic() + timeout
    sleep = 0.01

    if rank != 0:
        while True:
            v = kv_get(assembled_key, namespace=_NAMESPACE)
            if v:
                rec = json.loads(v.decode())
                if "__error__" in rec:
                    raise ValueError(rec["__error__"])
                if not rec:
                    return None
                return {int(s): rs for s, rs in sorted(rec.items())}
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"slice rendezvous for {group_key}: rank 0 did not "
                    f"publish the assembled slice map within {timeout}s")
            time.sleep(sleep)
            sleep = min(sleep * 2, 0.5)

    got: Dict[int, Optional[int]] = {rank: slice_id}
    while len(got) < world:
        for r in range(world):
            if r in got:
                continue
            v = kv_get(f"{group_key}/slice/{r}".encode(),
                       namespace=_NAMESPACE)
            if v:
                s = v.decode()
                got[r] = None if s == "none" else int(s)
        if len(got) < world:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"slice rendezvous for {group_key}: only "
                    f"{len(got)}/{world} ranks published within "
                    f"{timeout}s")
            time.sleep(sleep)
            sleep = min(sleep * 2, 0.5)

    missing = sorted(r for r, s in got.items() if s is None)
    if missing:
        if len(missing) < world:
            msg = (f"inconsistent slice identity in {group_key}: ranks "
                   f"{missing} have no slice id while the rest do — "
                   f"slice identity must be all-or-none across the gang")
            kv_put(assembled_key, json.dumps({"__error__": msg}).encode(),
                   namespace=_NAMESPACE)
            raise ValueError(msg)
        kv_put(assembled_key, b"{}", namespace=_NAMESPACE)
        return None

    slice_map: Dict[int, List[int]] = {}
    for r, s in got.items():
        slice_map.setdefault(s, []).append(r)
    slice_map = {s: sorted(rs) for s, rs in sorted(slice_map.items())}
    kv_put(assembled_key,
           json.dumps({str(s): rs for s, rs in slice_map.items()}).encode(),
           namespace=_NAMESPACE)
    return slice_map


def publish_slice_map(kv_put: Callable, group_key: str,
                      slice_map: Dict[int, List[int]],
                      process_ids: Dict[int, int], world: int) -> None:
    """Write the gang's slice map under `{group_key}/slice_map` where
    `ray_tpu.util.state.slice_topology` reads it (rank 0 only)."""
    kv_put(f"{group_key}/slice_map".encode(),
           json.dumps({"slices": {str(s): rs
                                  for s, rs in slice_map.items()},
                       "process_ids": {str(r): p
                                       for r, p in process_ids.items()},
                       "world": world}).encode(),
           namespace=_NAMESPACE)


def slice_process_ids(slice_map: Dict[int, List[int]]) -> Dict[int, int]:
    """Slice-major process-id assignment: ranks of the same slice get
    CONTIGUOUS process ids (what `mesh_utils.create_hybrid_device_mesh`
    with process-granules and the DCN-outer axis order expect), with
    rank 0's slice first so rank 0 keeps process id 0 — it hosts the
    jax.distributed coordinator service."""
    rank0_slice = next(s for s, rs in slice_map.items() if 0 in rs)
    order = sorted(slice_map, key=lambda s: (s != rank0_slice, s))
    pids: Dict[int, int] = {}
    pid = 0
    for s in order:
        for r in sorted(slice_map[s]):
            pids[r] = pid
            pid += 1
    return pids


def initialize_jax_distributed(group_key: str, rank: int, world: int,
                               kv_put: Optional[Callable] = None,
                               kv_get: Optional[Callable] = None,
                               timeout: float = 120.0,
                               host: Optional[str] = None,
                               slice_id: Optional[int] = None,
                               ) -> Optional[Dict[str, object]]:
    """Run the coordinator rendezvous and `jax.distributed.initialize`.

    Must be called before any other jax API touches the backend. With
    world == 1 this is a no-op (single-process SPMD needs no service).
    kv_put/kv_get default to the connected cluster's conductor KV.

    With `slice_id` (explicit, or detected from the runtime env by the
    caller via `detect_slice_id`), ranks first rendezvous their slice
    membership: process ids are reassigned slice-major so processes of
    one slice are contiguous in the jax.distributed job, and rank 0
    publishes the slice map under `{group_key}/slice_map` where the
    state API (`ray_tpu.util.state.slice_topology`) finds it. Returns
    the slice info dict ({"slice_id", "slices", "process_ids"}) when a
    slice rendezvous ran, else None.
    """
    if world <= 1:
        return None
    if kv_put is None or kv_get is None:
        from .._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is None:
            raise RuntimeError(
                "initialize_jax_distributed needs a connected ray_tpu "
                "worker (or explicit kv_put/kv_get)")
        kv_put = lambda k, v, namespace: w.conductor.call(  # noqa: E731
            "kv_put", k, v, True, namespace, timeout=10.0)
        kv_get = lambda k, namespace: w.conductor.call(  # noqa: E731
            "kv_get", k, namespace, timeout=10.0)
        if host is None:
            # advertise on the interface that reaches the conductor
            host = _local_ip(w.conductor_address[0])

    process_id = rank
    slice_info: Optional[Dict[str, object]] = None
    # Always rendezvous (slice_id may be None): slice identity must be
    # all-or-none across the gang, and only the rendezvous can tell this
    # rank whether the OTHERS have one — a mixed gang fails fast with a
    # clear error on every rank instead of deadlocking on mismatched
    # process ids.
    slice_map = rendezvous_slices(kv_put, kv_get, group_key, rank,
                                  world, slice_id, timeout)
    if slice_map is not None:
        pids = slice_process_ids(slice_map)
        process_id = pids[rank]
        slice_info = {"slice_id": int(slice_id),
                      "slices": {int(s): rs
                                 for s, rs in slice_map.items()},
                      "process_ids": {int(r): p
                                      for r, p in pids.items()}}
        if rank == 0:
            publish_slice_map(kv_put, group_key, slice_map, pids, world)

    coordinator = rendezvous_coordinator(kv_put, kv_get, group_key, rank,
                                         timeout, host=host)
    import jax

    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu" or \
            getattr(jax.config, "jax_platforms", None) == "cpu":
        # CPU-pinned gangs (tests, host-side data/eval work): the
        # default CPU client has no cross-process collectives ("not
        # implemented on the CPU backend"); gloo is jaxlib's portable
        # implementation.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=world,
                               process_id=process_id)
    return slice_info


def is_jax_distributed_initialized() -> bool:
    """True once jax.distributed.initialize succeeded in this process."""
    import jax

    return bool(jax.distributed.is_initialized())


def setup_jax_distributed(timeout: float = 120.0) -> Tuple[int, int]:
    """Inside a JaxTrainer(mode="workers") train_fn: rendezvous this
    worker gang into one jax.distributed job and return (rank, world).

    JaxTrainer performs this automatically before train_fn when
    ScalingConfig.setup_jax_distributed (the default) — calling it again
    is a no-op, so train_fns written for older versions keep working.

    After this returns, `jax.devices()` is the GLOBAL device set across
    all gang workers; build a Mesh over it (parallel.make_mesh) and jit
    normally — the reference's prepare_model/DDP step
    (train_loop_utils.py:158) has no equivalent here because XLA owns
    gradient reduction.
    """
    from ..train.session import get_context

    ctx = get_context()
    if not is_jax_distributed_initialized():
        group_key = getattr(ctx, "jax_dist_key", None) or \
            f"group/{ctx.experiment_name}"
        # slice identity: the runtime env (MEGASCALE_SLICE_ID) is ground
        # truth when present — gang placement does not guarantee host
        # order follows physical slice boundaries, so the trainer's
        # rank-arithmetic assignment (ScalingConfig.num_slices) is only
        # the fallback for runtimes that advertise no slice identity.
        detected = detect_slice_id()
        assigned = getattr(ctx, "slice_id", None)
        slice_id = detected if detected is not None else assigned
        if detected is not None and assigned is not None and \
                detected != assigned:
            logging.getLogger(__name__).warning(
                "rank %d: trainer assigned slice %s but the TPU runtime "
                "reports slice %s; using the runtime's value",
                ctx.rank, assigned, detected)
        info = initialize_jax_distributed(group_key, ctx.rank,
                                          ctx.world_size, timeout=timeout,
                                          slice_id=slice_id)
        if info is not None:
            ctx.slice_map = info["slices"]
    return ctx.rank, ctx.world_size
