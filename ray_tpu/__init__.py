"""ray_tpu: a TPU-native distributed AI framework.

Capability surface of the reference Ray runtime (tasks, actors, objects, gang
scheduling, fault tolerance + the Data/Train/Tune/Serve/RLlib libraries),
re-designed TPU-first: a single conductor control plane with slice-aware
resources, direct worker-to-worker task push, shared-memory host objects, and
JAX/XLA/pjit/Pallas for everything on-device (see ray_tpu.parallel,
ray_tpu.models, ray_tpu.train, ...).

Public core API mirrors /root/reference/python/ray/_private/worker.py:
init :1214, get :2523, put :2655, wait :2720, kill :2901.
"""
from __future__ import annotations

import time

_IMPORT_T0 = time.time()  # the package's import, first line to last

import atexit  # noqa: E402
import os  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from . import exceptions  # noqa: F401
from ._private import worker as _worker_mod
from ._private.conductor import Conductor
from ._private.object_store import ObjectRef  # noqa: F401
from ._private.worker import Worker
from .actor import ActorClass, ActorHandle, exit_actor, get_actor  # noqa: F401
from .remote_function import RemoteFunction

__version__ = "0.1.0"

_conductor: Optional[Conductor] = None
_system_config_prior: Optional[Dict[str, Optional[str]]] = None


def is_initialized() -> bool:
    return _worker_mod.global_worker is not None


def init(address: Optional[str] = None, *,
         num_cpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         namespace: str = "default",
         session_dir: Optional[str] = None,
         worker_env: Optional[Dict[str, str]] = None,
         ignore_reinit_error: bool = False,
         _system_config: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Start a local cluster (conductor in-process) or connect to an existing
    one via ``address="host:port"``.

    ``_system_config`` overrides flags from the central table
    (``ray_tpu._private.config``) — reference semantics of ray.init's
    _system_config over ray_config_def.h."""
    global _conductor, _system_config_prior
    if _system_config:
        from ._private.config import config as _cfg

        _system_config_prior = _cfg.apply(_system_config)
    if is_initialized():
        if ignore_reinit_error:
            return {"address": _worker_mod.global_worker.conductor_address}
        raise RuntimeError("ray_tpu.init() already called; "
                           "use ignore_reinit_error=True to ignore")
    if isinstance(address, str) and address.startswith("ray://"):
        # Ray-Client mode (reference python/ray/util/client): one
        # outbound connection to the head's ClientProxy, the whole
        # public API routed through a server-side driver.
        from .client import connect

        _worker_mod.global_worker = connect(address[len("ray://"):])
        return {"address": _worker_mod.global_worker.conductor_address,
                "client": True}
    if address == "auto":
        # Reference semantics of ray.init("auto") / RAY_ADDRESS.
        address = os.environ.get("RAY_TPU_ADDRESS")
        if not address:
            raise RuntimeError(
                "no RAY_TPU_ADDRESS in the environment; pass "
                "address='host:port' or start a head with "
                "`python -m ray_tpu start --head`")
    elif address is None:
        # Job drivers spawned by the head's JobManager find their cluster
        # here (reference: RAY_ADDRESS).
        address = os.environ.get("RAY_TPU_ADDRESS") or None
    if session_dir is None:
        # must be unique per cluster: a reused dir would make the new
        # conductor restore the PREVIOUS cluster's persistence snapshot
        import uuid as _uuid

        session_dir = os.path.join(
            tempfile.gettempdir(), "ray_tpu",
            f"session_{time.strftime('%Y%m%d-%H%M%S')}_{os.getpid()}"
            f"_{_uuid.uuid4().hex[:8]}")
    os.makedirs(session_dir, exist_ok=True)

    if address is None:
        total: Dict[str, float] = dict(resources or {})
        total.setdefault("CPU", float(num_cpus if num_cpus is not None
                                      else (os.cpu_count() or 1)))
        tpus = _detect_tpu_chips()
        if tpus and "TPU" not in total:
            total["TPU"] = float(tpus)
        # A chip belongs to one process at a time, so ordinary workers are
        # held to the CPU: whoever asked for no chip must not take one
        # from the process that did. Only a lease with a whole-number TPU
        # resource (num_tpus=k) gets a worker bound to k chips, on the TPU
        # platform (conductor._take_idle_or_spawn).
        wenv = {"JAX_PLATFORMS": "cpu"}
        import sys as _sys

        wenv["RAY_TPU_DRIVER_SYS_PATH"] = os.pathsep.join(
            p for p in _sys.path if p and os.path.isdir(p))
        wenv.update(worker_env or {})
        _conductor = Conductor(total, session_dir, worker_env=wenv).start()
        conductor_address = _conductor.address
        # Pre-start workers so first tasks don't pay process cold-start
        # (reference: WorkerPool prestarts language workers, worker_pool.h:156)
        _conductor.handler.prestart_workers(min(int(total.get("CPU", 1)), 4))
    else:
        host, port = address.rsplit(":", 1)
        conductor_address = (host, int(port))

    w = Worker(mode="driver", conductor_address=conductor_address,
               session_dir=session_dir)
    _worker_mod.global_worker = w
    # metrics registered before a prior shutdown() stopped the push loop
    # must resume flowing to THIS cluster's conductor
    try:
        from .util.metrics import _registry as _metrics_registry

        if _metrics_registry._metrics:
            _metrics_registry._ensure_pusher()
    except Exception:  # noqa: BLE001 — metrics are never init-fatal
        pass
    atexit.register(shutdown)
    return {"address": conductor_address, "session_dir": session_dir}


def _detect_tpu_chips(dev_root: str = "/dev") -> int:
    """TPU chips on this host, counted without importing JAX (a driver
    that touched JAX would hold every chip its workers need) — analog of
    the reference's python/ray/_private/accelerators/tpu.py:102-119.
    RAY_TPU_CHIPS overrides; otherwise the device nodes: `accel<n>` on
    older hosts, `vfio/<n>` (numeric names; `vfio/vfio` is the control
    node) on v5e and later."""
    if os.environ.get("RAY_TPU_CHIPS"):
        return int(os.environ["RAY_TPU_CHIPS"])
    import glob

    accels = glob.glob(os.path.join(dev_root, "accel*"))
    if accels:
        return len(accels)
    return sum(1 for p in glob.glob(os.path.join(dev_root, "vfio", "*"))
               if os.path.basename(p).isdigit())


def shutdown() -> None:
    global _conductor, _system_config_prior
    w = _worker_mod.global_worker
    if w is not None:
        # metrics first: the final registry flush needs the conductor
        # connection the worker shutdown is about to close
        try:
            from .util import metrics as _metrics

            _metrics.shutdown()
        except Exception:  # noqa: BLE001 — never block shutdown
            pass
        try:
            # cached weight publishers hold chunk refs against this
            # worker's store; drop them with the cluster they fed.
            # sys.modules check: never IMPORT the fabric (and jax with
            # it) just to shut down a process that never published.
            import sys as _sys

            pub_mod = _sys.modules.get("ray_tpu.weights.publisher")
            if pub_mod is not None:
                pub_mod._reset_publishers()
        except Exception:  # noqa: BLE001 — never block shutdown
            pass
        w.shutdown()
        _worker_mod.global_worker = None
    if _conductor is not None:
        _conductor.stop()
        _conductor = None
    # SIGKILL'ed workers (chaos tests, OOM kills) cannot unlink their shm
    # arena segments; left behind they hold tmpfs RAM across runs. The
    # conductor's stop() sweeps its own session — this covers connects
    # to remote clusters and anything that died since.
    try:
        from ._private.object_store import cleanup_leaked_segments

        cleanup_leaked_segments()
    except Exception:  # noqa: BLE001 — never block shutdown
        pass
    if _system_config_prior is not None:
        # this cluster's _system_config env exports must not leak into
        # the next cluster started in this process
        from ._private.config import config as _cfg

        _cfg.restore(_system_config_prior)
        _system_config_prior = None


def remote(*args, **kwargs):
    """``@remote`` decorator for functions and classes (reference
    python/ray/_private/worker.py `remote`)."""
    if len(args) == 1 and not kwargs and callable(args[0]):
        return _make_remote(args[0], {})
    if args:
        raise TypeError("remote() takes keyword options only, e.g. "
                        "@remote(num_cpus=2)")

    def wrap(fn_or_cls):
        return _make_remote(fn_or_cls, kwargs)

    return wrap


def _make_remote(fn_or_cls, options: Dict[str, Any]):
    if isinstance(fn_or_cls, type):
        return ActorClass(fn_or_cls, options)
    return RemoteFunction(fn_or_cls, options)


def put(value: Any) -> ObjectRef:
    return _require_worker().put(value)


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        timeout: Optional[float] = None):
    return _require_worker().get(refs, timeout=timeout)


async def get_async(ref: ObjectRef):
    """Await an ObjectRef from asyncio code without blocking the loop
    (reference: `await ref` support, python/ray/_private/async_compat)."""
    return await _require_worker().get_async(ref)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True
         ) -> Tuple[List[ObjectRef], List[ObjectRef]]:
    return _require_worker().wait(refs, num_returns=num_returns,
                                  timeout=timeout, fetch_local=fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    w = _require_worker()
    w.conductor.call("kill_actor", actor.actor_id, no_restart, timeout=30.0)


def cancel(ref: ObjectRef, *, force: bool = False) -> None:
    """Cancel the task or actor call producing `ref`.

    Queued work is dropped; running work is interrupted cooperatively
    (force=True kills the executing worker — the guaranteed stop).
    Subsequent get(ref) raises TaskCancelledError. Best-effort like the
    reference: a non-force cancel cannot interrupt native code until it
    re-enters the interpreter."""
    _require_worker().cancel(ref, force=force)


def cluster_resources() -> Dict[str, float]:
    return _require_worker().conductor.call("cluster_resources", timeout=30.0)


def available_resources() -> Dict[str, float]:
    return _require_worker().conductor.call("available_resources",
                                            timeout=30.0)


def nodes() -> List[Dict[str, Any]]:
    return _require_worker().conductor.call("nodes", timeout=30.0)


def _require_worker() -> Worker:
    w = _worker_mod.global_worker
    if w is None:
        raise RuntimeError("ray_tpu.init() must be called first")
    return w


class _RuntimeContext:
    @property
    def worker_id(self) -> str:
        return _require_worker().worker_id

    @property
    def job_id(self) -> str:
        return _require_worker().job_id

    @property
    def is_driver(self) -> bool:
        return _require_worker().mode == "driver"

    @property
    def actor_id(self) -> Optional[str]:
        rt = _require_worker()._actor_runtime
        return rt.actor_id if rt else None

    def get_actor_handle(self) -> Optional[ActorHandle]:
        w = _require_worker()
        rt = w._actor_runtime
        if rt is None:
            return None
        return ActorHandle(rt.actor_id, w.address)


def get_runtime_context() -> _RuntimeContext:
    return _RuntimeContext()


__all__ = [
    "init", "shutdown", "is_initialized", "remote", "put", "get",
    "get_async", "wait",
    "kill", "cancel", "get_actor", "exit_actor", "cluster_resources",
    "available_resources", "nodes", "get_runtime_context", "ObjectRef",
    "ActorClass", "ActorHandle", "exceptions", "__version__",
]

from .util.compile_cache import _stamp_import  # noqa: E402

_stamp_import(__name__, _IMPORT_T0, time.time())
