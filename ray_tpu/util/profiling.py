"""Device profiling: jax.profiler wired into the cluster runtime.

Reference surface: the dashboard/CLI profiling endpoints
(python/ray/dashboard worker profiling, `ray timeline`) — there they
attach py-spy to a worker; on TPU the interesting profile is the DEVICE
trace, so the integration is jax.profiler (XLA's profiler: HLO ops,
TPU step traces, memory viewer) captured either in-process or remotely
on any worker/actor via the worker RPC plane. Traces land in the
session dir (`{session}/profiles/<tag>`) where TensorBoard's profile
plugin (or xprof) reads them.

Driver-side:
    with ray_tpu.util.profiling.profile("step10"):   # in-process
        train_step(...)
    ray_tpu.util.profiling.profile_actor(handle, seconds=5)  # remote
Annotations: `annotate("fwd")` marks regions inside jitted host code
(jax.profiler.TraceAnnotation) so they show up on the trace timeline.
The serving engine's loop wraps its boundaries in them (the `engine.*`
spans, models/engine.py) on a thread `name_thread` labels `cb-engine`.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Optional

_ACTIVE_DIR: Optional[str] = None


def _default_dir(tag: Optional[str]) -> str:
    from ray_tpu._private.worker import global_worker

    base = getattr(global_worker, "session_dir", None) or "/tmp/ray_tpu"
    tag = tag or time.strftime("%Y%m%d-%H%M%S")
    return os.path.join(base, "profiles", tag)


def start_profile(tag: Optional[str] = None,
                  log_dir: Optional[str] = None) -> str:
    """Begin a jax.profiler trace; returns the trace directory."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is not None:
        raise RuntimeError(f"profile already running into {_ACTIVE_DIR}")
    import jax

    d = log_dir or _default_dir(tag)
    os.makedirs(d, exist_ok=True)
    jax.profiler.start_trace(d)
    _ACTIVE_DIR = d
    return d


def stop_profile() -> str:
    """End the running trace; returns its directory. On a stop_trace
    failure the module guard stays set, keeping state in sync with
    XLA's (still-open) session so the stop can be retried."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is None:
        raise RuntimeError("no profile running")
    import jax

    jax.profiler.stop_trace()
    d = _ACTIVE_DIR
    _ACTIVE_DIR = None
    return d


@contextlib.contextmanager
def profile(tag: Optional[str] = None, log_dir: Optional[str] = None):
    """Context-managed device trace around a block of work."""
    d = start_profile(tag, log_dir)
    try:
        yield d
    finally:
        stop_profile()


def annotate(name: str, **kwargs):
    """Named region on the profiler timeline (TraceAnnotation)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kwargs)


def name_thread(name: str) -> None:
    """Label the calling thread for the profiler: a trace's host lines
    carry the OS thread's name, which `threading.Thread(name=)` does
    not set before Python 3.14. Linux only; elsewhere the line keeps
    the process's name."""
    import ctypes

    try:
        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (AttributeError, OSError):  # no prctl: not Linux
        pass


def save_device_memory_profile(path: Optional[str] = None) -> str:
    """Snapshot the device memory profile (pprof format) — jax's
    memory-leak hunting tool, surfaced next to the traces."""
    import jax

    if path is None:
        path = os.path.join(_default_dir(None) + "-memory", "memory.prof")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    jax.profiler.save_device_memory_profile(path)
    return path


# ------------------------------------------------------ remote profiling


def profile_actor(actor, seconds: float = 5.0,
                  tag: Optional[str] = None) -> str:
    """Capture a device trace ON the actor's worker process for
    `seconds` while it keeps serving calls; returns the trace dir path
    on that worker's host. The actor's jitted work during the window
    shows up in the trace (reference: dashboard worker profiling, but
    device-level)."""
    from ray_tpu._private.worker import global_worker

    addr = getattr(actor, "_address", None)
    if addr is None:
        raise TypeError("profile_actor expects an ActorHandle")
    tag = tag or f"actor-{time.strftime('%H%M%S')}"
    client = global_worker.clients.get(tuple(addr))
    d = client.call("start_device_profile", tag, timeout=30.0)
    try:
        time.sleep(seconds)
        return client.call("stop_device_profile", timeout=60.0) or d
    except BaseException:
        # never leave the remote worker tracing forever (unbounded trace
        # growth + every later profile rejected)
        try:
            client.notify("stop_device_profile")
        except Exception:  # noqa: BLE001 — worker may be gone
            pass
        raise


def list_profiles() -> list:
    """Profile trace dirs in this session (driver-local host)."""
    from ray_tpu._private.worker import global_worker

    base = getattr(global_worker, "session_dir", None)
    if base is None:
        return []
    root = os.path.join(base, "profiles")
    if not os.path.isdir(root):
        return []
    return sorted(os.path.join(root, d) for d in os.listdir(root))
