"""JAX's persistent compilation cache, enabled the same way by every
process that will own a chip (chip_smoke.py's children, the benchmark,
chip workers): compiled programs are shared between those processes and
found again by the next run.

The directory is part of each entry's key, so it must not move: where
JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory is
set here; otherwise it is `<checkout>/.xla_cache` (git-ignored), never a
name made from a pid, a time or tempfile.
"""
from __future__ import annotations

import os
import threading
from typing import Dict

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".xla_cache")

_lock = threading.Lock()
_counts: Dict[str, float] = {"hits": 0, "misses": 0, "compiles": 0,
                             "compile_s": 0.0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _lock:
            _counts["misses"] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    # one event per program handed to the backend, timed around
    # compile-or-fetch-from-cache: on a hit it is the retrieval time
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _counts["compiles"] += 1
            _counts["compile_s"] += float(duration)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    global _listening
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # keep every program: the serving programs (decode tick, per-length
    # prefills) compile in well under JAX's default one-second threshold,
    # and a replica process otherwise compiles them cold each start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
    return cache_dir


def compile_cache_counts() -> Dict[str, float]:
    """This process since `enable_compile_cache`: persistent-cache `hits`
    and `misses` (entries written), programs handed to the backend
    (`compiles`) and the seconds that took (`compile_s`, set-up time)."""
    with _lock:
        out = dict(_counts)
    out["compile_s"] = round(out["compile_s"], 3)
    return out
