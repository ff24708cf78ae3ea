"""The run's stdout belongs to one line. At process start descriptor 1 is
duplicated for the result and then pointed at descriptor 2, so nothing
else in the process or its children (libtpu, the profiler, logging, a
thread that outlives the window) can write to the run's stdout. The result
is written once, to the kept descriptor, and the process leaves by
`os._exit`."""
from __future__ import annotations

import json
import math
import os
import sys
from typing import Any, Dict, List, NoReturn, Optional

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")

_kept_fd: Optional[int] = None
_children: List[Any] = []  # subprocess.Popen objects this run started


def register_child(proc: Any) -> None:
    """A process this run started: whatever way the run ends, it is
    ended and waited for first."""
    _children.append(proc)


def reap_children() -> None:
    for proc in _children:
        if proc.poll() is None:
            proc.kill()
        try:
            proc.wait(timeout=10.0)
        except Exception:  # noqa: BLE001 - leaving anyway
            pass


def take_stdout() -> int:
    """Keep descriptor 1 for the result and send everything else that
    writes to it to stderr. Idempotent."""
    global _kept_fd
    if _kept_fd is None:
        sys.stdout.flush()
        _kept_fd = os.dup(1)
        os.dup2(2, 1)
    return _kept_fd


def check_result(result: Dict[str, Any], traced: bool) -> None:
    """Raise ValueError unless `result` is exactly the contract's object."""
    extra = set(result) - set(RESULT_KEYS) - {"breakdown"}
    missing = set(RESULT_KEYS) - set(result)
    if extra or missing:
        raise ValueError(f"last line keys: extra {sorted(extra)}, "
                         f"missing {sorted(missing)}")
    if not traced and "breakdown" in result:
        raise ValueError("breakdown belongs to a traced run")
    if not isinstance(result["correct"], bool):
        raise ValueError("correct must be a boolean")
    for k in ("attempted", "failed"):
        if not isinstance(result[k], int) or result[k] < 0:
            raise ValueError(f"{k} must be a count, got {result[k]!r}")
    if not result["metrics"]:
        raise ValueError("no metric to report")
    for name, m in result["metrics"].items():
        if set(m) != {"value", "unit"}:
            raise ValueError(f"metric {name} has keys {sorted(m)}")
        v = m["value"]
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v):
            raise ValueError(f"metric {name} is not a finite number: {v!r}")
    dev = result["device"]
    want = set(DEVICE_KEYS) | ({"busy_s", "window_s"} if traced else set())
    if set(dev) != want:
        raise ValueError(f"device keys {sorted(dev)}, want {sorted(want)}")
    if traced:
        busy, window = dev["busy_s"], dev["window_s"]
        if not (math.isfinite(busy) and math.isfinite(window)
                and 0.0 < busy <= window):
            raise ValueError(
                f"need 0 < busy_s <= window_s, got {busy} and {window}")


def emit_and_exit(result: Dict[str, Any], traced: bool) -> NoReturn:
    """Validate, write the one line to the kept descriptor, leave."""
    fd = take_stdout()
    try:
        check_result(result, traced)
        line = json.dumps(result, allow_nan=False)
    except ValueError as e:
        fail(f"the result is not the contract's object: {e}")
    reap_children()
    sys.stderr.flush()
    os.write(fd, (line + "\n").encode())
    os._exit(0)


def fail(message: str, code: int = 1) -> NoReturn:
    """No result: the reason goes to stderr and the code is not 0."""
    reap_children()
    sys.stderr.write(f"benchmark: FAILED: {message}\n")
    sys.stderr.flush()
    os._exit(code)
