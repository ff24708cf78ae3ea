"""Worker process spawning, shared by the conductor's head-local pool and
per-host node agents (reference: raylet WorkerPool starting
default_worker.py, src/ray/raylet/worker_pool.h:343).

Two paths:
- fork server (default): a pre-warmed template process forks
  workers in ~10ms (see fork_server.py) — the analog of the reference
  pool's prestarted workers, sized for actor churn.
- direct subprocess: cold interpreter start (~200ms); the fallback when
  the fork server is unavailable (non-linux, chip workers, which start
  with the full `site` so the TPU plugin is found as in the driver, or
  the template died).
"""
from __future__ import annotations

import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, Optional, Tuple


class ForkedProc:
    """Popen-shaped handle for a fork-server worker. The worker is the
    TEMPLATE's child (the template reaps it), so liveness is probed with
    signal 0 instead of waitpid; the exit code is unknowable here and
    reported as 0."""

    def __init__(self, pid: int):
        self.pid = pid
        self.returncode: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        try:
            os.kill(self.pid, 0)
        except ProcessLookupError:
            self.returncode = 0
            return 0
        except PermissionError:
            return None
        return None

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired("forked-worker", timeout)
            time.sleep(0.01)
        return self.returncode or 0

    def send_signal(self, sig: int) -> None:
        try:
            os.kill(self.pid, sig)
        except ProcessLookupError:
            self.returncode = self.returncode if self.returncode is not None \
                else 0

    def terminate(self) -> None:
        self.send_signal(signal.SIGTERM)

    def kill(self) -> None:
        self.send_signal(signal.SIGKILL)


class _ForkServer:
    """Client + lifecycle for one template process, keyed by session."""

    def __init__(self, sock_path: str, proc: subprocess.Popen):
        self.sock_path = sock_path
        self.proc = proc
        self.lock = threading.Lock()

    def spawn(self, env: Dict[str, str], log_path: str) -> ForkedProc:
        req = pickle.dumps({"env": env, "log_path": log_path})
        with self.lock:  # template serves sequentially
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                conn.settimeout(10.0)
                conn.connect(self.sock_path)
                conn.sendall(struct.pack("<I", len(req)) + req)
                buf = b""
                while len(buf) < 4:
                    chunk = conn.recv(4 - len(buf))
                    if not chunk:
                        raise EOFError("fork server closed mid-reply")
                    buf += chunk
            finally:
                conn.close()
        (pid,) = struct.unpack("<i", buf)
        return ForkedProc(pid)

    def stop(self) -> None:
        try:
            self.proc.terminate()
            self.proc.wait(timeout=3.0)
        except Exception:  # noqa: BLE001 — best-effort teardown
            try:
                self.proc.kill()
            except OSError:
                pass
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass


_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

_fork_servers: Dict[str, _ForkServer] = {}
_fork_servers_lock = threading.Lock()


def _apply_no_site_paths(env: Dict[str, str]) -> None:
    """-S/PYTHONPATH wiring shared by both spawn paths: skip `site`
    (.pth processing and user-site discovery, cold-start time a host-side
    worker doesn't need), re-exposing site packages via PYTHONPATH. Chip
    workers (conductor.chip_worker_env) set RAY_TPU_WORKER_FULL_SITE=1
    and start with the full site."""
    import site

    paths = list(site.getsitepackages())
    paths.append(_REPO_ROOT)
    if env.get("PYTHONPATH"):
        paths.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(paths)


def _get_fork_server(session_dir: str,
                     base_env: Dict[str, str]) -> Optional[_ForkServer]:
    if sys.platform != "linux" or os.environ.get("RAY_TPU_NO_FORK_SERVER"):
        return None
    with _fork_servers_lock:
        fs = _fork_servers.get(session_dir)
        if fs is not None and fs.proc.poll() is None:
            return fs
        if fs is not None:
            _fork_servers.pop(session_dir, None)
        sock_path = os.path.join(session_dir, "fork_server.sock")
        tmpl_env = dict(base_env)
        _apply_no_site_paths(tmpl_env)
        proc = None
        try:
            proc = subprocess.Popen(
                [sys.executable, "-S", "-m",
                 "ray_tpu._private.fork_server", sock_path],
                env=tmpl_env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, start_new_session=True)
            # bounded readiness wait: a stuck template import must not
            # hold _fork_servers_lock forever (that would freeze every
            # future spawn cluster-wide); on timeout, kill + cold-spawn
            import select

            ready, _, _ = select.select([proc.stdout], [], [], 60.0)
            line = proc.stdout.readline() if ready else b""
            if b"READY" not in line:
                raise RuntimeError(f"fork server not ready: {line!r}")
        except Exception:  # noqa: BLE001 — caller falls back to subprocess
            if proc is not None:
                try:
                    proc.kill()
                    proc.wait(timeout=3.0)
                except Exception:  # noqa: BLE001 — already gone
                    pass
            return None
        fs = _ForkServer(sock_path, proc)
        _fork_servers[session_dir] = fs
        return fs


def stop_fork_server(session_dir: str) -> None:
    with _fork_servers_lock:
        fs = _fork_servers.pop(session_dir, None)
    if fs is not None:
        fs.stop()


def spawn_worker_process(worker_id: str,
                         conductor_address: Tuple[str, int],
                         session_dir: str,
                         worker_env: Optional[Dict[str, str]] = None,
                         env_extra: Optional[Dict[str, str]] = None,
                         node_id: Optional[str] = None):
    """Start one ray_tpu worker wired to the conductor; returns a
    Popen-shaped handle (subprocess.Popen or ForkedProc)."""
    host, port = conductor_address
    env = dict(os.environ)
    env.update(worker_env or {})
    if env_extra:
        env.update(env_extra)
    env["RAY_TPU_WORKER_ID"] = worker_id
    env["RAY_TPU_CONDUCTOR"] = f"{host}:{port}"
    env["RAY_TPU_SESSION_DIR"] = session_dir
    if node_id:
        env["RAY_TPU_NODE_ID"] = node_id
    logs = os.path.join(session_dir, "logs")
    os.makedirs(logs, exist_ok=True)
    log_path = os.path.join(logs, f"worker-{worker_id[:12]}.log")

    if env.get("RAY_TPU_WORKER_FULL_SITE") != "1":
        fs = _get_fork_server(
            session_dir, dict(os.environ, **(worker_env or {})))
        if fs is not None:
            child_env = dict(env)
            _apply_no_site_paths(child_env)
            try:
                return fs.spawn(child_env, log_path)
            except Exception:  # noqa: BLE001 — template died: cold spawn
                stop_fork_server(session_dir)
        _apply_no_site_paths(env)
        cmd = [sys.executable, "-S", "-m", "ray_tpu._private.worker_main"]
    else:
        # full site; `-m` still has to find the package from any cwd
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_REPO_ROOT, env.get("PYTHONPATH")) if p)
        cmd = [sys.executable, "-m", "ray_tpu._private.worker_main"]

    out = open(log_path, "ab")
    return subprocess.Popen(
        cmd, env=env, stdout=out, stderr=subprocess.STDOUT,
        start_new_session=True)
