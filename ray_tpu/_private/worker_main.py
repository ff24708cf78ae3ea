"""Worker process entrypoint — analog of the reference's
python/ray/_private/workers/default_worker.py (parse addresses, connect,
run the task loop :254,:289). Spawned by the conductor's worker pool."""
from __future__ import annotations

import os
import signal
import sys
import time

from ray_tpu.util import envknobs


def _bound_chips():
    """TPU chips this process was bound to at spawn (the conductor set
    TPU_VISIBLE_CHIPS); announced on every registration so a restarted
    conductor re-learns live bindings."""
    spec = os.environ.get("TPU_VISIBLE_CHIPS", "")
    try:
        chips = tuple(int(c) for c in spec.split(",") if c.strip() != "")
    except ValueError:
        return None
    return chips or None


def main() -> None:
    # Driver sys.path propagation: functions/classes pickled by reference
    # (module-level defs) must be importable here — the analog of the
    # reference's working_dir/py_modules runtime-env exposure.
    extra = os.environ.get("RAY_TPU_DRIVER_SYS_PATH", "")
    for p in reversed([p for p in extra.split(os.pathsep) if p]):
        if p not in sys.path:
            sys.path.insert(0, p)

    conductor = os.environ["RAY_TPU_CONDUCTOR"]
    worker_id = os.environ["RAY_TPU_WORKER_ID"]
    session_dir = os.environ.get("RAY_TPU_SESSION_DIR", "/tmp/ray_tpu")
    host, port = conductor.rsplit(":", 1)
    if os.environ.get("RAY_TPU_WORKER_VERBOSE") == "1":
        # boot diagnostics are opt-in: by default every worker's stdout
        # is mirrored to the driver (log_to_driver), and one boot line
        # per spawned process is pure noise interleaved into driver
        # output — failures surface through register_worker / the
        # conductor's death tracking, not this print
        print(f"[worker {worker_id[:8]}] connecting to conductor "
              f"{host}:{port}", flush=True)

    from . import worker as worker_mod
    from .worker import Worker

    chips = _bound_chips()
    if chips:
        # this process will compile for its chips: share the persistent
        # cache with every other chip owner, before the first program
        from ray_tpu.util.compile_cache import enable_compile_cache

        enable_compile_cache()
    w = Worker(mode="worker", conductor_address=(host, int(port)),
               session_dir=session_dir, worker_id=worker_id)
    worker_mod.global_worker = w
    # announce the chip binding so a restarted conductor (whose free_chips
    # reinitialized to the full range) re-learns which chips are taken
    w.conductor.call("register_worker", worker_id, w.address, os.getpid(),
                     os.environ.get("RAY_TPU_NODE_ID"), chips, timeout=30.0)

    def _term(signum, frame):
        os._exit(0)

    signal.signal(signal.SIGTERM, _term)

    # Park the main thread; all work arrives via the RPC server. Re-register
    # periodically — idempotent, and it re-announces this worker to a
    # restarted conductor (persistence story; the reconnecting client
    # re-dials underneath). Exit only after a sustained outage: the
    # cluster is then really gone.
    from .config import config

    grace = config.worker_orphan_grace
    last_ok = time.monotonic()
    while True:
        # chunked: in fork-server children the kernel often delivers
        # SIGTERM to a non-main thread, which only sets CPython's signal
        # flag — the main thread notices at its next bytecode, so a flat
        # 5s sleep made teardown take seconds (cold-spawned processes
        # get the signal on the main thread and EINTR out immediately)
        for _ in range(50):
            time.sleep(0.1)
        try:
            ok = w.conductor.call(
                "register_worker", worker_id, w.address, os.getpid(),
                envknobs.get_str("RAY_TPU_NODE_ID"), chips, timeout=5.0)
            if ok is False:
                # conductor rebound our chips to another worker while we
                # were partitioned — we must not touch the TPU again
                os._exit(0)
            last_ok = time.monotonic()
        except Exception:
            if time.monotonic() - last_ok > grace:
                os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
