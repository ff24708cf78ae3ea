"""Smoke for the micro-benchmark suite (reference ray_perf.py) — every
bench runs end-to-end at tiny scale and emits well-formed records."""
from __future__ import annotations

import json
import os


def test_microbench_smoke(tmp_path):
    from ray_tpu._private import perf

    out = str(tmp_path / "micro.json")
    sink = perf.run(scale=0.02, out=out)
    names = {r["name"] for r in sink}
    assert {"task_roundtrip_sync", "tasks_async", "actor_call_sync",
            "actor_calls_async", "put_1kb", "put_100mb",
            "task_result_fetch_100mb", "queue_drain",
            "actor_churn"} <= names
    for r in sink:
        assert r["iters"] > 0
        ops = [v for k, v in r.items()
               if k.endswith(("_per_s", "gb_per_s"))]
        assert ops and all(v > 0 for v in ops), r
    assert os.path.exists(out)
    with open(out) as f:
        data = json.load(f)
    assert data["results"] == sink


def test_pipelined_tasks_not_inverted(tmp_path):
    """Regression guard for the round-4 anomaly: pipelined task
    throughput (tasks_async) ran 5x BELOW serial round-trips because
    every task paid lease+return RPCs and parked submit threads woke in
    herds. With worker-lease reuse (worker.py _lease_recache) pipelined
    throughput must stay at least comparable to serial — the historic
    failure mode was a 5x inversion, so the 0.6 floor catches it while
    tolerating 1-core CI jitter."""
    import time

    import ray_tpu

    ray_tpu.init(num_cpus=8)
    try:
        @ray_tpu.remote
        def f():
            return b"ok"

        ray_tpu.get([f.remote() for _ in range(50)])  # warm pool
        n = 200
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(f.remote())
        sync_rate = n / (time.perf_counter() - t0)

        t0 = time.perf_counter()
        ray_tpu.get([f.remote() for _ in range(n)], timeout=120.0)
        async_rate = n / (time.perf_counter() - t0)
    finally:
        ray_tpu.shutdown()
    assert async_rate > 0.6 * sync_rate, (
        f"pipelined inversion returned: async {async_rate:.0f}/s vs "
        f"sync {sync_rate:.0f}/s")


def test_actor_churn_floor():
    """Regression guard for 4-actors/s churn, which was a cold
    interpreter start per actor: every actor's worker must be a fork of
    the session's template (fork_server.py, ~10 ms a spawn), and waves
    of create+call+kill must complete and return the right values. No
    rate is asserted: a rate here is the host's, not the mechanism's."""
    import os

    import ray_tpu

    ray_tpu.init(num_cpus=8)
    try:
        @ray_tpu.remote
        class Cell:
            def __init__(self, v):
                self.v = v

            def get(self):
                return self.v, os.getppid()

        a = Cell.remote(0)
        ray_tpu.get(a.get.remote())
        ray_tpu.kill(a)  # warm (fork server boots on first spawn)

        n, wave, done, parents = 24, 8, 0, set()
        while done < n:
            k = min(wave, n - done)
            actors = [Cell.remote(i) for i in range(k)]
            got = ray_tpu.get([x.get.remote() for x in actors],
                              timeout=120.0)
            assert [v for v, _ in got] == list(range(k))
            parents.update(ppid for _, ppid in got)
            for x in actors:
                ray_tpu.kill(x)
            done += k

        if not os.environ.get("RAY_TPU_NO_FORK_SERVER"):
            assert len(parents) == 1, parents
            with open(f"/proc/{parents.pop()}/cmdline", "rb") as f:
                assert b"ray_tpu._private.fork_server" in f.read()
    finally:
        ray_tpu.shutdown()
