"""Bounded stress tests — the miniature analog of the reference's
release/stress_tests (many_tasks, many_actors, chained deps): volume
and churn shapes that historically exposed livelocks, leaks, and
ordering bugs in this runtime."""
from __future__ import annotations

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_many_small_tasks(cluster):
    @ray_tpu.remote
    def sq(i):
        return i * i

    t0 = time.monotonic()
    refs = [sq.remote(i) for i in range(500)]
    got = ray_tpu.get(refs, timeout=120.0)
    dt = time.monotonic() - t0
    assert got == [i * i for i in range(500)]
    assert dt < 60.0, f"500 tasks took {dt:.1f}s"


def test_many_actors_churn(cluster):
    @ray_tpu.remote
    class Cell:
        def __init__(self, v):
            self.v = v

        def get(self):
            return self.v

    for _round in range(3):
        cells = [Cell.remote(i) for i in range(20)]
        vals = ray_tpu.get([c.get.remote() for c in cells], timeout=60.0)
        assert vals == list(range(20))
        for c in cells:
            ray_tpu.kill(c)


def test_deep_nested_task_tree(cluster):
    """Recursive fan-out: every level submits children and get()s them —
    exercises the blocked-lease release under real nesting."""
    @ray_tpu.remote
    def tree(depth, width):
        if depth == 0:
            return 1
        return sum(ray_tpu.get(
            [tree.remote(depth - 1, width) for _ in range(width)]))

    assert ray_tpu.get(tree.remote(3, 3), timeout=120.0) == 27


def test_object_churn_stays_flat(cluster):
    """Sustained put/get churn must not grow the store (distributed
    refcounting done-criterion, round-2 item 3)."""
    from ray_tpu._private.worker import global_worker

    payload = np.zeros(200_000, np.uint8)  # 200KB -> shm path
    for i in range(50):
        ref = ray_tpu.put(payload)
        out = ray_tpu.get(ref)
        assert out.nbytes == payload.nbytes
        del ref, out
    import gc

    gc.collect()
    time.sleep(1.0)
    stats = global_worker.store.stats()
    assert stats["bytes"] < 5 * payload.nbytes, stats


def test_mixed_workload_smoke(cluster):
    """Tasks + actors + large objects + cancellation interleaved."""
    @ray_tpu.remote
    def make_block(i):
        return np.full(100_000, i, np.uint8)

    @ray_tpu.remote
    class Accum:
        def __init__(self):
            self.total = 0

        def add(self, arr):
            self.total += int(arr[0])
            return self.total

    @ray_tpu.remote
    def sleeper():
        time.sleep(30)

    acc = Accum.remote()
    doomed = sleeper.remote()
    blocks = [make_block.remote(i) for i in range(10)]
    adds = [acc.add.remote(b) for b in blocks]
    ray_tpu.cancel(doomed)
    assert ray_tpu.get(adds[-1], timeout=60.0) == sum(range(10))
    with pytest.raises(Exception):
        ray_tpu.get(doomed, timeout=10.0)


def test_two_thousand_task_queue_drain(cluster):
    """Mid-scale envelope check in-suite (the full 10k-task drain runs in
    the committed microbench): 2k no-op tasks submit and drain through
    the conductor lease path without stalls."""
    @ray_tpu.remote
    def nop(i):
        return i

    t0 = time.monotonic()
    refs = [nop.remote(i) for i in range(2000)]
    got = ray_tpu.get(refs, timeout=300.0)
    dt = time.monotonic() - t0
    assert got == list(range(2000))
    # envelope: microbench measures ~1.3-1.6k tasks/s on this host;
    # alert only on order-of-magnitude regressions
    assert dt < 60.0, f"2k tasks took {dt:.1f}s"
