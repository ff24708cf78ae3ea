"""State API + metrics tests — modeled on the reference's
python/ray/tests/test_state_api*.py and test_metrics_agent.py."""
from __future__ import annotations

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics, state


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_list_nodes_and_workers(cluster):
    nodes = state.list_nodes()
    assert len(nodes) >= 1
    assert all(n["alive"] and "total" in n for n in nodes)


def test_list_tasks_and_summary(cluster):
    @ray_tpu.remote
    def tracked_task(x):
        time.sleep(0.01)
        return x

    ray_tpu.get([tracked_task.remote(i) for i in range(5)])
    tasks = state.list_tasks(name="tracked_task")
    assert len(tasks) >= 5
    assert all(t["end"] >= t["start"] for t in tasks)
    summary = state.summarize_tasks()
    assert summary["tracked_task"]["count"] >= 5
    assert summary["tracked_task"]["mean_s"] >= 0.005


def test_failed_task_status(cluster):
    @ray_tpu.remote
    def exploding():
        raise ValueError("nope")

    with pytest.raises(Exception):
        ray_tpu.get(exploding.remote())
    tasks = state.list_tasks(name="exploding")
    assert any(t.get("status") == "FAILED" for t in tasks)


def test_list_actors(cluster):
    @ray_tpu.remote
    class Tracked:
        def ping(self):
            return 1

    a = Tracked.options(name="state-test-actor").remote()
    ray_tpu.get(a.ping.remote())
    actors = state.list_actors()
    assert any(rec.get("name") == "state-test-actor" for rec in actors)


def test_list_objects(cluster):
    import numpy as np

    ref = ray_tpu.put(np.ones(200_000))
    stats = state.list_objects()
    assert any(s.get("is_driver") for s in stats)
    assert sum(s["num_objects"] for s in stats) >= 1
    del ref


def test_timeline_chrome_trace(cluster, tmp_path):
    @ray_tpu.remote
    def traced():
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    out = tmp_path / "trace.json"
    trace = state.timeline(str(out))
    assert len(trace) >= 3
    loaded = json.loads(out.read_text())
    ev = next(e for e in loaded if e["name"] == "traced")
    assert ev["ph"] == "X" and ev["dur"] >= 0 and "ts" in ev


def test_metrics_counter_gauge(cluster):
    c = metrics.Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(1, tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(5, tags={"route": "/b"})
    g = metrics.Gauge("test_queue_depth", "depth")
    g.set(7)
    metrics.flush()
    text = state.prometheus_metrics()
    assert 'test_requests_total{route="/a"' in text
    assert "# TYPE test_requests_total counter" in text
    assert "test_queue_depth" in text and " 7" in text


def test_metrics_histogram(cluster):
    h = metrics.Histogram("test_latency_s", "lat",
                          boundaries=[0.01, 0.1, 1.0])
    for v in [0.005, 0.05, 0.5, 5.0]:
        h.observe(v)
    metrics.flush()
    text = state.prometheus_metrics()
    assert 'test_latency_s_bucket' in text
    assert 'le="+Inf"} 4' in text
    assert "test_latency_s_count" in text


def test_metrics_in_worker(cluster):
    @ray_tpu.remote
    def emits_metrics():
        from ray_tpu.util import metrics as m

        c = m.Counter("test_worker_side_total", "from a task")
        c.inc(3)
        m.flush()
        return True

    assert ray_tpu.get(emits_metrics.remote())
    # the worker's flush() is a notify: the task's reply can reach the
    # driver before the conductor has handled the push
    deadline = time.monotonic() + 30.0
    while "test_worker_side_total" not in state.prometheus_metrics():
        assert time.monotonic() < deadline
        time.sleep(0.05)


def test_cluster_summary(cluster):
    s = state.cluster_summary()
    assert s["resources_total"].get("CPU", 0) >= 4
    assert s["num_workers"] >= 0 and len(s["nodes"]) >= 1


def test_invalid_metric_usage(cluster):
    with pytest.raises(ValueError):
        metrics.Counter("bad name!")
    c = metrics.Counter("test_valid_total", tag_keys=("k",))
    with pytest.raises(ValueError):
        c.inc(1, tags={"unknown": "x"})
    with pytest.raises(ValueError):
        c.inc(-1)


def test_rpc_handler_stats(cluster):
    """The conductor's RPC server accounts per-method queue/handler
    latency (reference instrumented_io_context.h stats)."""
    from ray_tpu.util import state

    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get([f.remote() for _ in range(5)]) == [1] * 5
    stats = state.rpc_stats()
    assert "lease_worker" in stats, sorted(stats)
    s = stats["lease_worker"]
    # lease reuse pipelines same-shape tasks onto cached leases, so 5
    # tasks need >= 1 lease RPC, not 5 (worker.py _lease_recache)
    assert s["count"] >= 1
    assert s["mean_handler_ms"] >= 0.0
    assert s["max_handler_ms"] >= s["mean_handler_ms"] - 1e-9
    assert s["max_queue_ms"] >= 0.0
