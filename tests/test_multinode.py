"""Multi-host layer tests: NodeAgent (per-host daemon) + jax.distributed
rendezvous — the in-process analog of the reference's
python/ray/cluster_utils.py:135 (Cluster.add_node) multi-node tests.

The NodeAgent is the raylet-equivalent (src/ray/raylet/node_manager.h:125);
the rendezvous replaces the reference's NCCL/MASTER_ADDR bootstrap
(python/ray/train/torch/config.py:64-117) with
jax.distributed.initialize over the conductor KV."""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.node_agent import NodeAgent

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def small_head(monkeypatch):
    """A 1-CPU head: anything bigger must land on an agent node."""
    monkeypatch.setenv("RAY_TPU_NODE_TIMEOUT", "2.0")
    info = ray_tpu.init(num_cpus=1)
    yield info
    ray_tpu.shutdown()


def _head_address():
    return ray_tpu._private.worker.global_worker.conductor_address


def _conductor():
    return ray_tpu._private.worker.global_worker.conductor


def test_agent_registers_resources(small_head):
    agent = NodeAgent(_head_address(), {"CPU": 4.0, "widget": 2.0}).start()
    try:
        total = ray_tpu.cluster_resources()
        assert total["CPU"] == 5.0
        assert total["widget"] == 2.0
        nodes = _conductor().call("nodes", timeout=5.0)
        assert any(n["node_id"] == agent.node_id and n["alive"]
                   for n in nodes)
    finally:
        agent.stop()
    total = ray_tpu.cluster_resources()
    assert total["CPU"] == 1.0 and "widget" not in total


def test_task_placed_on_agent_node(small_head):
    """A task too big for the head must be spawned by the agent, on the
    agent's node, and report the agent's node id."""
    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        @ray_tpu.remote(num_cpus=2)
        def where():
            return os.environ.get("RAY_TPU_NODE_ID")

        assert ray_tpu.get(where.remote(), timeout=60.0) == agent.node_id
        # and the agent (not the head) owns that worker process
        assert agent.handler._procs, "agent spawned no worker"
    finally:
        agent.stop()


def test_actor_on_agent_node_death_detected(small_head):
    """Kill a remote-node actor's process: the agent's heartbeat reports
    the pid death and callers get ActorDiedError (the conductor cannot
    poll remote pids — node_heartbeat dead_worker_ids is the only path)."""
    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        @ray_tpu.remote(num_cpus=2, max_restarts=0)
        class Pinned:
            def pid(self):
                return os.getpid()

        a = Pinned.remote()
        pid = ray_tpu.get(a.pid.remote(), timeout=60.0)
        os.kill(pid, signal.SIGKILL)
        with pytest.raises(ray_tpu.exceptions.ActorDiedError):
            for _ in range(100):  # death arrives via next agent heartbeat
                ray_tpu.get(a.pid.remote(), timeout=30.0)
                time.sleep(0.1)
    finally:
        agent.stop()


def test_dead_agent_detected_by_heartbeat_expiry(small_head):
    """An agent that stops heartbeating (host crash) is marked dead and
    its resources leave the pool (gcs_health_check_manager.cc analog)."""
    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    assert ray_tpu.cluster_resources()["CPU"] == 5.0
    # simulate host crash: stop the heartbeat + RPC server, skip dereg
    agent._stopped.set()
    agent.server.stop()
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if ray_tpu.cluster_resources().get("CPU") == 1.0:
            break
        time.sleep(0.2)
    assert ray_tpu.cluster_resources().get("CPU") == 1.0, \
        "dead agent's resources never reclaimed"


_CHILD = r"""
import os, sys
import numpy as np

import jax
import ray_tpu
from ray_tpu.parallel.distributed import initialize_jax_distributed

rank = int(sys.argv[1])
ray_tpu.init(address=os.environ["RAY_TPU_TEST_HEAD"])
initialize_jax_distributed("test_gang", rank, 2)

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.devices()
mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
arr = jax.make_array_from_callback(
    (2,), NamedSharding(mesh, P("dp")),
    lambda idx: np.array([float(rank) + 1.0], dtype=np.float32))
total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
print("MULTIHOST_OK", float(total), flush=True)
"""


def test_two_process_jax_distributed(small_head):
    """Two driver processes rendezvous through the conductor KV into ONE
    jax.distributed job: each contributes its local CPU device to a
    global 2-device mesh and a jitted cross-process reduction agrees."""
    host, port = _head_address()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # children get 1 local device each
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_TEST_HEAD"] = f"{host}:{port}"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(rank)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for rank in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert "MULTIHOST_OK 3.0" in out, f"rank {rank} output:\n{out}"


def test_spread_scheduling_strategy(small_head):
    """scheduling_strategy='SPREAD' prefers the emptiest node (reference
    spread_scheduling_policy.cc); DEFAULT packs head-first."""
    import time as _time

    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        @ray_tpu.remote(num_cpus=1, scheduling_strategy="SPREAD")
        def where():
            _time.sleep(0.8)  # keep leases overlapping
            return os.environ.get("RAY_TPU_NODE_ID")

        _time.sleep(1.0)  # let the agent register
        nodes = set(ray_tpu.get([where.remote() for _ in range(4)],
                                timeout=60.0))
        assert len(nodes) == 2, f"SPREAD used only {nodes}"

        @ray_tpu.remote(num_cpus=1)
        def where_default():
            return os.environ.get("RAY_TPU_NODE_ID")

        # sequential DEFAULT tasks pack onto the head
        head_nodes = {ray_tpu.get(where_default.remote(), timeout=60.0)
                      for _ in range(3)}
        assert agent.node_id not in head_nodes
    finally:
        agent.stop()


def test_locality_aware_leasing(small_head):
    """A task whose (large, locator-only) arg lives on the agent node must
    lease there even though the head also has room (reference
    core_worker/lease_policy.cc LocalityAwareLeasePolicy)."""
    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        import numpy as np

        @ray_tpu.remote(num_cpus=2)  # head has 1 CPU: runs on the agent
        def big():
            return np.zeros(16 << 20, np.uint8)  # >8MB: stays with holder

        ref = big.remote()
        ray_tpu.wait([ref], timeout=60.0)

        @ray_tpu.remote(num_cpus=1)  # fits the head too
        def consume(a):
            return (os.environ.get("RAY_TPU_NODE_ID"), a.nbytes)

        node, nbytes = ray_tpu.get(consume.remote(ref), timeout=60.0)
        assert nbytes == 16 << 20
        assert node == agent.node_id, \
            f"consumer ran on {node}, arg lives on {agent.node_id}"
    finally:
        agent.stop()


def test_node_affinity_strategies(small_head):
    """NodeAffinity: hard pins (or fails for unknown nodes), soft degrades
    (reference node_affinity_scheduling_policy.cc)."""
    from ray_tpu.exceptions import SchedulingError
    from ray_tpu.util.scheduling_strategies import \
        NodeAffinitySchedulingStrategy

    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        @ray_tpu.remote
        def where():
            return os.environ.get("RAY_TPU_NODE_ID")

        # hard pin to the agent: must run there though the head has room
        pinned = where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                agent.node_id, soft=False))
        assert ray_tpu.get(pinned.remote(), timeout=60.0) == agent.node_id

        # hard pin to a dead node: typed failure, no infinite wait
        with pytest.raises(SchedulingError):
            ray_tpu.get(where.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    "no-such-node", soft=False)).remote(), timeout=30.0)

        # soft pin to a dead node: degrades to DEFAULT placement
        soft = where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                "no-such-node", soft=True))
        assert ray_tpu.get(soft.remote(), timeout=60.0) is not None

        # actors honor the strategy too
        @ray_tpu.remote(num_cpus=1)
        class Where:
            def node(self):
                return os.environ.get("RAY_TPU_NODE_ID")

        a = Where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                agent.node_id, soft=False)).remote()
        assert ray_tpu.get(a.node.remote(), timeout=60.0) == agent.node_id
        ray_tpu.kill(a)
    finally:
        agent.stop()


def test_serve_proxy_on_every_node(small_head):
    """Serve runs a proxy replica per cluster node, each serving the
    shared route table: a request through the NON-head node's proxy must
    succeed (reference serve/_private/proxy.py:1111 + proxy_state.py)."""
    import requests

    from ray_tpu import serve

    agent = NodeAgent(_head_address(), {"CPU": 4.0}).start()
    try:
        serve.start()

        @serve.deployment
        def hello(request):
            return {"from": os.environ.get("RAY_TPU_NODE_ID", "driver")}

        serve.run(hello.bind(), name="mn_app", route_prefix="/hello")

        proxies = {}
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            proxies = serve.status().get("proxies", {})
            if len(proxies) >= 2 and agent.node_id in proxies:
                break
            time.sleep(0.5)
        assert agent.node_id in proxies, \
            f"no proxy on agent node: {proxies}"

        host, port = proxies[agent.node_id]
        head_addr = serve.proxy_address()
        assert (host, port) != tuple(head_addr)
        r = requests.get(f"http://{host}:{port}/hello", timeout=30)
        assert r.status_code == 200 and "from" in r.json()
        # the same route serves through the head proxy too
        r2 = requests.get(
            f"http://{head_addr[0]}:{head_addr[1]}/hello", timeout=30)
        assert r2.status_code == 200
    finally:
        try:
            serve.shutdown()
        finally:
            agent.stop()
