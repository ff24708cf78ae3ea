"""The telemetry channel: one store (``_private/telemetry.py``), one
table (``SUBSYSTEMS``), one pusher (``util/telemetry.py``). A subsystem
is a row, and every surface reads every row: what a ``Pusher`` sends is
read back through ``util.state``, the dashboard's handler, the
conductor's ``get_events`` and the merged timeline."""
from __future__ import annotations

import json
import time

import pytest

from ray_tpu._private.telemetry import (GAUGE_FRESH_S, ORACLE_PREDICTION,
                                        SUBSYSTEMS, TelemetryStore)
from ray_tpu.util.telemetry import Pusher, emit

# ------------------------------------------------------- the store alone


def test_store_cap_evicts_the_oldest_snapshot():
    store = TelemetryStore()
    kept = SUBSYSTEMS["gateway"].stats_kept
    for i in range(kept + 3):
        store.report_stats("gateway", "w", f"gw-{i}", {"accepted": 1})
    gateways = store.status("gateway")["gateways"]
    assert len(gateways) == kept
    assert "gw-0" not in gateways and "gw-2" not in gateways
    assert "gw-3" in gateways and f"gw-{kept + 2}" in gateways
    # a component that reports again is the newest, not a second entry
    store.report_stats("gateway", "w", "gw-3", {"accepted": 2})
    store.report_stats("gateway", "w", "gw-new", {"accepted": 1})
    gateways = store.status("gateway")["gateways"]
    assert len(gateways) == kept
    assert gateways["gw-3"]["accepted"] == 2 and "gw-4" not in gateways


def test_store_ring_keeps_the_last_events(monkeypatch):
    import dataclasses

    monkeypatch.setitem(SUBSYSTEMS, "lora", dataclasses.replace(
        SUBSYSTEMS["lora"], events_kept=5))
    store = TelemetryStore()
    for i in range(12):
        store.report_event("lora", {"kind": "page_in", "n": i})
    events = store.events("lora")
    assert [e["n"] for e in events] == [7, 8, 9, 10, 11]
    assert all(isinstance(e["ts"], float) for e in events)
    assert [e["n"] for e in store.events("lora", 2)] == [10, 11]
    # a ts the component stamped is kept
    store.report_event("lora", {"kind": "swap", "n": 12, "ts": 1.5})
    assert store.events("lora", 1) == [{"kind": "swap", "n": 12,
                                        "ts": 1.5}]


def test_store_drops_what_is_no_dict_and_what_has_no_row():
    store = TelemetryStore()
    store.report_stats("disagg", "w", "r0", ["not", "a", "dict"])
    store.report_stats("widget", "w", "r0", {"role": "router"})
    store.report_stats("speculation", "w", "e0", {"speculate_k": 2})
    store.report_event("disagg", "shed")
    store.report_event("widget", {"kind": "shed"})
    store.report_event("servefault", {"kind": "failover"})  # no ring
    for name in SUBSYSTEMS:
        assert store.events(name) == []
    assert store.status("disagg")["routers"] == {}
    assert store.status("kvcache")["engines"] == {}
    with pytest.raises(ValueError):
        store.status("widget")
    with pytest.raises(ValueError):
        store.events("widget")


def test_disagg_queue_depth_leaves_a_stale_router_out():
    store = TelemetryStore()
    store.report_stats("disagg", "w", "router-live",
                       {"role": "router", "pending": 3, "max_pending": 4,
                        "dispatched": 10})
    store.report_stats("disagg", "w", "router-dead",
                       {"role": "router", "pending": 5, "max_pending": 9,
                        "dispatched": 20})
    assert store.status("disagg")["totals"]["queue_depth"] == 8
    snaps = store.snapshots("disagg")
    snaps["router-dead"]["ts"] -= GAUGE_FRESH_S + 1.0
    totals = SUBSYSTEMS["disagg"].aggregate(snaps, time.time())["totals"]
    # the live gauge forgets the dead router; the counters do not
    assert totals["queue_depth"] == 3
    assert totals["dispatched"] == 30
    assert totals["max_queue_depth_seen"] == 9


# ----------------------------------------- every row, on every surface


@pytest.fixture(scope="module")
def telemetry_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=2, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def _holds(obj, want) -> bool:
    """Some dict inside `obj` carries every item of `want`."""
    if isinstance(obj, dict):
        if all(obj.get(k) == v for k, v in want.items()):
            return True
        obj = list(obj.values())
    return isinstance(obj, list) and any(_holds(o, want) for o in obj)


# What a row needs to show its snapshot: the role its aggregate groups
# by, or the component id its pusher uses. The rest is the same for all.
_ROLE = {"online": "sampler", "disagg": "router", "servefault": "healer",
         "lora": "pool"}
# a row that keeps no snapshots or no ring of its own is fed through the
# channel that does
_PUSH_THROUGH = {"speculation": "kvcache"}
_EVENT_KIND = {"speculation": "spec_accept", "servefault": "failover",
               "requesttrace": "trace", "oracle": "validation"}
_LANE = {"servefault": "resilience", "requesttrace": "requests"}


@pytest.mark.parametrize("name", sorted(SUBSYSTEMS))
def test_a_row_reads_back_on_every_surface(telemetry_cluster, name):
    from ray_tpu.dashboard import _ClusterData
    from ray_tpu.observability.timeline import merged_timeline
    from ray_tpu.util import state

    w = telemetry_cluster
    mark = f"probe-{name}"
    component = (ORACLE_PREDICTION + mark) if name == "oracle" else mark
    stats = {"probe": mark, "speculate_k": 2, "spec_proposed": 5}
    if name in _ROLE:
        stats["role"] = _ROLE[name]
    event = {"kind": _EVENT_KIND.get(name, "probe"), "request_id": mark,
             "ts": time.time()}

    through = _PUSH_THROUGH.get(name, name)
    assert Pusher(through, component).push(stats, (event,), force=True)
    if name == "servefault":
        # its markers are recovery events: they ride the resilience log
        w.conductor.notify("report_resilience_event", event)
    else:
        emit(through, dict(event, emitted=True))
    # notifies are ordered behind one another on the connection: a call
    # that answers has seen them land
    want = ({"speculate_k": 2, "spec_proposed": 5, "engine_id": mark}
            if name == "speculation" else stats)
    status = state.status(name)
    assert _holds(status, want), status
    events = w.conductor.call("get_events", name, limit=10_000)
    assert event in events
    if name != "servefault":
        assert dict(event, emitted=True) in events
    assert events == state.events(name)

    dash = _ClusterData(w.conductor_address).telemetry(name)
    assert _holds({k: v for k, v in dash.items() if k != "events"}, want)
    assert event in dash["events"]

    trace = merged_timeline()
    lane = _LANE.get(name, name)
    assert any(e.get("pid") == lane
               and mark in json.dumps(e.get("args", {}), default=str)
               for e in trace), [e for e in trace
                                 if e.get("pid") == lane][:5]
