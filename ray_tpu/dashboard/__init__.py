"""Web dashboard: cluster state over HTTP + a single-file SPA.

Replaces the reference's `dashboard/` subsystem (aiohttp head + per-node
agents + React SPA, dashboard/dashboard.py, dashboard/client/) with one
aiohttp server beside the conductor. There are no per-node dashboard
agents to aggregate: the conductor is already the single authority for
nodes/workers/actors/jobs, and per-worker object stats are one RPC away.

Routes:
  /                      the SPA (ray_tpu/dashboard/index.html)
  /api/summary           cluster overview (nodes + resources + counts)
  /api/nodes|workers|actors|placement_groups|jobs
  /api/objects           per-process object store stats (fan-out)
  /api/tasks             task-name summary table
  /api/timeline          chrome-trace JSON of task events
  /api/metrics           Prometheus exposition (text)
  /api/serve             Serve apps/deployments/proxies (controller's
                         KV-mirrored status)
  /api/resilience        recovery subsystem: quarantined/draining hosts,
                         failure scores, restart/preemption counters
  /api/weights           live weight fabric: committed/pending versions
                         per weight-set name (ray_tpu.weights registry)
  /api/kvcache           paged KV prefix cache: per-engine stats +
                         totals (hit rates, pool utilization) and
                         recent prefix-hit/evict events
  /api/speculation       speculative decoding: per-engine draft
                         proposal/acceptance counters, tokens-per-
                         verify, int8-KV flag, and the kvcache lane's
                         spec_accept/spec_reject marker slice
                         (models/engine.py)
  /api/pipeline          MPMD pipelines: stage registry + per-stage
                         bubble fraction / channel bytes and recent
                         pipeline events (ray_tpu.mpmd)
  /api/online            online learning loop: sampler rollout +
                         staleness stats, buffer occupancy, learner
                         ingest, recent rollout/publish/swap/ingest
                         events (ray_tpu.online)
  /api/disagg            disaggregated prefill/decode serving: prefill
                         reuse + published KV, decode transfer
                         accounting (shm vs rpc), router shed/queue
                         depth, recent kv_publish/kv_transfer/shed
                         events (serve/disagg.py)
  /api/kvplane           global KV plane: per-replica host arenas
                         (tier-2 entries/bytes, spills, re-adopted
                         tokens), tier-3 publish/adopt traffic, prefix
                         directory summary + routing outcomes, recent
                         spill/tier2_hit/tier3_publish/tier3_adopt/
                         directory_hit events (serve/kvplane.py)
  /api/autoscale         serving autoscaler: per-loop tier targets,
                         scale-up/down decision counts, drain
                         outcomes, replica-seconds, recent scale_up/
                         drain/scale_down events (serve/autoscale.py;
                         the NODE-level autoscaler stays at
                         /api/autoscaler)
  /api/servefault        serving-plane fault tolerance: per-router
                         failovers by phase + sheds by cause, healer
                         deaths/replacements/breaker state, and the
                         resilience lane's failover/replace/
                         breaker_trip event slice (serve/disagg.py +
                         serve/autoscale.py self-healing)
  /api/lora              multi-tenant LoRA serving: adapter-pool
                         paging (hits/misses/evictions/swaps,
                         residents), per-tenant request counters,
                         recent page_in/evict/swap events
                         (serve/lora.py)
  /api/gateway           HTTP front door: per-replica request counters
                         by priority class and status code, recent
                         TTFT per class, QoS admissions, batch-slot
                         preemptions, recent accept/first_byte/
                         preempt/rate_limit/disconnect events
                         (serve/gateway.py + serve/qos.py)
  /api/oracle            step-time oracle: roofline predictions per
                         layout (device/ici/dcn breakdown),
                         predicted-vs-measured validations (residuals,
                         fitted calibration), recent prediction/
                         validation events (observability.roofline)
  /api/requesttrace      per-request flight recorder: completed/kept/
                         dropped totals, outcome tally, p99 phase
                         attribution (tail owner), slowest requests
                         with per-phase latency breakdowns, recent
                         kept-trace events (observability.requests)
  /api/actors/{id}       actor drill-down (record, worker, recent task
                         events, store stats)
"""
from __future__ import annotations

import asyncio
import functools
import os
import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.rpc import ClientPool, ReconnectingClient
from ray_tpu._private.telemetry import SUBSYSTEMS

DEFAULT_DASHBOARD_PORT = 8265


class _ClusterData:
    """Blocking conductor/worker queries (called via run_in_executor)."""

    def __init__(self, conductor_address: Tuple[str, int]):
        self.conductor = ReconnectingClient(conductor_address)
        self.pool = ClientPool()

    def summary(self) -> Dict[str, Any]:
        c = self.conductor
        return {
            "timestamp": time.time(),
            "address": list(self.conductor.address),
            "nodes": c.call("nodes", timeout=5.0),
            "resources_total": c.call("cluster_resources", timeout=5.0),
            "resources_available": c.call("available_resources", timeout=5.0),
            "num_workers": len(c.call("list_workers", timeout=5.0)),
            "num_actors": len(c.call("list_actors", timeout=5.0)),
        }

    def simple(self, method: str) -> Any:
        return self.conductor.call(method, timeout=10.0)

    def simple_args(self, method: str, *args) -> Any:
        return self.conductor.call(method, *args, timeout=10.0)

    def objects(self) -> List[Dict[str, Any]]:
        out = []
        for rec in self.conductor.call("list_workers", timeout=5.0):
            addr = rec.get("address")
            if not addr or rec.get("state") == "DEAD":
                continue
            try:
                out.append(self.pool.get(tuple(addr)).call("store_stats",
                                                           timeout=3.0))
            except Exception:  # noqa: BLE001 — worker mid-restart
                pass
        return out

    def tasks_summary(self) -> List[Dict[str, Any]]:
        events = self.conductor.call("get_task_events", 10_000, timeout=10.0)
        groups: Dict[str, Dict[str, Any]] = defaultdict(
            lambda: {"count": 0, "failed": 0, "total_s": 0.0})
        for ev in events:
            g = groups[ev["name"]]
            g["count"] += 1
            g["failed"] += 1 if ev.get("status") == "FAILED" else 0
            g["total_s"] += max(0.0, ev["end"] - ev["start"])
        return [dict(name=k, mean_s=v["total_s"] / max(1, v["count"]), **v)
                for k, v in sorted(groups.items())]

    def timeline(self) -> List[Dict[str, Any]]:
        from ray_tpu.observability.timeline import task_trace_events

        events = self.conductor.call("get_task_events", 10_000, timeout=10.0)
        return task_trace_events(events)

    def metrics_text(self) -> str:
        from ray_tpu.util.state import _render_prometheus

        return _render_prometheus(self.conductor.call("get_metrics",
                                                      timeout=5.0))

    def train_progress(self) -> Dict[str, Any]:
        """Flight-recorder gang telemetry (per-rank step stats, skew,
        stragglers) aggregated by the conductor. Int rank keys are fine:
        json_response's json.dumps coerces them to strings."""
        return self.conductor.call("get_train_progress", timeout=10.0)

    def serve_status(self) -> Dict[str, Any]:
        """Serve apps/deployments/proxies, mirrored into the conductor
        KV by the Serve controller's reconcile loop."""
        status = self.conductor.call("kv_get", "serve:status", "serve",
                                     timeout=5.0)
        return status or {"applications": {}, "proxies": {}}

    def autoscaler_status(self) -> Dict[str, Any]:
        """Autoscaler reconcile state (KV mirror) + live pending demand
        from the conductor — the `ray status` analog."""
        import json as _json

        raw = self.conductor.call("kv_get", b"autoscaler:status",
                                  "autoscaler", timeout=5.0)
        status = _json.loads(raw.decode()) if raw else {}
        try:
            status["live_demand"] = self.conductor.call(
                "get_pending_demand", timeout=5.0)
        except Exception:  # noqa: BLE001 — older conductor
            status["live_demand"] = []
        return status

    def telemetry(self, subsystem: str) -> Dict[str, Any]:
        """One telemetry subsystem's aggregate + its recent event tail
        (one payload so the SPA's panel needs a single fetch)."""
        out = self.conductor.call("get_status", subsystem, timeout=10.0)
        try:
            out["events"] = self.conductor.call("get_events", subsystem,
                                                100, timeout=5.0)
        except Exception:  # noqa: BLE001 — the aggregate still shows
            out["events"] = []
        return out

    def __getattr__(self, name: str):
        # `data.lora()` is the payload /api/lora serves, for every row
        if name in SUBSYSTEMS:
            return functools.partial(self.telemetry, name)
        raise AttributeError(name)

    def pipeline(self) -> Dict[str, Any]:
        """MPMD pipeline registry + the recent event tail (one payload
        so the SPA's panel needs a single fetch)."""
        out = self.conductor.call("get_pipeline_status", timeout=10.0)
        try:
            out["events"] = self.conductor.call("get_pipeline_events",
                                                100, timeout=5.0)
        except Exception:  # noqa: BLE001 — older conductor
            out["events"] = []
        return out

    def actor_detail(self, actor_id: str) -> Dict[str, Any]:
        """One actor's record + its worker + its recent task events —
        the actors-table drill-down."""
        actors = self.conductor.call("list_actors", timeout=5.0)
        rec = next((a for a in actors if a.get("actor_id") == actor_id),
                   None)
        if rec is None:
            return {"error": f"no actor {actor_id!r}"}
        addr = tuple(rec["address"]) if rec.get("address") else None
        if addr is None:  # PENDING/DEAD actor: nothing to join against
            return {"actor": rec, "worker": None, "recent_tasks": [],
                    "store": None}
        workers = self.conductor.call("list_workers", timeout=5.0)
        worker = next((w for w in workers if addr and w.get("address")
                       and tuple(w["address"]) == addr), None)
        events = self.conductor.call("get_task_events", 10_000,
                                     timeout=10.0)
        mine = [ev for ev in events
                if addr and ev.get("worker")
                and tuple(ev["worker"]) == addr][-100:]
        store = None
        if addr and worker is not None and worker.get("state") != "DEAD":
            try:
                store = self.pool.get(addr).call("store_stats",
                                                 timeout=3.0)
            except Exception:  # noqa: BLE001 — worker mid-restart
                pass
        return {"actor": rec, "worker": worker, "recent_tasks": mine,
                "store": store}


class DashboardServer:
    """aiohttp app on its own thread+loop — works beside a blocking
    conductor (in-process head) or standalone via `ray_tpu dashboard`."""

    def __init__(self, conductor_address: Tuple[str, int],
                 host: str = "127.0.0.1",
                 port: int = DEFAULT_DASHBOARD_PORT):
        self.data = _ClusterData(tuple(conductor_address))
        self.host, self.port = host, port
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="dashboard", daemon=True)

    # ------------------------------------------------------------ handlers

    async def _call(self, fn, *args):
        return await asyncio.get_running_loop().run_in_executor(
            None, fn, *args)

    async def _index(self, request):
        from aiohttp import web

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "index.html")
        return web.FileResponse(path)

    def _json_route(self, fn):
        from aiohttp import web

        async def handler(request):
            try:
                return web.json_response(await self._call(fn))
            except Exception as e:  # noqa: BLE001 — surface, don't 500-html
                return web.json_response({"error": str(e)}, status=503)
        return handler

    async def _metrics(self, request):
        from aiohttp import web

        text = await self._call(self.data.metrics_text)
        return web.Response(text=text,
                            content_type="text/plain", charset="utf-8")

    # ------------------------------------------------------------ lifecycle

    def _make_app(self):
        from aiohttp import web

        d = self.data
        app = web.Application()
        app.router.add_get("/", self._index)
        app.router.add_get("/api/summary", self._json_route(d.summary))
        for name, method in [("nodes", "nodes"),
                             ("workers", "list_workers"),
                             ("actors", "list_actors"),
                             ("placement_groups", "list_placement_groups"),
                             ("jobs", "list_jobs")]:
            app.router.add_get(
                f"/api/{name}",
                self._json_route(lambda m=method: d.simple(m)))
        app.router.add_get("/api/objects", self._json_route(d.objects))
        app.router.add_get("/api/tasks", self._json_route(d.tasks_summary))
        app.router.add_get("/api/timeline", self._json_route(d.timeline))
        app.router.add_get("/api/logs",
                           self._json_route(
                               lambda: d.simple_args("get_recent_logs", 500)))
        app.router.add_get("/api/metrics", self._metrics)
        app.router.add_get("/api/serve", self._json_route(d.serve_status))
        app.router.add_get("/api/train", self._json_route(d.train_progress))
        app.router.add_get("/api/autoscaler",
                           self._json_route(d.autoscaler_status))
        app.router.add_get(
            "/api/resilience",
            self._json_route(lambda: d.simple("get_resilience_status")))
        app.router.add_get(
            "/api/weights",
            self._json_route(lambda: d.simple("get_weight_versions")))
        app.router.add_get("/api/pipeline", self._json_route(d.pipeline))
        for name in SUBSYSTEMS:
            app.router.add_get(
                f"/api/{name}",
                self._json_route(lambda s=name: d.telemetry(s)))
        app.router.add_get(
            "/api/rpc",
            self._json_route(lambda: d.simple("get_rpc_stats")))

        async def actor_detail(request):
            from aiohttp import web

            try:
                return web.json_response(await self._call(
                    d.actor_detail, request.match_info["actor_id"]))
            except Exception as e:  # noqa: BLE001
                return web.json_response({"error": str(e)}, status=503)

        app.router.add_get("/api/actors/{actor_id}", actor_detail)
        return app

    def _run(self) -> None:
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        runner = web.AppRunner(self._make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, self.host, self.port)
        loop.run_until_complete(site.start())
        # port 0 -> discover the bound port
        for s in site._server.sockets:  # noqa: SLF001 — aiohttp API gap
            self.port = s.getsockname()[1]
            break
        self._started.set()
        try:
            loop.run_forever()
        finally:
            loop.run_until_complete(runner.cleanup())
            loop.close()

    def start(self, timeout: float = 10.0) -> "DashboardServer":
        self._thread.start()
        if not self._started.wait(timeout):
            raise RuntimeError("dashboard failed to start")
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5.0)


def main(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="ray_tpu web dashboard")
    ap.add_argument("--address", required=True, help="conductor host:port")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=DEFAULT_DASHBOARD_PORT)
    args = ap.parse_args(argv)
    host, port = args.address.rsplit(":", 1)
    srv = DashboardServer((host, int(port)), host=args.host,
                          port=args.port).start()
    print(f"dashboard at {srv.url}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
