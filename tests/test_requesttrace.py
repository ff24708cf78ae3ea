"""Per-request flight recorder (observability/requests.py): a request
id minted at the gateway (or router for direct calls) carries
phase-stamped spans through QoS admission, router queue/reserve,
prefill, KV transfer, decode ticks, and SSE flush, so a completed
request ships its full latency breakdown. The invariants:

- the non-concurrent phases sum to ~the request's wall time (loose
  bounds — tier-1 runs share the machine);
- tail-based retention keeps EVERY anomalous outcome
  (shed/error/deadline/disconnect/preempt/replayed) and the slowest N,
  and probabilistically samples the rest under the
  ``RAY_TPU_REQTRACE_*`` budget;
- failover and preemption replays nest as attempt-tagged child spans
  under ONE request id;
- a scripted ``delay_chunk_fetch`` chaos stretch surfaces as
  ``kv_transfer`` dominating the slowed request's breakdown AND as the
  p99-attribution report's named tail owner;
- every surface reports one set of numbers: state API == CLI ==
  dashboard == Prometheus families == `requests` timeline lane.

The ``requesttrace`` marker tags the scenarios; everything is
tier-1-safe on CPU — cluster tests run on a module-scoped cluster with
log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.observability import requests as reqtrace
from ray_tpu.serve.disagg import DecodeServer, DisaggRouter, PrefillServer
from ray_tpu.serve.gateway import GatewayServer
from ray_tpu.serve.handle import RequestShedError
from ray_tpu.serve.qos import QosGate

pytestmark = pytest.mark.requesttrace

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def reqtrace_cluster():
    ray_tpu.init(num_cpus=4, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def fresh_store():
    """Each test starts from an empty process-local store (the global
    is rebuilt lazily) and a clean env-knob memo."""
    from ray_tpu.util import envknobs

    reqtrace._reset_store_for_tests()
    envknobs.clear()
    yield
    reqtrace._reset_store_for_tests()
    envknobs.clear()


def _mk_record(rid, total_ms, outcome="ok", replayed=False,
               preempts=0, phase_ms=None):
    """A finished-trace record shaped like RequestTrace.finish()."""
    return {"kind": "trace", "request_id": rid,
            "trace_id": "0" * 32, "source": "test",
            "ts": time.time(), "total_ms": float(total_ms),
            "outcome": outcome, "attempts": 2 if replayed else 1,
            "replayed": replayed, "preempts": preempts,
            "phases": [], "phase_ms": dict(phase_ms or {})}


# -------------------------------------------------------- trace object


def test_phase_sum_approximates_wall_time():
    tr = reqtrace.RequestTrace("r-sum")
    with tr.phase("prefill"):
        time.sleep(0.03)
    with tr.phase("kv_transfer"):
        time.sleep(0.02)
    with tr.phase("decode_steady"):
        time.sleep(0.01)
    tr.add_phase("sse_flush", 500.0)  # concurrent: excluded from sum
    rec = tr.finish("ok")
    seq_ms = sum(p["dur_ms"] for p in rec["phases"]
                 if not p.get("concurrent"))
    assert rec["phase_ms"]["prefill"] >= 25.0
    assert rec["phase_ms"]["kv_transfer"] >= 15.0
    # the non-concurrent phases happened inside the request window
    assert seq_ms <= rec["total_ms"] + 5.0, rec
    # sse_flush overlaps the decode stream; it must NOT break the
    # invariant even though it dwarfs the wall time here
    assert rec["phase_ms"]["sse_flush"] == 500.0
    conc = [p for p in rec["phases"] if p["phase"] == "sse_flush"]
    assert conc and conc[0]["concurrent"] is True


def test_annotate_accumulates_on_open_phase():
    tr = reqtrace.RequestTrace("r-ann")
    with tr.phase("kv_transfer"):
        tr.annotate(pull_ms=10.0, pulls=1)
        tr.annotate(pull_ms=5.5, pulls=1, server="d0")
    rec = tr.finish("ok")
    ph = next(p for p in rec["phases"] if p["phase"] == "kv_transfer")
    assert ph["pull_ms"] == 15.5
    assert ph["pulls"] == 2
    assert ph["server"] == "d0"


def test_finish_is_idempotent_first_wins():
    tr = reqtrace.RequestTrace("r-idem")
    first = tr.finish("disconnect", cause="client_gone")
    second = tr.finish("ok")
    assert second is first
    assert first["outcome"] == "disconnect"


def test_replays_and_preempts_nest_under_one_id():
    store = reqtrace.RequestTraceStore()
    tr = reqtrace.RequestTrace("r-replay", store=store)
    with pytest.raises(ConnectionError):
        with tr.phase("prefill"):
            raise ConnectionError("replica died")
    tr.begin_attempt()                      # failover replay
    with tr.phase("prefill"):
        pass
    with tr.phase("kv_transfer"):
        pass
    tr.mark_preempt()                       # preempted mid-decode
    with tr.phase("decode_steady"):
        pass
    rec = tr.finish("ok")
    assert rec["attempts"] == 3
    assert rec["replayed"] is True
    assert rec["preempts"] == 1
    by_attempt = [p["attempt"] for p in rec["phases"]]
    assert by_attempt == [1, 2, 2, 3]
    assert rec["phases"][0]["error"] == "ConnectionError"
    # replayed == anomalous: retained regardless of speed or sampling
    assert store.trace("r-replay") is not None


# ---------------------------------------------------- tail retention


def test_tail_retention_keeps_anomalies_and_slowest(monkeypatch):
    from ray_tpu.util import envknobs

    monkeypatch.setenv("RAY_TPU_REQTRACE_SAMPLE", "0.0")
    monkeypatch.setenv("RAY_TPU_REQTRACE_SLOWEST", "2")
    monkeypatch.setenv("RAY_TPU_REQTRACE_KEPT", "32")
    envknobs.clear()
    store = reqtrace.RequestTraceStore()
    # two slow requests claim the slowest-N slots
    store.record(_mk_record("slow-1", 900.0))
    store.record(_mk_record("slow-2", 800.0))
    # every anomalous outcome is kept at admission, however fast
    for i, outcome in enumerate(sorted(reqtrace.ANOMALOUS_OUTCOMES)):
        store.record(_mk_record(f"anom-{outcome}", 1.0 + i,
                                outcome=outcome))
    store.record(_mk_record("anom-replayed", 2.0, replayed=True))
    store.record(_mk_record("anom-preempted", 2.0, preempts=1))
    # plain fast ok traffic is sampled at 0.0 -> dropped
    for i in range(20):
        store.record(_mk_record(f"fast-{i}", 10.0 + i))
    assert store.trace("slow-1") is not None
    assert store.trace("slow-2") is not None
    for outcome in reqtrace.ANOMALOUS_OUTCOMES:
        assert store.trace(f"anom-{outcome}") is not None, outcome
    assert store.trace("anom-replayed") is not None
    assert store.trace("anom-preempted") is not None
    assert all(store.trace(f"fast-{i}") is None for i in range(20))
    st = store.stats()
    assert st["dropped"] == 20
    assert st["completed"] == 2 + len(reqtrace.ANOMALOUS_OUTCOMES) \
        + 2 + 20
    assert st["replayed_requests"] == 1
    assert st["preempted_requests"] == 1
    # the slowest list leads with the champions
    tops = [r["request_id"] for r in st["slowest"][:2]]
    assert tops == ["slow-1", "slow-2"]


def test_retention_cap_evicts_fifo_but_protects_slowest(monkeypatch):
    from ray_tpu.util import envknobs

    monkeypatch.setenv("RAY_TPU_REQTRACE_SAMPLE", "0.0")
    monkeypatch.setenv("RAY_TPU_REQTRACE_SLOWEST", "2")
    monkeypatch.setenv("RAY_TPU_REQTRACE_KEPT", "4")
    envknobs.clear()
    store = reqtrace.RequestTraceStore()
    store.record(_mk_record("champ-1", 5000.0))
    store.record(_mk_record("champ-2", 4000.0))
    # a storm of anomalies overflows the cap; the champions survive
    for i in range(10):
        store.record(_mk_record(f"shed-{i}", 1.0, outcome="shed"))
    assert store.trace("champ-1") is not None
    assert store.trace("champ-2") is not None
    st = store.stats()
    assert st["kept"] <= 4


def test_p99_attribution_names_the_tail_owner():
    mk = _mk_record
    rows = [mk(f"fast-{i}", 100.0,
               phase_ms={"prefill": 40.0, "decode_steady": 55.0})
            for i in range(50)]
    rows.append(mk("slow", 900.0,
                   phase_ms={"prefill": 45.0, "kv_transfer": 790.0,
                             "decode_steady": 60.0}))
    rep = reqtrace.p99_attribution(rows)
    assert rep["n"] == 51
    assert rep["tail_owner"] == "kv_transfer"
    assert rep["tail_share"] >= 0.9
    assert rep["phases"]["kv_transfer"]["delta_ms"] > 700.0
    # empty population degrades, not raises
    assert reqtrace.p99_attribution([])["tail_owner"] is None


# ------------------------------------------------- router serving path


def test_router_owned_trace_covers_the_serving_path(model):
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=2, affinity_tokens=BS)
    try:
        toks = router.generate([1, 2, 3, 4, 5], 6)
        assert len(toks) == 6
    finally:
        dec.stop()
    store = reqtrace.store()
    rows = store.summaries_since(0)
    assert len(rows) == 1
    phase_ms = rows[0]["phase_ms"]
    for ph in ("queue_reserve", "prefill", "kv_transfer",
               "decode_first_token"):
        assert ph in phase_ms, phase_ms
    assert rows[0]["outcome"] == "ok"
    # loose phase-sum bound (shared tier-1 machine): the recorded
    # phases live inside the wall clock and cover the dominant work
    kept = store.slowest(1)[0]
    seq_ms = sum(p["dur_ms"] for p in kept["phases"]
                 if not p.get("concurrent"))
    assert seq_ms <= kept["total_ms"] + 5.0
    assert seq_ms >= 0.35 * kept["total_ms"]


def test_router_deadline_shed_is_kept_with_cause(model):
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=2, affinity_tokens=BS)
    try:
        with pytest.raises(RequestShedError):
            router.generate([1, 2, 3, 4], 6, deadline_s=0.0)
    finally:
        dec.stop()
    store = reqtrace.store()
    rows = store.summaries_since(0)
    assert len(rows) == 1
    assert rows[0]["outcome"] == "deadline"
    kept = store.trace(rows[0]["request_id"])
    assert kept is not None                  # anomalous -> retained
    assert kept["cause"] == "deadline"


class _FlakyDecode:
    """Proxies a DecodeServer; dies after serving N tokens (the
    in-process stand-in for an actor death mid-stream)."""

    def __init__(self, inner, die_after=10**9):
        self._inner = inner
        self._served = 0
        self._die = die_after
        self.dead = False

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def start_decode(self, *a, **k):
        if self.dead:
            raise ConnectionError("replica is dead")
        return self._inner.start_decode(*a, **k)

    def next_tokens(self, hid, max_tokens=64, wait_s=2.0):
        if self.dead:
            raise ConnectionError("replica is dead")
        out = self._inner.next_tokens(hid, 1, wait_s)
        self._served += len(out["tokens"])
        if self._served >= self._die and not out["done"]:
            self.dead = True
            raise ConnectionError("replica died mid-stream")
        return out


def test_failover_replay_is_a_child_span_under_one_id(model):
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    d1 = DecodeServer(model, CFG, max_batch=4)
    d2 = DecodeServer(model, CFG, max_batch=4)
    # free-slot tie-break favors the LAST replica: the flaky one
    router = DisaggRouter(decode=[_FlakyDecode(d2),
                                  _FlakyDecode(d1, die_after=3)],
                          prefill=[pf], max_queue_depth=4,
                          affinity_tokens=BS)
    try:
        toks = router.generate([1, 2, 3, 4, 5, 6, 7, 8], 8)
        assert len(toks) == 8
    finally:
        d1.stop()
        d2.stop()
    store = reqtrace.store()
    rows = store.summaries_since(0)
    assert len(rows) == 1
    kept = store.trace(rows[0]["request_id"])
    assert kept is not None                  # replayed -> retained
    assert kept["outcome"] == "ok"
    assert kept["replayed"] is True
    assert kept["attempts"] >= 2
    attempts = {p["attempt"] for p in kept["phases"]}
    assert 1 in attempts and 2 in attempts
    # the replay re-prefilled under attempt 2 — a child span of the
    # SAME request id, not a second request
    a2 = [p["phase"] for p in kept["phases"] if p["attempt"] == 2]
    assert "prefill" in a2
    st = store.stats()
    assert st["replayed_requests"] == 1


# ---------------------------------------------------- gateway headers


@pytest.fixture(scope="module")
def gw_stack(model):
    engine = ContinuousBatchingEngine(model, CFG, max_batch=2)
    router = DisaggRouter(colocated=engine, max_queue_depth=8)
    gw = GatewayServer(router, model="tiny", vocab_size=CFG.vocab_size,
                       qos=QosGate(router=router), max_tokens_cap=64)
    host, port = gw.ready()
    yield {"host": host, "port": port, "engine": engine, "gw": gw}
    gw.stop()
    engine.stop()


def _post(host, port, path, body=None, headers=None, raw=None,
          timeout=60.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    payload = raw if raw is not None else json.dumps(body)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", path, payload, hdrs)
    return conn, conn.getresponse()


def test_gateway_honors_traceparent_and_stamps_request_id(gw_stack):
    incoming_trace = "ab" * 16
    tp = f"00-{incoming_trace}-{'12' * 8}-01"
    conn, resp = _post(gw_stack["host"], gw_stack["port"],
                       "/v1/completions",
                       body={"model": "tiny", "prompt": [1, 2, 3],
                             "max_tokens": 4},
                       headers={"traceparent": tp})
    assert resp.status == 200
    rid = resp.getheader("X-Request-Id")
    assert rid and rid.startswith("cmpl-")
    assert json.loads(resp.read())["id"] == rid
    conn.close()
    # the gateway-minted trace adopted the INCOMING W3C trace id
    kept = reqtrace.store().trace(rid)
    assert kept is not None
    assert kept["trace_id"] == incoming_trace
    assert kept["source"] == "gateway"
    assert "qos_admission" in kept["phase_ms"]


def test_request_id_header_on_errors_and_streams(gw_stack):
    host, port = gw_stack["host"], gw_stack["port"]
    # 400 invalid JSON
    conn, resp = _post(host, port, "/v1/completions",
                       raw=b"{not json")
    assert resp.status == 400
    assert resp.getheader("X-Request-Id")
    conn.close()
    # 404 unknown model
    conn, resp = _post(host, port, "/v1/completions",
                       body={"model": "nope", "prompt": [1]})
    assert resp.status == 404
    assert resp.getheader("X-Request-Id")
    conn.close()
    # SSE stream: header present on the live stream response
    conn, resp = _post(host, port, "/v1/completions",
                       body={"model": "tiny", "prompt": [4, 5],
                             "max_tokens": 4, "stream": True})
    assert resp.status == 200
    rid = resp.getheader("X-Request-Id")
    assert rid and rid.startswith("cmpl-")
    while resp.readline():          # drain so the slot frees cleanly
        pass
    conn.close()
    # non-completion routes get the middleware's fallback id
    c2 = http.client.HTTPConnection(host, port, timeout=30.0)
    c2.request("GET", "/v1/models")
    r2 = c2.getresponse()
    assert r2.getheader("X-Request-Id", "").startswith("req-")
    r2.read()
    c2.close()


def test_gateway_stream_records_sse_flush_and_tokens(gw_stack):
    conn, resp = _post(gw_stack["host"], gw_stack["port"],
                       "/v1/completions",
                       body={"model": "tiny", "prompt": [6, 7, 8],
                             "max_tokens": 5, "stream": True})
    assert resp.status == 200
    rid = resp.getheader("X-Request-Id")
    while resp.readline():
        pass
    conn.close()
    store = reqtrace.store()
    deadline = time.monotonic() + 10.0
    kept = None
    while time.monotonic() < deadline:
        kept = store.trace(rid)
        if kept is not None:
            break
        time.sleep(0.05)
    assert kept is not None, rid
    assert kept["outcome"] == "ok"
    assert kept.get("streamed") is True
    flush = [p for p in kept["phases"] if p["phase"] == "sse_flush"]
    assert flush and flush[0]["concurrent"] is True
    assert flush[0]["writes"] >= 1
    # what the frames cost: tokens framed, tokens handed to decode
    assert flush[0]["tokens"] == 5
    assert 5 <= flush[0]["decoded"] <= 7 * 5
    # the phase-sum invariant still leaves the concurrent phase out
    seq_ms = sum(p["dur_ms"] for p in kept["phases"]
                 if not p.get("concurrent"))
    assert seq_ms <= kept["total_ms"] + 5.0, kept
    stats = gw_stack["gw"].stats()
    assert stats["sse_tokens"] >= 5
    assert stats["sse_tokens"] <= stats["sse_decoded_tokens"] \
        <= 7 * stats["sse_tokens"]


# --------------------------------------------------------- chaos e2e


def test_chaos_chunk_delay_makes_kv_transfer_the_tail_owner(
        reqtrace_cluster, model, monkeypatch):
    """delay_chunk_fetch ms=200: the slowed request tops the slowest
    list with kv_transfer dominating its breakdown, and the
    p99-attribution report names kv_transfer as the tail owner."""
    from ray_tpu.resilience import chaos

    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=2, affinity_tokens=BS)
    try:
        # warm up the jit caches first, then drop the warmup trace —
        # compile time would otherwise dwarf the chaos delay and own
        # the tail itself
        router.generate([1, 2, 3, 4], 4)
        reqtrace._reset_store_for_tests()
        # a baseline population (distinct prompts: no prefix-cache
        # shortcut hiding the transfer), then one chaos-slowed request
        for i in range(6):
            router.generate([10 + i, 20 + i, 30 + i, 40 + i], 4)
        monkeypatch.setenv(
            chaos.ENV_VAR,
            '[{"action": "delay_chunk_fetch", "ms": 200}]')
        router.generate([91, 92, 93, 94], 4)
        monkeypatch.delenv(chaos.ENV_VAR)
    finally:
        dec.stop()
    store = reqtrace.store()
    slowest = store.slowest(1)[0]
    # each leaf pull sleeps 200ms: kv_transfer dominates the slowed
    # request and owns its breakdown
    assert slowest["phase_ms"]["kv_transfer"] >= 300.0, slowest
    assert slowest["phase_ms"]["kv_transfer"] >= \
        0.5 * slowest["total_ms"]
    kv_phase = next(p for p in slowest["phases"]
                    if p["phase"] == "kv_transfer")
    assert kv_phase.get("pulls", 0) >= 2       # ChunkFetcher annotated
    assert kv_phase.get("pull_ms", 0.0) >= 300.0
    rep = store.stats()["attribution"]
    assert rep["tail_owner"] == "kv_transfer", rep


# ------------------------------------------------ preempted gateway


def test_preempted_stream_resumes_as_child_span_one_id(model):
    """A batch SSE stream preempted by an interactive arrival resumes
    and completes under ONE request id with the replay attempt-tagged
    (the acceptance scenario's gateway half)."""
    engine = ContinuousBatchingEngine(model, dataclasses.replace(
        CFG, max_seq_len=1024), max_batch=1)
    cfg = dataclasses.replace(CFG, max_seq_len=1024)
    router = DisaggRouter(colocated=engine, max_queue_depth=0)
    gw = GatewayServer(router, model="tiny", vocab_size=cfg.vocab_size,
                       qos=QosGate(router=router), max_tokens_cap=800)
    host, port = gw.ready()
    out = {}
    try:
        def batch_client():
            conn, resp = _post(host, port, "/v1/completions",
                               body={"model": "tiny",
                                     "prompt": [7, 8, 9],
                                     "max_tokens": 600, "stream": True,
                                     "priority": "batch"},
                               timeout=180.0)
            out["rid"] = resp.getheader("X-Request-Id")
            out["status"] = resp.status
            resp.readline()
            streaming.set()
            while resp.readline():
                pass
            conn.close()

        streaming = threading.Event()
        th = threading.Thread(target=batch_client, daemon=True)
        th.start()
        # land inside the production window: the first frame on the
        # wire says the slot is taken, and 600 tokens outlast one POST
        # (a sleep of 0.8 s no longer does: PR 32's loop is done by then)
        assert streaming.wait(timeout=120)
        conn, resp = _post(host, port, "/v1/completions",
                           body={"model": "tiny", "prompt": [4, 5],
                                 "max_tokens": 16,
                                 "priority": "interactive"},
                           timeout=120.0)
        assert resp.status == 200
        resp.read()
        conn.close()
        th.join(timeout=120)
        assert not th.is_alive()
        assert out["status"] == 200
    finally:
        gw.stop()
        engine.stop()
    store = reqtrace.store()
    deadline = time.monotonic() + 10.0
    kept = None
    while time.monotonic() < deadline:
        kept = store.trace(out["rid"])
        if kept is not None:
            break
        time.sleep(0.05)
    assert kept is not None, out
    assert kept["outcome"] == "ok"
    assert kept["preempts"] >= 1             # preempted -> anomalous
    assert kept["attempts"] >= 2
    # the post-preemption decode is a child span under the SAME id
    replay = [p for p in kept["phases"] if p["attempt"] >= 2]
    assert any(p["phase"].startswith("decode") for p in replay), kept


# --------------------------------------------- e2e surface consistency


def test_all_surfaces_report_one_set_of_numbers(reqtrace_cluster,
                                                model, capsys):
    """requesttrace_status() == CLI --json == /api/requesttrace, the
    Prometheus reqtrace families cover the workload, and every kept
    trace renders as real spans in the merged timeline's `requests`
    lane."""
    import urllib.request

    from ray_tpu.dashboard import DashboardServer
    from ray_tpu.scripts import cli
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=2)
    router = DisaggRouter(decode=[dec], prefill=[pf],
                          max_queue_depth=2, affinity_tokens=BS)
    try:
        for i in range(4):
            router.generate([50 + i, 60 + i, 70 + i], 4)
        with pytest.raises(RequestShedError):
            router.generate([1, 2, 3], 4, deadline_s=0.0)
    finally:
        dec.stop()
    store = reqtrace.store()
    local = store.stats()
    assert local["completed"] == 5
    assert local["outcomes"].get("deadline") == 1
    store.publish_telemetry(force=True)
    metrics_mod.flush()

    # state API (fire-and-forget notify: poll until the snapshot lands)
    deadline = time.monotonic() + 10.0
    while True:
        st = state.requesttrace_status()
        mine = st["stores"].get(store.component_id)
        if mine is not None and mine.get("completed") \
                == local["completed"]:
            break
        assert time.monotonic() < deadline, st
        time.sleep(0.1)
    totals = st["totals"]
    assert totals["completed"] >= local["completed"]
    assert totals["outcomes"].get("deadline", 0) >= 1
    assert st["attribution"]["n"] >= 5
    # settle past the publish throttle so the three reads below see
    # the SAME conductor aggregate
    time.sleep(0.6)
    st = state.requesttrace_status()

    # CLI --json (same conductor snapshot)
    w = reqtrace_cluster
    host, port = w.conductor_address
    cli.main(["requests", "--json", "--address", f"{host}:{port}"])
    cli_out = json.loads(capsys.readouterr().out)
    assert cli_out["totals"] == st["totals"]

    # per-id replay: CLI --trace reads the kept record back
    kept_id = st["slowest"][0]["request_id"]
    trc = state.request_trace(kept_id)
    assert trc is not None and trc["request_id"] == kept_id
    assert trc["phases"]

    # dashboard /api/requesttrace
    srv = DashboardServer(w.conductor_address, port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/api/requesttrace",
                                    timeout=10.0) as r:
            dash = json.loads(r.read())
    finally:
        srv.stop()
    assert dash["totals"] == st["totals"]
    assert [r["request_id"] for r in dash["slowest"]] \
        == [r["request_id"] for r in st["slowest"]]
    assert any(e.get("kind") == "trace" for e in dash["events"])

    # Prometheus: the reqtrace families cover this workload
    prom = state.prometheus_metrics()
    assert "ray_tpu_reqtrace_phase_ms" in prom
    assert "ray_tpu_reqtrace_requests_total" in prom
    assert "ray_tpu_reqtrace_kept_total" in prom
    assert "ray_tpu_reqtrace_slowest_ms" in prom
    req_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_reqtrace_requests_total{"))
    assert req_total >= local["completed"]

    # merged timeline: kept traces render as REAL spans in the
    # `requests` lane — enclosing request span + per-phase spans
    trace = state.timeline(merged=True)
    lane = [e for e in trace if e.get("pid") == "requests"]
    req_spans = [e for e in lane if e.get("cat") == "request"]
    phase_spans = [e for e in lane if e.get("cat") == "request_phase"]
    assert any(e["args"]["request_id"] == kept_id for e in req_spans)
    assert all(e["ph"] == "X" for e in req_spans + phase_spans)
    names = {e["name"] for e in phase_spans}
    assert "prefill" in names and "kv_transfer" in names


def test_remote_child_phases_merge_into_the_kept_trace(
        reqtrace_cluster):
    """An actor-mode tier pushes kind="phase" records under the
    originating id; get_request_trace merges them as remote_phases —
    the cross-process half of replay nesting."""
    from ray_tpu.util import state

    store = reqtrace.store()
    tr = reqtrace.RequestTrace("r-remote-1", store=store)
    with tr.phase("prefill"):
        pass
    tr.finish("preempt", cause="preempted")   # anomalous -> kept+event
    reqtrace.push_remote_phase("r-remote-1", "kv_transfer_remote",
                               12.5, attempt=2, server="dec-x")
    deadline = time.monotonic() + 10.0
    trc = None
    while time.monotonic() < deadline:
        trc = state.request_trace("r-remote-1")
        if trc is not None and trc.get("remote_phases"):
            break
        time.sleep(0.1)
    assert trc is not None
    remote = trc["remote_phases"]
    assert remote and remote[0]["phase"] == "kv_transfer_remote"
    assert remote[0]["attempt"] == 2
    assert remote[0]["server"] == "dec-x"
    assert state.request_trace("no-such-id") is None
