"""Production front door (serve/gateway.py + serve/qos.py): the
OpenAI-compatible HTTP surface over REAL sockets.

Everything here exercises the gateway the way a client would — raw
``http.client`` connections against the bound port, SSE frames parsed
off the wire — because the bugs this subsystem exists to catch
(disconnect reaping, status-line-before-shed ordering, stream/
non-stream divergence) are invisible to an in-process call. The core
invariants:

- protocol errors come back as OpenAI error BODIES with the right
  status (400 invalid JSON, 404 unknown model, 401 bad key, 429 over
  quota with ``Retry-After``);
- the concatenated SSE deltas are EXACTLY the non-streaming body, and
  both are bit-identical to the engine oracle (greedy decode is
  deterministic, so "close" is a bug);
- a batch stream that gets preempted by an interactive arrival resumes
  and still finishes bit-identical to an uninterrupted run;
- a client that vanishes mid-stream frees its decode slot (router shed
  cause ``disconnect``, engine cancel tagged ``disconnect``, gateway
  499) instead of finishing a stream nobody reads.

The ``gateway`` marker tags the scenarios; everything is tier-1-safe
on CPU — the telemetry roundtrip runs on a module-scoped cluster with
log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import http.client
import json
import random
import socket
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.disagg import DisaggRouter
from ray_tpu.serve.gateway import (ByteCodec, GatewayServer,
                                   _DELTA_CONTEXT, _StreamText)
from ray_tpu.serve.handle import RequestShedError
from ray_tpu.serve.qos import QosGate, TenantPolicy, TokenBucket

pytestmark = pytest.mark.gateway

# max_seq_len well past tiny()'s 128: the preemption scenario needs a
# batch decode long enough that an interactive arrival lands while the
# engine is still PRODUCING (the window in which a cancel triggers a
# replay instead of a no-op)
CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                          max_seq_len=1024)


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def stack(model):
    """One engine + router + gateway shared by the protocol tests.
    Counters accumulate across tests — assertions use deltas."""
    engine = ContinuousBatchingEngine(model, CFG, max_batch=2)
    router = DisaggRouter(colocated=engine, max_queue_depth=8)
    qos = QosGate(
        api_keys={"sk-alpha": "alpha", "sk-blocked": "blocked"},
        policies={"blocked": TenantPolicy(rate_rps=0.0, burst=0.0)},
        router=router)
    gw = GatewayServer(router, model="tiny",
                       vocab_size=CFG.vocab_size, qos=qos,
                       max_tokens_cap=800)
    host, port = gw.ready()
    yield SimpleNamespace(engine=engine, router=router, gw=gw,
                          host=host, port=port)
    gw.stop()
    engine.stop()


@pytest.fixture
def full_tier(model):
    """One decode slot and no queue behind the gateway: whoever holds
    the slot fills the tier."""
    engine = ContinuousBatchingEngine(model, CFG, max_batch=1)
    router = DisaggRouter(colocated=engine, max_queue_depth=0)
    gw = GatewayServer(router, model="tiny",
                       vocab_size=CFG.vocab_size,
                       qos=QosGate(router=router), max_tokens_cap=800)
    host, port = gw.ready()
    yield SimpleNamespace(engine=engine, router=router, host=host,
                          port=port)
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


def _drain_sse(resp, stop_after=None):
    """Parse SSE frames off the socket; returns (chunks, saw_done).
    ``stop_after`` aborts the read early after N content frames (the
    disconnect tests walk away mid-stream)."""
    chunks = []
    saw_done = False
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            saw_done = True
            break
        chunks.append(json.loads(payload))
        if stop_after is not None and len(chunks) >= stop_after:
            break
    return chunks, saw_done


def _oracle_text(engine, prompt, n):
    return " ".join(str(int(t)) for t in engine.generate(prompt, n))


# ------------------------------------------------------ protocol errors


def test_malformed_json_is_openai_400(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       raw=b"{this is not json")
    assert resp.status == 400
    err = json.loads(resp.read())["error"]
    assert err["type"] == "invalid_request_error"
    assert err["code"] == "invalid_json"
    assert err["message"]
    conn.close()


def test_unknown_model_is_404(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "gpt-nope", "prompt": [1, 2]})
    assert resp.status == 404
    err = json.loads(resp.read())["error"]
    assert err["code"] == "model_not_found"
    conn.close()


def test_bad_prompt_is_400(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": {"no": 1}})
    assert resp.status == 400
    assert json.loads(resp.read())["error"]["type"] == \
        "invalid_request_error"
    conn.close()


def test_unknown_api_key_is_401(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": [1, 2]},
                       headers={"Authorization": "Bearer sk-wrong"})
    assert resp.status == 401
    err = json.loads(resp.read())["error"]
    assert err["type"] == "authentication_error"
    assert err["code"] == "invalid_api_key"
    conn.close()


def test_zero_rate_tenant_is_429_with_retry_after(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": [1, 2]},
                       headers={"Authorization": "Bearer sk-blocked"})
    assert resp.status == 429
    assert int(resp.headers["Retry-After"]) >= 1
    assert resp.headers["X-Shed-Cause"] == "rate_limit"
    err = json.loads(resp.read())["error"]
    assert err["type"] == "rate_limit_error"
    conn.close()
    # the same shed with stream=true must STILL be a real 429 status
    # line, not a 200 that turns into an error frame
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": [1, 2],
                             "stream": True},
                       headers={"Authorization": "Bearer sk-blocked"})
    assert resp.status == 429
    assert resp.headers["X-Shed-Cause"] == "rate_limit"
    conn.close()
    assert stack.gw.stats()["rate_limited"] >= 2


# ------------------------------------------------- parity vs the oracle


def test_stream_and_nonstream_match_engine_oracle(stack):
    prompt, n = [1, 2, 3, 4, 5], 32
    expected = _oracle_text(stack.engine, prompt, n)

    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": prompt,
                             "max_tokens": n})
    assert resp.status == 200
    body = json.loads(resp.read())
    assert body["object"] == "text_completion"
    assert body["choices"][0]["text"] == expected
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == n
    conn.close()

    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": prompt,
                             "max_tokens": n, "stream": True})
    assert resp.status == 200
    assert resp.headers["Content-Type"].startswith("text/event-stream")
    chunks, saw_done = _drain_sse(resp)
    conn.close()
    assert saw_done
    assert chunks[0]["id"].startswith("cmpl-")
    streamed = "".join(c["choices"][0]["text"] for c in chunks)
    assert streamed == expected
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"


def test_chat_stream_matches_chat_nonstream(stack):
    body = {"model": "tiny", "max_tokens": 24,
            "messages": [{"role": "user", "content": "hello there"}]}
    conn, resp = _post(stack.host, stack.port, "/v1/chat/completions",
                       body=body)
    assert resp.status == 200
    out = json.loads(resp.read())
    assert out["object"] == "chat.completion"
    msg = out["choices"][0]["message"]
    assert msg["role"] == "assistant"
    conn.close()

    conn, resp = _post(stack.host, stack.port, "/v1/chat/completions",
                       body=dict(body, stream=True))
    assert resp.status == 200
    chunks, saw_done = _drain_sse(resp)
    conn.close()
    assert saw_done
    assert chunks[0]["object"] == "chat.completion.chunk"
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    streamed = "".join(c["choices"][0]["delta"].get("content", "")
                       for c in chunks)
    assert streamed == msg["content"]


# ------------------------------------------- preemption bit-identity


def test_preempted_batch_stream_is_bit_identical(full_tier):
    """An interactive arrival on a full tier preempts a batch slot;
    the preempted stream replays with its history and must still
    deliver EXACTLY the uninterrupted greedy decode."""
    engine, router = full_tier.engine, full_tier.router
    host, port = full_tier.host, full_tier.port
    prompt, n = [7, 8, 9], 600
    expected = _oracle_text(engine, prompt, n)

    out = {}

    def batch_client():
        conn, resp = _post(host, port, "/v1/completions",
                           body={"model": "tiny", "prompt": prompt,
                                 "max_tokens": n, "stream": True,
                                 "priority": "batch"},
                           timeout=180.0)
        head, _ = _drain_sse(resp, stop_after=1)
        streaming.set()
        chunks, saw_done = _drain_sse(resp)
        out["batch"] = ("".join(c["choices"][0]["text"]
                                for c in head + chunks), saw_done,
                        resp.status)
        conn.close()

    streaming = threading.Event()
    th = threading.Thread(target=batch_client, daemon=True)
    th.start()
    # land inside the engine-production window of the 600-token
    # batch decode, with the single slot occupied -> must preempt:
    # the first frame on the wire says the slot is taken, and 600
    # tokens outlast one POST on any host
    assert streaming.wait(timeout=120)
    conn, resp = _post(host, port, "/v1/completions",
                       body={"model": "tiny", "prompt": [4, 5],
                             "max_tokens": 16,
                             "priority": "interactive"},
                       timeout=120.0)
    assert resp.status == 200
    inter = json.loads(resp.read())["choices"][0]["text"]
    conn.close()
    assert inter == _oracle_text(engine, [4, 5], 16)
    th.join(timeout=120)
    assert not th.is_alive()

    text, saw_done, status = out["batch"]
    assert status == 200 and saw_done
    assert text == expected
    rt = router.stats()
    assert rt["preemptions"] >= 1
    assert rt["preempted_requests"] >= 1
    assert engine.kv_stats()["cancelled_by_reason"].get(
        "preempt", 0) >= 1


def test_batch_arrival_on_full_tier_sheds_with_cause(full_tier):
    """A batch-class arrival cannot preempt: while a batch stream holds
    the only slot (queue depth 0), a second batch request is refused
    over the wire with 503, ``Retry-After`` and an attributed
    ``X-Shed-Cause`` — and the holder's stream is untouched by it."""
    engine, router = full_tier.engine, full_tier.router
    host, port = full_tier.host, full_tier.port
    prompt, n = [7, 8, 9], 40
    expected = _oracle_text(engine, prompt, n)
    conn, resp = _post(host, port, "/v1/completions",
                       body={"model": "tiny", "prompt": prompt,
                             "max_tokens": n, "stream": True,
                             "priority": "batch",
                             "token_sleep_s": 0.05},
                       timeout=120.0)
    assert resp.status == 200
    # the first frame on the wire: the slot is held from here until
    # the paced stream ends, about 2 s later
    head, _ = _drain_sse(resp, stop_after=1)
    conn2, shed = _post(host, port, "/v1/completions",
                        body={"model": "tiny", "prompt": [4, 5],
                              "max_tokens": 8, "priority": "batch"})
    assert shed.status == 503
    assert shed.headers["X-Shed-Cause"] == "capacity"
    assert int(shed.headers["Retry-After"]) >= 1
    assert json.loads(shed.read())["error"]["type"] == "overloaded"
    conn2.close()
    rest, saw_done = _drain_sse(resp)
    conn.close()
    assert saw_done
    assert "".join(c["choices"][0]["text"]
                   for c in head + rest) == expected
    rt = router.stats()
    assert rt["sheds_by_cause"] == {"capacity": 1}
    assert rt["preemptions"] == 0


# --------------------------------------------------- disconnect reaping


def test_client_disconnect_frees_decode_slot(stack):
    before = dict(stack.router.stats()["sheds_by_cause"])
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": [3, 1],
                             "max_tokens": 400, "stream": True,
                             "token_sleep_s": 0.05})
    assert resp.status == 200
    chunks, _ = _drain_sse(resp, stop_after=3)
    assert len(chunks) == 3
    # http.client holds the fd through the response's makefile()
    # refcount — close() alone never sends FIN/RST; shutdown() tears
    # down the OS socket so the gateway actually sees the drop
    conn.sock.shutdown(socket.SHUT_RDWR)
    conn.close()

    deadline = time.time() + 15
    while time.time() < deadline:
        after = stack.router.stats()["sheds_by_cause"]
        if after.get("disconnect", 0) > before.get("disconnect", 0):
            break
        time.sleep(0.2)
    after = stack.router.stats()["sheds_by_cause"]
    assert after.get("disconnect", 0) > before.get("disconnect", 0)
    assert stack.engine.kv_stats()["cancelled_by_reason"].get(
        "disconnect", 0) >= 1
    gs = stack.gw.stats()
    assert gs["disconnects"] >= 1
    assert gs["by_code"].get("499", 0) >= 1


def test_chaos_drop_connection_reaps_like_a_real_drop(stack):
    """The scripted chaos knob must exercise the SAME reap path as an
    organic disconnect: server aborts the transport at token K, the
    router sheds with cause disconnect."""
    spec = json.dumps({"actions": [
        {"action": "drop_connection", "at": "token:5"}]})
    gw = GatewayServer(stack.router, model="tiny",
                       vocab_size=CFG.vocab_size, chaos_spec=spec)
    host, port = gw.ready()
    try:
        before = stack.router.stats()["sheds_by_cause"].get(
            "disconnect", 0)
        conn, resp = _post(host, port, "/v1/completions",
                           body={"model": "tiny", "prompt": [9, 9],
                                 "max_tokens": 400, "stream": True,
                                 "token_sleep_s": 0.02})
        assert resp.status == 200
        with pytest.raises((http.client.IncompleteRead,
                            ConnectionResetError, OSError)):
            while True:
                if not resp.readline():
                    break
            raise ConnectionResetError("server closed early")
        conn.close()
        deadline = time.time() + 15
        while time.time() < deadline:
            if stack.router.stats()["sheds_by_cause"].get(
                    "disconnect", 0) > before:
                break
            time.sleep(0.2)
        assert stack.router.stats()["sheds_by_cause"].get(
            "disconnect", 0) > before
        assert gw.stats()["disconnects"] >= 1
    finally:
        gw.stop()


# ------------------------------------------------ deadline propagation


def test_deadline_header_sheds_with_cause(stack):
    conn, resp = _post(stack.host, stack.port, "/v1/completions",
                       body={"model": "tiny", "prompt": [2, 2],
                             "max_tokens": 400,
                             "token_sleep_s": 0.05},
                       headers={"X-Request-Deadline": "0.2"})
    assert resp.status == 503
    assert resp.headers["X-Shed-Cause"] == "deadline"
    err = json.loads(resp.read())["error"]
    assert err["type"] == "overloaded"
    conn.close()


# ------------------------------------------------------- discovery ops


def test_models_healthz_and_snapshot(stack):
    conn = http.client.HTTPConnection(stack.host, stack.port,
                                      timeout=30)
    conn.request("GET", "/v1/models")
    resp = conn.getresponse()
    assert resp.status == 200
    listing = json.loads(resp.read())
    assert "tiny" in [m["id"] for m in listing["data"]]

    conn.request("GET", "/-/healthz")
    assert conn.getresponse().read() == b"ok"

    conn.request("GET", "/-/gateway")
    snap = json.loads(conn.getresponse().read())
    assert snap["role"] == "gateway"
    assert snap["accepted"] >= 1
    assert "interactive" in snap["by_class"]
    conn.close()


# ---------------------------------------------------------- QoS units


def test_token_bucket_refills_at_rate():
    b = TokenBucket(rate_rps=50.0, burst=1.0)
    assert b.try_acquire() == 0.0
    wait = b.try_acquire()
    assert wait > 0.0
    time.sleep(max(wait, 0.025) + 0.01)
    assert b.try_acquire() == 0.0


def test_qos_inflight_quota_and_release():
    gate = QosGate(policies={"t": TenantPolicy(max_inflight=1)})
    gate.admit("t", "interactive")
    with pytest.raises(RequestShedError) as ei:
        gate.admit("t", "interactive")
    assert ei.value.cause == "quota"
    gate.release("t")
    gate.admit("t", "interactive")
    st = gate.stats()
    assert st["tenants"]["t"]["admitted"] == 2
    assert st["tenants"]["t"]["rejected"] == {"quota": 1}


def test_qos_lifetime_quota_reads_router_accounting():
    class FakeRouter:
        def tenant_stats(self):
            return {"t": {"dispatched": 3}}

    gate = QosGate(policies={"t": TenantPolicy(max_requests=3)},
                   router=FakeRouter())
    with pytest.raises(RequestShedError) as ei:
        gate.admit("t")
    assert ei.value.cause == "quota"


# ------------------------------------------------- telemetry roundtrip


@pytest.fixture(scope="module")
def gateway_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def test_state_api_sees_gateway_telemetry(gateway_cluster, model):
    from ray_tpu.util import state

    engine = ContinuousBatchingEngine(model, CFG, max_batch=2)
    router = DisaggRouter(colocated=engine, max_queue_depth=8)
    gw = GatewayServer(router, model="tiny",
                       vocab_size=CFG.vocab_size,
                       qos=QosGate(router=router))
    host, port = gw.ready()
    try:
        conn, resp = _post(host, port, "/v1/completions",
                           body={"model": "tiny", "prompt": [1, 2],
                                 "max_tokens": 8})
        assert resp.status == 200
        conn.close()
        gw.publish_telemetry(force=True)

        st = state.gateway_status()
        assert gw.gateway_id in st["gateways"]
        totals = st["totals"]
        assert totals["accepted"] >= 1
        assert totals["completed"] >= 1
        assert totals["by_class"]["interactive"]["accepted"] >= 1
        assert totals["by_code"].get("200", 0) >= 1

        w = gateway_cluster
        events = w.conductor.call("get_events", "gateway", limit=10_000)
        kinds = {e.get("kind") for e in events}
        assert "accept" in kinds

        # the timeline lane renders the same events
        from ray_tpu.observability.timeline import gateway_trace_events

        tr = gateway_trace_events(events)
        assert any(ev.get("pid") == "gateway" for ev in tr)
    finally:
        gw.stop()
        engine.stop()


class _GroupRouter:
    """Hands a fixed answer over in the groups it was given."""

    def __init__(self, groups, pause_s):
        self.groups, self.pause_s = groups, pause_s

    def generate(self, prompt, max_tokens, on_tokens=None, **_kw):
        out = []
        for g in self.groups:
            out.extend(g)
            if on_tokens is not None:
                on_tokens(g)
                if self.pause_s:
                    time.sleep(self.pause_s)
        return out

    def stats(self):
        return {}


def test_a_burst_goes_out_in_fewer_frames_and_the_same_text():
    """What queues while the loop writes a frame is sent as ONE frame
    (a loop that lags 64 long streams must not fall ever further behind
    them): the deltas still concatenate to exactly the body."""
    n = 600
    # every token a chunk of its own, handed over back to back: faster
    # than one frame a token can be written
    burst = _GroupRouter([[(7 * i) % 250 + 1] for i in range(n)], 0.0)
    gw = GatewayServer(burst, model="tiny", vocab_size=256,
                       max_tokens_cap=800)
    host, port = gw.ready()
    try:
        conn, resp = _post(host, port, "/v1/completions",
                           body={"model": "tiny", "prompt": [1, 2, 3],
                                 "max_tokens": n, "stream": True})
        assert resp.status == 200
        chunks, saw_done = _drain_sse(resp)
        conn.close()
    finally:
        gw.stop()
    assert saw_done
    want = " ".join(str((7 * i) % 250 + 1) for i in range(n))
    assert "".join(c["choices"][0]["text"] for c in chunks) == want
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    # one frame a token would be n + 1
    assert len(chunks) < n // 2


# ------------------------------------------------ a frame costs a frame


class _Utf8Codec:
    """Tokens are UTF-8 bytes: a character of two to four bytes lies
    across tokens, and half a character decodes to U+FFFD."""

    def encode(self, text):
        return list(text.encode("utf-8")) or [32]

    def decode(self, tokens):
        return bytes(int(t) for t in tokens).decode("utf-8",
                                                    errors="replace")


class _PieceCodec:
    """Pieces joined with NO separator, some with a space of their own
    in front (the sentence-piece shape)."""

    PIECES = ["a", " the", "ing", " ", "qu", " Z", "-", "é", " 你", "x"]

    def encode(self, text):
        return [1 + (b % len(self.PIECES)) for b in text.encode()] or [1]

    def decode(self, tokens):
        return "".join(self.PIECES[int(t) % len(self.PIECES)]
                       for t in tokens)


_UTF8_TEXT = "naïve 你好, wörld 🌍! ½ of a 🧪 — ok. "

_CODECS = {"bytecodec": ByteCodec(256), "utf8": _Utf8Codec(),
           "pieces": _PieceCodec()}


def _stream_tokens(codec_name, n):
    if codec_name == "utf8":    # may end INSIDE a character
        return list((_UTF8_TEXT * (1 + n // 20)).encode("utf-8"))[:n]
    return [(7 * i) % 250 + 1 for i in range(n)]


def _groups(tokens, grouping, seed):
    if grouping == "one":
        return [[t] for t in tokens]
    if grouping == "all":
        return [list(tokens)]
    rng, out, i = random.Random(seed), [], 0
    while i < len(tokens):
        k = rng.randint(1, 5)
        out.append(list(tokens[i:i + k]))
        i += k
    return out


@pytest.mark.parametrize("grouping", ["one", "random", "all"])
@pytest.mark.parametrize("n", [1, 2, 7, 600])
@pytest.mark.parametrize("codec_name", sorted(_CODECS))
def test_joined_deltas_are_the_body_at_a_frames_cost(codec_name, n,
                                                      grouping):
    """The bridge's contract, for any codec and however the tokens were
    grouped into frames: the deltas of a stream, joined, are EXACTLY
    codec.decode(all its tokens), which is the non-streaming body; a
    delta is empty only while it is held back for the rest of its
    character; and a frame costs a few tokens of decode, not the
    answer so far (the old form handed n / 2 tokens a token to
    decode: 300 at 600)."""
    codec = _CODECS[codec_name]
    tokens = _stream_tokens(codec_name, n)
    groups = _groups(tokens, grouping, seed=1000 * n + len(codec_name))
    want = codec.decode(tokens)
    bound = 2 * _DELTA_CONTEXT + 5

    # the delta's maker alone: the groups ARE the frames
    text, sent = _StreamText(codec), ""
    for g in groups:
        text.tokens.extend(g)
        delta = text.delta()
        if not delta:   # held back, and with reason
            rest = codec.decode(text.tokens)[len(sent):]
            assert codec_name == "utf8" and rest.endswith("\ufffd"), (
                g, rest)
        sent += delta
    sent += text.delta(final=True)
    assert sent == want
    assert text.framed == n and text.delta(final=True) == ""
    if n == 600:
        assert text.decoded / n < bound, text.decoded

    # through the wire: frames merge as the loop finds them queued
    gw = GatewayServer(_GroupRouter(groups, 2e-4 if n <= 7 else 0.0),
                       model="tiny", codec=codec, max_tokens_cap=800)
    host, port = gw.ready()
    try:
        body = {"model": "tiny", "prompt": [1, 2, 3], "max_tokens": n}
        conn, resp = _post(host, port, "/v1/completions",
                           body=dict(body, stream=True))
        assert resp.status == 200
        chunks, saw_done = _drain_sse(resp)
        conn.close()
        conn, resp = _post(host, port, "/v1/completions", body=body)
        assert resp.status == 200
        whole = json.loads(resp.read())
        conn.close()
        stats = gw.stats()
    finally:
        gw.stop()
    assert saw_done
    texts = [c["choices"][0]["text"] for c in chunks]
    assert "".join(texts) == want == whole["choices"][0]["text"]
    # no content frame is empty: a held-back delta rides a later frame
    assert all(texts[:-1]) and texts[-1] == ""
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert stats["sse_tokens"] == n
    if n == 600:
        assert stats["sse_decoded_tokens"] / n < bound, stats
