"""OpenAI-compatible HTTP front door for the serving plane.

An asyncio ingress tier (aiohttp server on its own thread + event
loop, the serve/proxy.py idiom) that speaks REAL sockets — so slow
clients, dropped connections, and mixed traffic classes exercise
genuine backpressure — and bridges onto the blocking
``DisaggRouter.generate`` data plane through an executor pool plus the
router's ``on_tokens`` chunk callback (the chunked-pull decode stream,
re-framed as SSE).

Routes::

    POST /v1/completions        OpenAI text completion (+ SSE stream)
    POST /v1/chat/completions   OpenAI chat completion (+ SSE stream)
    GET  /v1/models             the model -> router table
    GET  /-/healthz             liveness
    GET  /-/gateway             this replica's stats snapshot (JSON)

Request contract:

- ``Authorization: Bearer <key>`` resolves the tenant through the
  QoS gate's API-key table (serve/qos.py); ``X-Tenant`` (or OpenAI's
  ``user`` field) is the keyless fallback.
- ``priority`` body field / ``X-Priority`` header picks the class
  (``interactive`` | ``batch``); interactive requests may PREEMPT a
  batch-tier decode slot (router cancel + replay-with-history — the
  resumed stream is bit-identical, same oracle as failover).
- ``X-Request-Deadline: <seconds>`` maps onto
  ``generate(deadline_s=)`` so mid-stream deadline sheds attribute
  correctly for HTTP-originated requests.
- Over-quota / rate-limited -> 429 with ``Retry-After`` (from
  RequestShedError.retry_after_s); capacity/deadline/failover sheds
  -> 503 with ``Retry-After`` + ``X-Shed-Cause``.
- A client that disconnects mid-stream is REAPED: the handler's
  cancel event sheds the router request (cause ``disconnect``) and
  the engine slot frees at the next tick boundary instead of
  decoding to an abandoned socket.

The tiny research checkpoints ship no tokenizer, so the default
:class:`ByteCodec` folds utf-8 bytes into the model vocab on encode
and renders token ids as space-joined integers on decode — every
surface stays bit-checkable against the engine oracle. ``prompt`` may
also be a raw token-id list (the OpenAI array-of-tokens form), which
is what the tests drive.

A streamed delta is made from the tokens that are NEW since the last
frame and ``_DELTA_CONTEXT`` tokens before them (:class:`_StreamText`:
``decode(tokens[p:])`` less ``decode(tokens[p:r])``), never from the
whole answer again, so a frame's cost does not grow with the answer;
a delta that ends inside a character (U+FFFD from a byte codec) waits
for the tokens that complete it. The deltas of a stream, joined, are
exactly ``codec.decode(all its tokens)``, the non-streaming body, for
any ``codec=`` whose pieces join locally. The request's ``sse_flush``
phase carries ``writes``, ``tokens`` (framed) and ``decoded`` (handed
to ``codec.decode``), ``stats()`` their totals ``sse_tokens`` and
``sse_decoded_tokens``: the ratio is what a frame costs.

Per repo convention the gateway gets the full surface treatment:
``util.state.gateway_status()``, ``ray_tpu gateway``, dashboard
``/api/gateway`` + tab, lazy Prometheus
(``ray_tpu_gateway_requests_total{route,class,code}``,
``ray_tpu_gateway_ttft_ms{class}``,
``ray_tpu_gateway_rate_limited_total{tenant}``,
``ray_tpu_gateway_preemptions_total``), and the merged timeline's
``gateway`` lane (accept / first_byte / preempt / rate_limit /
disconnect markers) — one set of numbers across all five.
"""
from __future__ import annotations

import asyncio
import itertools
import json
import os
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

from ray_tpu.observability import requests as reqtrace
from ray_tpu.util.telemetry import Pusher, emit

from .autoscale import SlidingWindow
from .handle import RequestShedError
from .qos import (CLASSES, INTERACTIVE, QosGate, gateway_metrics,
                  shed_outcome)

_GW_SEQ = itertools.count()

# write failures that mean "the client went away", not "we broke"
_CLIENT_GONE = (ConnectionResetError, ConnectionAbortedError,
                BrokenPipeError)


class ByteCodec:
    """Deterministic toy text codec for tokenizer-less checkpoints:
    encode folds utf-8 bytes into ``[1, vocab)`` (id 0 is reserved —
    many configs use it for padding), decode renders ids as
    space-joined integers. decode(encode(s)) is NOT the identity —
    the contract is determinism and prefix-stability (the streaming
    deltas concatenate to exactly the non-streaming body), not
    round-tripping."""

    def __init__(self, vocab_size: int = 32000):
        self.vocab_size = max(3, int(vocab_size))

    def encode(self, text: str) -> List[int]:
        data = text.encode("utf-8")
        span = self.vocab_size - 1
        return [1 + (b % span) for b in data] or [1]

    def decode(self, tokens) -> str:
        return " ".join(str(int(t)) for t in tokens)


def _sse_frame(payload: Any) -> bytes:
    """One SSE data frame. Payloads are single-line JSON (json.dumps
    emits no raw newlines), so the one-line form is spec-compliant."""
    if isinstance(payload, bytes):
        data = payload
    elif isinstance(payload, str):
        data = payload.encode()
    else:
        data = json.dumps(payload, default=str).encode()
    return b"data: " + data + b"\n\n"


# tokens of context a delta is decoded behind: a codec's pieces join
# LOCALLY (ByteCodec's separating space needs one token, a byte
# tokenizer's character at most three bytes before its last, a
# sentence-piece's leading space one), so a few tokens behind the last
# frame's end render the new tokens as the whole answer would
_DELTA_CONTEXT = 3
_INCOMPLETE = "\ufffd"     # what a byte codec renders half a character as


class _StreamText:
    """One stream's text, a frame at a time, at a frame's cost: the
    delta of the tokens that are new is ``decode(tokens[p:])`` less
    ``decode(tokens[p:r])``, ``r`` the last frame's end and ``p``
    ``_DELTA_CONTEXT`` tokens behind it — never the whole answer
    again. A delta that is empty or ends in an incomplete character is
    held back (``""``; ``r`` stays) until the tokens that complete it
    have come, and ``final=True`` sends what is held as it is. The
    deltas, joined, are EXACTLY ``codec.decode(tokens)``, however the
    tokens were grouped into frames."""

    __slots__ = ("_decode", "tokens", "framed", "decoded")

    def __init__(self, codec: Any):
        self._decode = codec.decode
        self.tokens: List[int] = []
        self.framed = 0     # tokens whose text has gone out: r
        self.decoded = 0    # tokens handed to decode, both calls

    def delta(self, final: bool = False) -> str:
        toks, r = self.tokens, self.framed
        if r == len(toks):
            return ""
        p = max(0, r - _DELTA_CONTEXT)
        text = self._decode(toks[p:])
        self.decoded += len(toks) - p
        if r > p:
            text = text[len(self._decode(toks[p:r])):]
            self.decoded += r - p
        if not final and (not text or text.endswith(_INCOMPLETE)):
            return ""
        self.framed = len(toks)
        return text


class GatewayServer:
    """One gateway replica: an aiohttp server thread in front of one
    (or several, keyed by model name) DisaggRouter(s). Runs equally
    as an in-process object or a ray_tpu actor — the constructor only
    spawns a thread; ``ready()`` blocks until the socket is bound."""

    def __init__(self, router: Any = None, *,
                 models: Optional[Dict[str, Any]] = None,
                 model: str = "ray-tpu",
                 qos: Optional[QosGate] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 eos_token: Optional[int] = None,
                 vocab_size: int = 32000,
                 codec: Any = None,
                 default_max_tokens: int = 16,
                 max_tokens_cap: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 request_timeout_s: float = 120.0,
                 chaos_spec: Optional[str] = None,
                 replica: int = 0,
                 gateway_id: Optional[str] = None):
        if models is None:
            if router is None:
                raise ValueError("need a router (or a models= table)")
            models = {model: router}
        self._models = dict(models)
        self._qos = qos
        self._host = host
        self._port = port
        self._eos_token = eos_token
        self._codec = codec or ByteCodec(vocab_size)
        self.default_max_tokens = int(default_max_tokens)
        if max_tokens_cap is None:
            max_tokens_cap = int(os.environ.get(
                "RAY_TPU_GATEWAY_MAX_TOKENS", "512"))
        self.max_tokens_cap = max(1, int(max_tokens_cap))
        self.default_deadline_s = default_deadline_s
        self.request_timeout_s = float(request_timeout_s)
        self.gateway_id = gateway_id or \
            f"gateway-{os.getpid()}-{next(_GW_SEQ)}"
        # scripted connection drops (resilience/chaos.py
        # drop_connection at=token:K): the monkey's exit_fn latches a
        # flag instead of killing the process; the handler that
        # crossed the K-th served token aborts ITS transport — from
        # the router's point of view this is exactly a client that
        # vanished, which is the point: the chaos knob proves the
        # disconnect-reap path with a deterministic trigger.
        from ray_tpu.resilience.chaos import serve_monkey_from_spec

        self._chaos = serve_monkey_from_spec(
            chaos_spec, "gateway", replica, exit_fn=self._chaos_fire)
        self._chaos_fired = False
        self._lock = threading.Lock()
        self._stats: Dict[str, Any] = {
            "accepted": 0, "completed": 0, "streamed": 0,
            "disconnects": 0, "rate_limited": 0, "sheds": 0,
            "errors": 0, "preempt_dropped": 0, "tokens_out": 0,
            # tokens the SSE bridge framed, and tokens it handed to
            # codec.decode for them: their ratio is a frame's cost
            "sse_tokens": 0, "sse_decoded_tokens": 0,
        }
        self._by_class: Dict[str, Dict[str, int]] = {
            c: {"accepted": 0, "completed": 0, "shed": 0,
                "disconnects": 0} for c in CLASSES}
        self._by_code: Dict[str, int] = {}
        self._ttft_win: Dict[str, SlidingWindow] = {
            c: SlidingWindow() for c in CLASSES}
        self._pusher = Pusher("gateway", self.gateway_id)
        self._ready = threading.Event()
        self._bound_port: Optional[int] = None
        self._shutdown = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._pool = ThreadPoolExecutor(
            max_workers=int(os.environ.get(
                "RAY_TPU_GATEWAY_POOL", "32")),
            thread_name_prefix="gateway-generate")
        threading.Thread(target=self._serve_thread, daemon=True,
                         name="gateway-http").start()
        gateway_metrics()

    # --------------------------------------------------------- control

    def ready(self) -> tuple:
        if not self._ready.wait(timeout=30.0):
            raise RuntimeError("gateway HTTP server failed to start")
        return (self._host, self._bound_port)

    def stop(self) -> bool:
        self._shutdown.set()
        self.publish_telemetry(force=True)
        return True

    def _chaos_fire(self, _code: int) -> None:
        self._chaos_fired = True

    def _consume_chaos(self, hook: str, n: int = 1) -> bool:
        """Advance the chaos monkey's request/token counters; True
        when a drop_connection action fired on THIS call (handlers run
        on the single loop thread, so fire attribution is race-free)."""
        if self._chaos is None:
            return False
        if hook == "request":
            self._chaos.on_request()
        else:
            self._chaos.on_tokens(n)
        if self._chaos_fired:
            self._chaos_fired = False
            return True
        return False

    # ------------------------------------------------------- accounting

    def _count(self, route: str, cls: str, code: int) -> None:
        with self._lock:
            key = str(code)
            self._by_code[key] = self._by_code.get(key, 0) + 1
            if code == 429:
                self._stats["rate_limited"] += 1
            elif code in (499,):
                self._stats["disconnects"] += 1
                if cls in self._by_class:
                    self._by_class[cls]["disconnects"] += 1
            elif code in (503,):
                self._stats["sheds"] += 1
                if cls in self._by_class:
                    self._by_class[cls]["shed"] += 1
            elif code >= 400:
                self._stats["errors"] += 1
        gateway_metrics()["requests"].inc(
            tags={"route": route, "class": cls, "code": str(code)})
        self.publish_telemetry()

    def _count_accept(self, route: str, cls: str,
                      tenant: Optional[str]) -> None:
        with self._lock:
            self._stats["accepted"] += 1
            if cls in self._by_class:
                self._by_class[cls]["accepted"] += 1
        emit("gateway", {"kind": "accept", "gateway": self.gateway_id,
                         "route": route, "class": cls,
                         "tenant": tenant})
        self.publish_telemetry()

    def _count_done(self, cls: str, n_tokens: int,
                    streamed: bool) -> None:
        with self._lock:
            self._stats["completed"] += 1
            self._stats["tokens_out"] += int(n_tokens)
            if streamed:
                self._stats["streamed"] += 1
            if cls in self._by_class:
                self._by_class[cls]["completed"] += 1

    def _first_byte(self, cls: str, ttft_ms: float) -> None:
        self._ttft_win.setdefault(cls, SlidingWindow()).add(ttft_ms)
        gateway_metrics()["ttft_ms"].observe(ttft_ms,
                                             tags={"class": cls})
        emit("gateway", {"kind": "first_byte",
                         "gateway": self.gateway_id, "class": cls,
                         "ttft_ms": round(ttft_ms, 3)})

    def stats(self) -> Dict[str, Any]:
        """This replica's snapshot — the shape the conductor
        aggregates. ``preemptions`` reads the routers' own counter
        (the router fires preemptions, the gateway only causes them):
        one counter, surfaced everywhere."""
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            s["by_class"] = {c: dict(v)
                            for c, v in self._by_class.items()}
            s["by_code"] = dict(self._by_code)
        preempt = 0
        for r in self._models.values():
            try:
                preempt += int(r.stats().get("preemptions", 0))
            except Exception:  # noqa: BLE001 — router mid-teardown
                pass
        s["preemptions"] = preempt
        s["role"] = "gateway"
        s["gateway_id"] = self.gateway_id
        s["host"] = self._host
        s["port"] = self._bound_port
        s["models"] = sorted(self._models)
        s["ttft_ms"] = {c: w.summary()
                        for c, w in self._ttft_win.items()}
        if self._qos is not None:
            s["qos"] = self._qos.stats()
        return s

    def publish_telemetry(self, force: bool = False) -> None:
        self._pusher.push(self.stats, force=force)

    # ------------------------------------------------------ http plumbing

    def _error_body(self, message: str, err_type: str,
                    code: Optional[str]) -> Dict[str, Any]:
        return {"error": {"message": message, "type": err_type,
                          "param": None, "code": code}}

    def _client_gone(self, request) -> bool:
        t = request.transport
        return t is None or t.is_closing()

    @staticmethod
    def _shed_status(e: RequestShedError) -> int:
        return 429 if getattr(e, "cause", None) in ("rate_limit",
                                                    "quota") else 503

    @staticmethod
    def _shed_headers(e: RequestShedError) -> Dict[str, str]:
        return {"Retry-After":
                str(max(1, int(getattr(e, "retry_after_s", 1.0)))),
                "X-Shed-Cause": str(getattr(e, "cause", "capacity"))}

    def _encode_prompt(self, body: Dict[str, Any],
                       route: str) -> List[int]:
        """OpenAI request -> token ids. Raises ValueError (-> 400)."""
        if route == "chat":
            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                raise ValueError("'messages' must be a non-empty list")
            parts = []
            for m in messages:
                if not isinstance(m, dict) or "content" not in m:
                    raise ValueError(
                        "each message needs 'role' and 'content'")
                parts.append(f"{m.get('role', 'user')}: {m['content']}")
            return self._codec.encode("\n".join(parts))
        prompt = body.get("prompt")
        if isinstance(prompt, str):
            return self._codec.encode(prompt)
        if isinstance(prompt, list) and prompt and \
                all(isinstance(t, int) for t in prompt):
            return [int(t) for t in prompt]
        raise ValueError(
            "'prompt' must be a string or a list of token ids")

    def _completion_payload(self, route: str, req_id: str,
                            created: int, model: str, text: str,
                            finish: Optional[str],
                            n_prompt: int, n_out: int,
                            chunk: bool = False,
                            first_chunk: bool = False
                            ) -> Dict[str, Any]:
        if route == "chat":
            if chunk:
                delta: Dict[str, Any] = {"content": text}
                if first_chunk:
                    delta["role"] = "assistant"
                choice: Dict[str, Any] = {"index": 0, "delta": delta,
                                          "finish_reason": finish}
                obj = "chat.completion.chunk"
            else:
                choice = {"index": 0,
                          "message": {"role": "assistant",
                                      "content": text},
                          "finish_reason": finish}
                obj = "chat.completion"
        else:
            choice = {"index": 0, "text": text,
                      "finish_reason": finish}
            obj = "text_completion"
        out = {"id": req_id, "object": obj, "created": created,
               "model": model, "choices": [choice]}
        if not chunk:
            out["usage"] = {"prompt_tokens": n_prompt,
                            "completion_tokens": n_out,
                            "total_tokens": n_prompt + n_out}
        return out

    # ------------------------------------------------------ the handlers

    async def _handle(self, request, route: str):
        """Parse/authenticate/admit, then dispatch to the streaming or
        blocking bridge. Every early exit counts into
        requests_total{route,class,code} — the class is "-" until the
        request names one."""
        from aiohttp import web

        cls = "-"
        tenant: Optional[str] = None
        admitted = False
        # the request id is minted BEFORE parsing so even a 400 carries
        # a correlatable X-Request-Id (the middleware stamps whatever
        # this handler left in request["req_id"]); an incoming W3C
        # traceparent bridges the caller's trace id into the flight
        # recorder
        t_req = time.perf_counter()
        req_id = (f"cmpl-{uuid.uuid4().hex[:24]}" if route != "chat"
                  else f"chatcmpl-{uuid.uuid4().hex[:24]}")
        request["req_id"] = req_id
        tp_in = request.headers.get("traceparent")
        try:
            try:
                body = json.loads((await request.read()) or b"")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
            except (ValueError, UnicodeDecodeError):
                self._count(route, cls, 400)
                return web.json_response(self._error_body(
                    "request body is not a valid JSON object",
                    "invalid_request_error", "invalid_json"),
                    status=400)
            model = body.get("model")
            if model is None and len(self._models) == 1:
                model = next(iter(self._models))
            router = self._models.get(model)
            if router is None:
                self._count(route, cls, 404)
                return web.json_response(self._error_body(
                    f"model {model!r} does not exist",
                    "invalid_request_error", "model_not_found"),
                    status=404)
            auth = request.headers.get("Authorization", "")
            api_key = auth[7:] if auth.startswith("Bearer ") else None
            hint = request.headers.get("X-Tenant") or body.get("user")
            try:
                tenant = (self._qos.resolve(api_key, hint)
                          if self._qos is not None else hint)
            except PermissionError:
                self._count(route, cls, 401)
                return web.json_response(self._error_body(
                    "invalid API key", "authentication_error",
                    "invalid_api_key"), status=401)
            requested_cls = (body.get("priority")
                             or request.headers.get("X-Priority"))
            try:
                if self._qos is not None:
                    cls = self._qos.classify(tenant, requested_cls)
                else:
                    cls = requested_cls or INTERACTIVE
                    if cls not in CLASSES:
                        raise ValueError(
                            f"unknown priority class {cls!r}")
                prompt_tokens = self._encode_prompt(body, route)
                max_tokens = int(body.get(
                    "max_tokens", self.default_max_tokens))
                max_tokens = max(1, min(max_tokens,
                                        self.max_tokens_cap))
                deadline_s = self.default_deadline_s
                hdr = request.headers.get("X-Request-Deadline")
                if hdr:
                    deadline_s = float(hdr)
                # test extension: router-side slow-client pacing —
                # tiny research checkpoints decode faster than any
                # real socket, so real-pacing scenarios need the
                # stream held open
                token_sleep_s = min(
                    1.0, max(0.0, float(body.get("token_sleep_s", 0))))
            except (TypeError, ValueError) as e:
                self._count(route, cls, 400)
                return web.json_response(self._error_body(
                    str(e), "invalid_request_error", None),
                    status=400)
            if self._qos is not None:
                try:
                    self._qos.admit(tenant, cls)
                    admitted = True
                except RequestShedError as e:
                    status = self._shed_status(e)
                    self._count(route, cls, status)
                    # a gate-shed request still leaves a trace — shed
                    # outcomes are always retained, so the tail report
                    # sees admission rejections, not just completions
                    tr = reqtrace.start_trace(
                        req_id, source="gateway", traceparent=tp_in,
                        tenant=tenant, cls=cls, t0=t_req)
                    if tr is not None:
                        tr.add_phase(
                            "qos_admission",
                            (time.perf_counter() - t_req) * 1e3)
                        tr.finish("shed",
                                  cause=getattr(e, "cause", None))
                    return web.json_response(
                        self._error_body(str(e), "rate_limit_error",
                                         getattr(e, "cause", None)),
                        status=status, headers=self._shed_headers(e))
            self._count_accept(route, cls, tenant)
            if self._consume_chaos("request"):
                # scripted drop at admission: the socket dies before
                # any byte of response — the client sees a reset
                if request.transport is not None:
                    request.transport.abort()
                self._count(route, cls, 499)
                raise ConnectionResetError("chaos drop_connection")
            created = int(time.time())
            # the flight-recorder trace: t0 backdated to handler entry
            # so qos_admission covers parse + auth + classify + admit
            tr = reqtrace.start_trace(
                req_id, source="gateway", traceparent=tp_in,
                tenant=tenant, cls=cls, t0=t_req)
            if tr is not None:
                tr.add_phase("qos_admission",
                             (time.perf_counter() - t_req) * 1e3)
            ctx = dict(route=route, cls=cls, tenant=tenant,
                       router=router, model=model or "",
                       prompt_tokens=prompt_tokens,
                       max_tokens=max_tokens, deadline_s=deadline_s,
                       token_sleep_s=token_sleep_s,
                       req_id=req_id, created=created, trace=tr)
            if body.get("stream"):
                return await self._stream_response(request, ctx)
            return await self._block_response(request, ctx)
        finally:
            if admitted:
                self._qos.release(tenant)

    def _generate_kwargs(self, ctx: Dict[str, Any]) -> Dict[str, Any]:
        kw = dict(eos_token=self._eos_token,
                  timeout_s=self.request_timeout_s,
                  deadline_s=ctx["deadline_s"],
                  token_sleep_s=ctx.get("token_sleep_s") or 0.0,
                  priority=ctx["cls"])
        # the tenant reaches the DATA plane only on a LoRA-enabled
        # deployment (adapter routing, namespace-keyed KV, per-tenant
        # router accounting); an explicit tenant on a pool-less tier
        # fails loudly by design, so a plain deployment keeps the
        # tenant at the QoS layer
        router = ctx["router"]
        try:
            lora = bool(router._lora_enabled())
        except Exception:  # noqa: BLE001 — non-DisaggRouter backend
            lora = False
        if lora:
            kw["tenant"] = ctx["tenant"]
        return kw

    async def _block_response(self, request, ctx: Dict[str, Any]):
        """Non-streaming bridge: the blocking generate runs on the
        executor pool (never on the loop); a client that disconnects
        while waiting cancels the decode through the same reap path
        as a mid-stream drop."""
        from aiohttp import web

        loop = asyncio.get_running_loop()
        route, cls = ctx["route"], ctx["cls"]
        router = ctx["router"]
        tr = ctx.get("trace")
        cancel_event = threading.Event()
        t0 = time.perf_counter()
        kwargs = self._generate_kwargs(ctx)
        kwargs["cancel_event"] = cancel_event

        def work():
            # activate on the EXECUTOR thread: the router's generate —
            # and every in-process tier hop under it — stamps phases
            # onto this request's trace through the thread-local
            with reqtrace.activate(tr):
                return router.generate(ctx["prompt_tokens"],
                                       ctx["max_tokens"], **kwargs)

        try:
            toks = await loop.run_in_executor(self._pool, work)
        except asyncio.CancelledError:
            # aiohttp cancelled the handler: the client went away
            cancel_event.set()
            self._count(route, cls, 499)
            emit("gateway", {"kind": "disconnect",
                             "gateway": self.gateway_id,
                             "class": cls, "phase": "waiting"})
            if tr is not None:
                tr.finish("disconnect", cause="client_gone")
            raise
        except RequestShedError as e:
            status = self._shed_status(e)
            self._count(route, cls, status)
            if tr is not None:
                outcome, cause = shed_outcome(e)
                tr.finish(outcome, cause=cause)
            return web.json_response(
                self._error_body(str(e), "rate_limit_error"
                                 if status == 429 else "overloaded",
                                 getattr(e, "cause", None)),
                status=status, headers=self._shed_headers(e))
        except ValueError as e:
            self._count(route, cls, 400)
            if tr is not None:
                tr.finish("error", cause=type(e).__name__)
            return web.json_response(self._error_body(
                str(e), "invalid_request_error", None), status=400)
        except Exception as e:  # noqa: BLE001 — surface as 500
            self._count(route, cls, 500)
            if tr is not None:
                tr.finish("error", cause=type(e).__name__)
            return web.json_response(self._error_body(
                f"{type(e).__name__}: {e}", "api_error", None),
                status=500)
        self._first_byte(cls, (time.perf_counter() - t0) * 1e3)
        text = self._codec.decode(toks)
        finish = ("stop" if self._eos_token is not None and toks
                  and toks[-1] == int(self._eos_token) else "length")
        self._count_done(cls, len(toks), streamed=False)
        self._count(route, cls, 200)
        if tr is not None:
            tr.finish("ok", tokens=len(toks))
        return web.json_response(self._completion_payload(
            route, ctx["req_id"], ctx["created"], ctx["model"], text,
            finish, len(ctx["prompt_tokens"]), len(toks)))

    def _hand_over(self, batch: List[tuple]) -> None:
        """This server's ONE sink (``models.engine.StreamSink.call``),
        called on an engine's loop thread once a pass with every
        stream's new tokens: ONE `call_soon_threadsafe` for them all,
        whose callback (`_deliver`, on the asyncio thread) puts each
        stream's on that stream's queue, which its `tag` holds."""
        try:
            self._loop.call_soon_threadsafe(self._deliver, batch)
        except RuntimeError:  # loop shut down: nobody reads any more
            for stream, _toks, _ended in batch:
                stream.tag[1]()

    @staticmethod
    def _deliver(batch: List[tuple]) -> None:
        for stream, toks, _ended in batch:
            if toks:
                stream.tag[0].put_nowait(("tokens", toks))

    async def _stream_response(self, request, ctx: Dict[str, Any]):
        """SSE bridge: generate runs on the executor and what it makes
        lands on this request's asyncio queue, to be re-framed as OpenAI
        stream chunks. Where the router's engine hands over (a colocated
        engine: `DisaggRouter.stream_sink`), the TOKENS cross from the
        engine's loop thread, every stream's in one
        `call_soon_threadsafe` a pass (`_hand_over`), and the executor's
        thread sleeps from the first token to the end, when it sends
        ``done`` behind them; elsewhere its `on_tokens` chunks cross one
        `call_soon_threadsafe` each. Each delta is made from
        the tokens that are new and a few before them (_StreamText: a
        frame costs a frame, not the answer so far), and concatenated
        deltas are EXACTLY the non-streaming body. Disconnects —
        noticed by a failed write, by aiohttp cancelling the handler,
        or by transport polling while decode is quiet — set the cancel
        event; the router sheds the request with cause ``disconnect``
        and the decode slot frees instead of finishing the stream
        nobody reads."""
        from aiohttp import web

        loop = asyncio.get_running_loop()
        route, cls = ctx["route"], ctx["cls"]
        router = ctx["router"]
        tr = ctx.get("trace")
        cancel_event = threading.Event()
        q: asyncio.Queue = asyncio.Queue()
        t0 = time.perf_counter()
        # sse_flush accounting: wall time spent on frames (the delta's
        # decode, the payload and resp.write) — concurrent with decode
        # (the executor keeps generating while the loop flushes), so
        # the phase is marked concurrent and excluded from the
        # phase-sum invariant
        flush_s = 0.0
        flush_n = 0
        text = _StreamText(self._codec)
        got = text.tokens

        # the stream's tokens by hand-over, where the router has an
        # engine that hands over; its worker then sleeps on `sink.wake`
        make_sink = getattr(router, "stream_sink", None)
        sink = None

        def _cancel():
            cancel_event.set()
            if sink is not None:
                sink.wake.set()     # a sleeping worker sheds NOW

        def _put(item):
            try:
                loop.call_soon_threadsafe(q.put_nowait, item)
            except RuntimeError:  # loop shut down mid-request
                _cancel()

        kwargs = self._generate_kwargs(ctx)
        kwargs["cancel_event"] = cancel_event
        kwargs["on_tokens"] = lambda toks: _put(("tokens", list(toks)))
        if callable(make_sink):
            # its tag: where `_deliver` puts the tokens, and what
            # `_hand_over` calls where nobody reads any more
            sink = make_sink(self._hand_over, (q, _cancel))
        if sink is not None:
            kwargs["sink"] = sink

        def work():
            try:
                with reqtrace.activate(tr):
                    out = router.generate(ctx["prompt_tokens"],
                                          ctx["max_tokens"], **kwargs)
                _put(("done", out))
            except BaseException as e:  # noqa: BLE001 — relayed
                _put(("error", e))

        def _finish(outcome, cause=None, **attrs):
            with self._lock:
                self._stats["sse_tokens"] += text.framed
                self._stats["sse_decoded_tokens"] += text.decoded
            if tr is None:
                return
            if flush_s > 0.0:
                tr.add_phase("sse_flush", flush_s * 1e3,
                             concurrent=True, writes=flush_n,
                             tokens=text.framed, decoded=text.decoded)
            tr.finish(outcome, cause=cause, **attrs)

        # the status line is written lazily at the FIRST frame: a
        # request the router sheds before producing anything (capacity,
        # quota, deadline) still gets a real 429/503 status response —
        # only a shed that lands mid-stream has to ride an SSE error
        # frame, because by then the 200 is already on the wire
        resp = web.StreamResponse(status=200)
        resp.headers["Content-Type"] = "text/event-stream"
        resp.headers["Cache-Control"] = "no-cache"
        # set pre-prepare: once the SSE status line is on the wire the
        # middleware can no longer add headers
        resp.headers["X-Request-Id"] = ctx["req_id"]
        resp.enable_chunked_encoding()
        prepared = False

        async def _prepare_once():
            nonlocal prepared
            if not prepared:
                await resp.prepare(request)
                prepared = True

        first = True

        async def _write_delta(delta):
            nonlocal flush_n, first
            await _prepare_once()
            await resp.write(_sse_frame(self._completion_payload(
                route, ctx["req_id"], ctx["created"], ctx["model"],
                delta, None, 0, 0, chunk=True, first_chunk=first)))
            flush_n += 1
            if first:
                first = False
                self._first_byte(cls, (time.perf_counter() - t0) * 1e3)

        self._pool.submit(work)
        held: Optional[tuple] = None    # an item taken behind a frame
        disconnected = False
        failed: Optional[BaseException] = None
        try:
            while True:
                # poll the transport every pass, not only when the
                # queue is quiet — under a steady token stream the
                # queue never drains and a dead socket would
                # otherwise go unnoticed until a write bounced
                if self._client_gone(request):
                    disconnected = True
                    break
                if held is not None:
                    (kind, payload), held = held, None
                else:
                    try:
                        kind, payload = await asyncio.wait_for(
                            q.get(), timeout=0.5)
                    except asyncio.TimeoutError:
                        continue
                if kind == "tokens":
                    got.extend(payload)
                    # a loop that lags its streams (64 of them at 80
                    # tokens a second each outrun one frame a token)
                    # sends what has queued meanwhile as ONE frame: the
                    # cost of a pass is then a frame's, not a token's,
                    # and the backlog cannot grow without bound
                    while held is None and not q.empty():
                        item = q.get_nowait()
                        if item[0] == "tokens":
                            got.extend(item[1])
                        else:       # the stream's end: the next pass's
                            held = item
                    t_w = time.perf_counter()
                    n_new = len(got) - text.framed
                    try:
                        delta = text.delta()
                        if delta:
                            await _write_delta(delta)
                    except _CLIENT_GONE:
                        disconnected = True
                        break
                    finally:
                        flush_s += time.perf_counter() - t_w
                    if not delta:   # held back: it rides a later frame
                        continue
                    if self._consume_chaos("tokens", n_new):
                        if request.transport is not None:
                            request.transport.abort()
                        disconnected = True
                        break
                elif kind == "done":
                    toks = payload
                    finish = ("stop" if self._eos_token is not None
                              and toks
                              and toks[-1] == int(self._eos_token)
                              else "length")
                    t_w = time.perf_counter()
                    try:
                        delta = text.delta(final=True)
                        if delta:   # what was held goes out as it is
                            await _write_delta(delta)
                        await _prepare_once()
                        await resp.write(_sse_frame(
                            self._completion_payload(
                                route, ctx["req_id"], ctx["created"],
                                ctx["model"], "", finish, 0, 0,
                                chunk=True)))
                        await resp.write(_sse_frame(b"[DONE]"))
                        await resp.write_eof()
                        flush_n += 1
                    except _CLIENT_GONE:
                        disconnected = True
                        break
                    finally:
                        flush_s += time.perf_counter() - t_w
                    self._count_done(cls, len(toks), streamed=True)
                    self._count(route, cls, 200)
                    _finish("ok", tokens=len(toks), streamed=True)
                    return resp
                else:  # error relayed from the executor
                    failed = payload
                    break
        except asyncio.CancelledError:
            _cancel()
            self._count(route, cls, 499)
            emit("gateway", {"kind": "disconnect",
                             "gateway": self.gateway_id,
                             "class": cls, "phase": "streaming"})
            _finish("disconnect", cause="client_gone",
                    tokens_sent=len(got))
            raise
        if disconnected:
            _cancel()
            self._count(route, cls, 499)
            emit("gateway", {"kind": "disconnect",
                             "gateway": self.gateway_id,
                             "class": cls, "phase": "streaming",
                             "tokens_sent": len(got)})
            _finish("disconnect", cause="client_gone",
                    tokens_sent=len(got))
            return resp
        if isinstance(failed, RequestShedError):
            status = self._shed_status(failed)
            err_type = ("rate_limit_error" if status == 429
                        else "overloaded")
            headers = self._shed_headers(failed)
            outcome, cause = shed_outcome(failed)
            _finish(outcome, cause=cause)
        elif isinstance(failed, ValueError):
            status, err_type, headers = 400, "invalid_request_error", {}
            _finish("error", cause=type(failed).__name__)
        else:
            status, err_type, headers = 500, "api_error", {}
            _finish("error", cause=type(failed).__name__
                    if failed is not None else None)
        self._count(route, cls, status)
        body = self._error_body(str(failed), err_type,
                                getattr(failed, "cause", None))
        if not prepared:
            # nothing on the wire yet: the shed gets a real status
            # line, same shape as the non-streaming path
            return web.json_response(body, status=status,
                                     headers=headers)
        # mid-stream failure: headers are long gone — terminate the
        # event stream with an error frame + [DONE] so a compliant
        # client stops reading instead of hanging
        try:
            await resp.write(_sse_frame(body))
            await resp.write(_sse_frame(b"[DONE]"))
            await resp.write_eof()
        except _CLIENT_GONE:
            pass
        return resp

    # ---------------------------------------------------- server thread

    def _serve_thread(self):
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop

        async def completions(request):
            return await self._handle(request, "completions")

        async def chat(request):
            return await self._handle(request, "chat")

        async def models(_request):
            return web.json_response({
                "object": "list",
                "data": [{"id": m, "object": "model",
                          "owned_by": "ray_tpu"}
                         for m in sorted(self._models)]})

        async def healthz(_request):
            return web.Response(text="ok")

        async def snapshot(_request):
            return web.json_response(json.loads(
                json.dumps(self.stats(), default=str)))

        @web.middleware
        async def request_id_mw(request, handler):
            # every response — 2xx, 4xx/5xx error bodies, /v1/models,
            # healthz — carries X-Request-Id. The completion handlers
            # mint a route-prefixed id into request["req_id"]; anything
            # else (or an early rejection before the mint) gets a
            # req- fallback so clients can always quote an id back.
            try:
                resp = await handler(request)
            except web.HTTPException as e:
                rid = request.get("req_id") or \
                    f"req-{uuid.uuid4().hex[:24]}"
                e.headers.setdefault("X-Request-Id", rid)
                raise
            rid = request.get("req_id") or \
                f"req-{uuid.uuid4().hex[:24]}"
            # SSE StreamResponses set the header pre-prepare in the
            # handler; a prepared response's headers are on the wire
            if not getattr(resp, "prepared", False):
                resp.headers.setdefault("X-Request-Id", rid)
            return resp

        app = web.Application(client_max_size=64 * 1024 * 1024,
                              middlewares=[request_id_mw])
        app.router.add_post("/v1/completions", completions)
        app.router.add_post("/v1/chat/completions", chat)
        app.router.add_get("/v1/models", models)
        app.router.add_get("/-/healthz", healthz)
        app.router.add_get("/-/gateway", snapshot)

        async def run():
            runner = web.AppRunner(app)
            await runner.setup()
            port = self._port
            site = None
            for _attempt in range(20):  # skip ports already in use
                try:
                    site = web.TCPSite(runner, self._host, port)
                    await site.start()
                    break
                except OSError:
                    if port == 0:  # ephemeral bind cannot EADDRINUSE
                        raise
                    port += 1
                    site = None
            if site is None:
                raise RuntimeError("could not bind gateway port")
            if port == 0:
                port = site._server.sockets[0].getsockname()[1]
            self._bound_port = port
            self._ready.set()
            self.publish_telemetry(force=True)
            while not self._shutdown.is_set():
                await asyncio.sleep(0.2)
            await runner.cleanup()

        loop.run_until_complete(run())


__all__ = ["ByteCodec", "GatewayServer"]
