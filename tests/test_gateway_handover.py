"""The gateway's half of the hand-over (PR 64): a streamed request's
tokens cross from the engine's loop to the asyncio thread in ONE
`call_soon_threadsafe` a pass for all streams, its worker sleeps from the
first token to the end, and a disconnect or a deadline still frees the
slot within a tick. The engine here is SLOW by construction: a landing
waits for the test to let it through, so nothing sleeps and hopes."""
from __future__ import annotations

import dataclasses
import http.client
import json
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
from ray_tpu.serve.gateway import GatewayServer

pytestmark = pytest.mark.gateway

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                          max_seq_len=256)


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


class Ticks:
    """The engine's landings, let through by the test one at a time
    (`let`), or all (`run`)."""

    def __init__(self, engine):
        self.free = threading.Event()
        self.free.set()
        self.gate = threading.Semaphore(0)
        land = engine._land

        def gated(*a, **k):
            if not self.free.is_set():
                self.gate.acquire()
            return land(*a, **k)

        engine._land = gated

    def hold(self):
        self.free.clear()

    def let(self, n=1):
        for _ in range(n):
            self.gate.release()

    def run(self):
        self.free.set()
        self.let(64)


@pytest.fixture()
def stack(model):
    engine = ContinuousBatchingEngine(model, CFG, max_batch=8)
    ticks = Ticks(engine)
    router = DisaggRouter(colocated=engine, max_queue_depth=8)
    gw = GatewayServer(router, model="tiny", vocab_size=CFG.vocab_size,
                       max_tokens_cap=200)
    host, port = gw.ready()
    yield SimpleNamespace(engine=engine, router=router, gw=gw,
                          host=host, port=port, ticks=ticks)
    ticks.run()
    gw.stop()
    engine.stop()


def _post(s, body, headers=None, timeout=60.0):
    conn = http.client.HTTPConnection(s.host, s.port, timeout=timeout)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", "/v1/completions", json.dumps(body), hdrs)
    return conn, conn.getresponse()


def _frames(resp, stop_after=None):
    """The stream's JSON frames; ends at [DONE], at EOF, or after
    `stop_after` of them."""
    out = []
    while stop_after is None or len(out) < stop_after:
        line = resp.readline()
        if not line or line.strip() == b"data: [DONE]":
            break
        if line.startswith(b"data: "):
            out.append(json.loads(line[6:]))
    return out


def _text(frames):
    return "".join(f["choices"][0]["text"] for f in frames
                   if "choices" in f)


def _until(cond, what, timeout=20.0):
    """A bounded wait for something another THREAD does on its own
    (never for the engine to tick: the test lets its ticks through)."""
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, what
        time.sleep(0.005)


def test_eight_concurrent_streams_equal_their_bodies_one_call_a_pass(
        stack):
    prompts = [[3 + i, 1, 4, 1, 5] for i in range(8)]
    budgets = [24 + 3 * i for i in range(8)]
    bodies = []
    for p, n in zip(prompts, budgets):
        conn, resp = _post(stack, {"model": "tiny", "prompt": p,
                                   "max_tokens": n})
        assert resp.status == 200
        bodies.append(json.loads(resp.read())["choices"][0]["text"])
        conn.close()
    before = stack.engine.kv_stats()["handover"]
    # the non-streaming bodies went by their queues
    assert before == {"handed": 0, "handovers": 0,
                      "queued": sum(budgets)}
    # count the crossings on the asyncio loop itself
    loop = stack.gw._loop
    crossings = []
    call_soon = loop.call_soon_threadsafe

    def counted(cb, *args):
        crossings.append(cb)
        return call_soon(cb, *args)

    loop.call_soon_threadsafe = counted
    texts = [None] * 8

    def client(i):
        conn, resp = _post(stack, {"model": "tiny", "prompt": prompts[i],
                                   "max_tokens": budgets[i],
                                   "stream": True})
        assert resp.status == 200
        frames = _frames(resp)
        assert frames[-1]["choices"][0]["finish_reason"] == "length"
        texts[i] = _text(frames)
        conn.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
        assert not t.is_alive()
    loop.call_soon_threadsafe = call_soon
    assert texts == bodies
    after = stack.engine.kv_stats()["handover"]
    assert after["queued"] == before["queued"]
    assert after["handed"] == sum(budgets)
    # ONE crossing a sink call, and a sink call a pass: fewer than a
    # token each (eight streams decode side by side)
    deliveries = [cb for cb in crossings if cb == stack.gw._deliver]
    assert len(deliveries) == after["handovers"] < sum(budgets) // 2
    # what else crossed: each request's end, once
    assert len(crossings) - len(deliveries) == 8


def test_a_paced_stream_and_a_body_keep_the_queue(stack):
    conn, resp = _post(stack, {"model": "tiny", "prompt": [7, 7],
                               "max_tokens": 5, "stream": True,
                               "token_sleep_s": 0.001})
    assert resp.status == 200 and len(_frames(resp)) >= 2
    conn.close()
    assert stack.engine.kv_stats()["handover"] == {
        "handed": 0, "handovers": 0, "queued": 5}


def _open_stream(stack, headers=None, frames=3):
    """A stream of 150 tokens on the slow engine, read up to its
    `frames`-th frame: the prefill's token, then a tick a frame."""
    stack.ticks.hold()
    conn, resp = _post(stack, {"model": "tiny", "prompt": [3, 1],
                               "max_tokens": 150, "stream": True},
                       headers=headers)
    assert resp.status == 200
    got = _frames(resp, stop_after=1)
    for _ in range(frames - 1):
        stack.ticks.let()
        got += _frames(resp, stop_after=1)
    assert len(got) == frames
    assert stack.engine.free_slots == 7
    return conn, resp


def _freed_within_a_tick(stack, reason):
    """The shed reached the engine with NO tick let through; the next
    one frees the slot."""
    eng = stack.engine
    _until(lambda: eng._cancels == 1, f"no cancel for the {reason}")
    assert eng.free_slots == 7
    stack.ticks.let()
    _until(lambda: eng.free_slots == 8, "the slot was not freed")
    assert eng.kv_stats()["cancelled_by_reason"] == {reason: 1}
    assert stack.router.stats()["sheds_by_cause"].get(reason) == 1


def test_a_disconnect_mid_stream_frees_the_slot_within_a_tick(stack):
    conn, _resp = _open_stream(stack)
    conn.sock.shutdown(socket.SHUT_RDWR)
    conn.close()
    # the gateway's own watch of the transport finds the socket gone,
    # sets the cancel and WAKES the sleeping worker
    _freed_within_a_tick(stack, "disconnect")
    assert stack.gw.stats()["disconnects"] == 1


def test_a_deadline_mid_stream_frees_the_slot_within_a_tick(stack):
    t0 = time.monotonic()
    conn, resp = _open_stream(stack,
                              headers={"X-Request-Deadline": "1.5"})
    assert time.monotonic() - t0 < 1.5, "the deadline fell too early"
    # no token comes: the worker's TIMED wait ends at the deadline
    _freed_within_a_tick(stack, "deadline")
    assert time.monotonic() - t0 >= 1.5
    stack.ticks.run()
    tail = _frames(resp)
    assert tail and tail[-1]["error"]["code"] == "deadline"
    conn.close()
