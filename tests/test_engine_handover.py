"""A tick lands once (PR 64): a stream that was given a `StreamSink`
leaves by ONE call a pass for all of a consumer's streams, the stream's
end in the call that holds its last token; a stream without one keeps
its queue, token by token; and the copy of what `_land` reads starts at
the launch. Tier-1, CPU: what is counted and handed, never a time."""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.engine import ContinuousBatchingEngine, StreamSink
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.observability import requests as reqtrace
from ray_tpu.util import envknobs

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
PROMPTS = [list(range(1, 6 + i)) for i in range(3)]
BUDGETS = [6, 9, 12]


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def fresh_store():
    reqtrace._reset_store_for_tests()
    envknobs.clear()
    yield
    reqtrace._reset_store_for_tests()
    envknobs.clear()


@pytest.fixture()
def engine(model):
    eng = ContinuousBatchingEngine(model, CFG, max_batch=4)
    yield eng
    eng.stop()


class Consumer:
    """One consumer: its one callable, and what each call brought."""

    def __init__(self):
        self.calls = []
        self.on_call = None

    def __call__(self, batch):
        self.calls.append([(s, list(toks), ended)
                           for s, toks, ended in batch])
        if self.on_call is not None:
            self.on_call(batch)

    def of(self, stream):
        """The stream's tokens as the calls brought them, and the
        indices of the calls that marked its end."""
        toks, ends = [], []
        for i, call in enumerate(self.calls):
            for s, new, ended in call:
                if s is stream:
                    assert not ends, "a token behind the stream's end"
                    toks.extend(new)
                    if ended:
                        ends.append(i)
        return toks, ends


def _sleep_to_the_end(sink, stream, timeout=60.0):
    """What a stream's owner does: sleep on `wake`, look, sleep."""
    wakes = 0
    while not stream.ended:
        assert sink.wake.wait(timeout), "the engine never woke the owner"
        sink.wake.clear()
        wakes += 1
    return wakes


def _ring(eng):
    return [r for r in reqtrace.store().loop_records()
            if r["engine_id"] == eng.engine_id]


def test_one_call_a_pass_for_every_stream_of_a_consumer(model, engine):
    # the iterator's output from an engine of its own, so that every
    # record of `engine`'s ring is a pass of the streams below
    other = ContinuousBatchingEngine(model, CFG, max_batch=4)
    try:
        refs = [other.generate(p, n) for p, n in zip(PROMPTS, BUDGETS)]
        assert other.kv_stats()["handover"] == {
            "handed": 0, "handovers": 0, "queued": sum(BUDGETS)}
    finally:
        other.stop()
    passes_before = 0
    consumer = Consumer()
    sinks = [StreamSink(consumer, tag=i) for i in range(3)]
    streams = [engine.stream(p, n, sink=s)
               for p, n, s in zip(PROMPTS, BUDGETS, sinks)]
    wakes = [_sleep_to_the_end(s, st) for s, st in zip(sinks, streams)]
    # twice a stream at most: its first token, its end
    assert all(w <= 2 for w in wakes), wakes
    for i, (stream, ref) in enumerate(zip(streams, refs)):
        assert stream.tag == i
        toks, ends = consumer.of(stream)
        assert toks == ref == stream.tokens()
        # the end rides the call that holds the last token
        (end,) = ends
        last = [new for s, new, _ in consumer.calls[end] if s is stream]
        assert last == [[ref[-1]]]
        with pytest.raises(TypeError):
            next(stream)
    for call in consumer.calls:
        slots = [s._req.slot for s, _new, _ended in call]
        assert slots == sorted(set(slots)), "one entry a stream, by slot"
    # a pass with all three live hands all three over in ONE call
    assert any(len(call) == 3 for call in consumer.calls)
    stats = engine.kv_stats()["handover"]
    assert stats == {"handed": sum(BUDGETS),
                     "handovers": len(consumer.calls), "queued": 0}
    assert len(consumer.calls) < sum(BUDGETS)
    # the last pass writes its record after it has woken the owner
    until = time.monotonic() + 10.0
    while True:
        ring = _ring(engine)[passes_before:]
        if sum(r["handed"] for r in ring) >= sum(BUDGETS) \
                or time.monotonic() > until:
            break
        time.sleep(0.002)
    assert sum(r["handed"] for r in ring) == sum(BUDGETS)
    assert sum(r["handovers"] for r in ring) == len(consumer.calls)
    steady = [r for r in ring if r["live"] and not r["admissions"]]
    assert steady and all(
        r["handovers"] == 1
        and r["handed"] == r["live"] - r["discarded"] for r in steady)


def test_two_consumers_get_a_call_each(engine):
    a, b = Consumer(), Consumer()
    sinks = [StreamSink(a), StreamSink(b), StreamSink(a)]
    streams = [engine.stream(p, 8, sink=s)
               for p, s in zip(PROMPTS, sinks)]
    for s, st in zip(sinks, streams):
        _sleep_to_the_end(s, st)
    assert a.of(streams[0])[0] == streams[0].tokens()
    assert b.of(streams[1])[0] == streams[1].tokens()
    assert all(s is streams[1] for call in b.calls for s, _n, _e in call)
    assert any(len(call) == 2 for call in a.calls)
    assert engine.kv_stats()["handover"]["handovers"] == \
        len(a.calls) + len(b.calls)


def test_a_stream_without_a_sink_reads_its_queue_beside_them(engine):
    refs = [engine.generate(p, 10) for p in PROMPTS]
    consumer = Consumer()
    sinks = [StreamSink(consumer), StreamSink(consumer)]
    handed = [engine.stream(p, 10, sink=s)
              for p, s in zip(PROMPTS[:2], sinks)]
    plain = engine.stream(PROMPTS[2], 10)
    assert list(plain) == refs[2] == plain.tokens()
    assert plain.ended and plain.tag is None
    for s, st, ref in zip(sinks, handed, refs):
        _sleep_to_the_end(s, st)
        assert consumer.of(st)[0] == ref
    assert all(s is not plain for call in consumer.calls
               for s, _n, _e in call)
    assert engine.kv_stats()["handover"] == {
        "handed": 20, "handovers": len(consumer.calls), "queued": 40}


def _through_both(eng, prompt, n, **kw):
    """The iterator's output and the sink's for one request."""
    by_queue = list(eng.stream(prompt, n, **kw))
    consumer = Consumer()
    sink = StreamSink(consumer)
    stream = eng.stream(prompt, n, sink=sink, **kw)
    _sleep_to_the_end(sink, stream)
    toks, ends = consumer.of(stream)
    assert len(ends) == 1 and toks == stream.tokens()
    return by_queue, toks, consumer


@pytest.mark.parametrize("ending", ["max_new", "eos", "speculative"])
def test_the_sink_brings_what_the_iterator_yields(model, engine, ending):
    if ending == "speculative":
        # a verify landing accepts up to k = 2 drafts a stream: the
        # batch holds LISTS
        chain = engine.generate(PROMPTS[1], 12)
        full = PROMPTS[1] + chain
        eng = ContinuousBatchingEngine(
            model, CFG, max_batch=2, speculate_k=2,
            draft_source=lambda ctx, k: full[len(ctx):len(ctx) + k])
        try:
            by_queue, by_sink, consumer = _through_both(
                eng, PROMPTS[1], 12)
            assert eng.kv_stats()["spec_accepted"] > 0
        finally:
            eng.stop()
        assert by_queue == chain
        assert max(len(new) for call in consumer.calls
                   for _s, new, _e in call) > 1
    elif ending == "eos":
        chain = engine.generate(PROMPTS[1], 12)
        eos = chain[4]
        by_queue, by_sink, _ = _through_both(engine, PROMPTS[1], 12,
                                             eos_token=eos)
        assert by_queue == chain[:chain.index(eos) + 1]
    else:
        by_queue, by_sink, _ = _through_both(engine, PROMPTS[1], 12)
        assert len(by_queue) == 12
    assert by_sink == by_queue


def test_a_cancel_mid_stream_ends_the_stream_by_the_sink(engine):
    ref = engine.generate(PROMPTS[0], 40)
    consumer = Consumer()
    sink = StreamSink(consumer)
    seen = []

    def cancel_at_three(batch):
        # on the loop's thread: the cancel holds from the next pass's
        # top, and the row of the tick in flight is discarded
        for stream, toks, _ended in batch:
            seen.extend(toks)
            if len(seen) == 3:
                assert engine.cancel_slot(stream, "disconnect")

    consumer.on_call = cancel_at_three
    stream = engine.stream(PROMPTS[0], 40, sink=sink)
    _sleep_to_the_end(sink, stream)
    toks, (end,) = consumer.of(stream)
    assert toks == ref[:3] == stream.tokens()
    # the end alone, in a call of its own: no token was made for it
    assert [(new, ended) for s, new, ended in consumer.calls[end]
            if s is stream] == [([], True)]
    assert engine.kv_stats()["cancelled_by_reason"] == {"disconnect": 1}
    # the iterator's: what it read before the end is the same chain
    plain = engine.stream(PROMPTS[0], 40)
    got = [next(plain) for _ in range(3)]
    engine.cancel_slot(plain)
    got.extend(plain)
    assert got == ref[:len(got)] and len(got) < 40
    assert engine.free_slots == 4


def test_a_consumer_that_raises_does_not_stop_the_loop(engine):
    ref = engine.generate(PROMPTS[0], 6)

    def broken(batch):
        raise RuntimeError("the consumer's fault")

    sink = StreamSink(broken)
    stream = engine.stream(PROMPTS[0], 6, sink=sink)
    _sleep_to_the_end(sink, stream)
    assert stream.tokens() == ref
    assert engine.generate(PROMPTS[0], 6) == ref


def test_the_copy_to_the_host_starts_at_the_launch(engine, monkeypatch):
    """`_launch` starts the copy of everything `_land` reads, so the
    read-back is no transfer of its own: counted as `_now` is."""
    started, landed = [], []
    start_copy, land = engine_mod._start_copy, engine._land

    def counted_copy(x):
        started.append(x)
        start_copy(x)

    def watched_land(flight, it, *a, **k):
        # by now the launch has asked for all three kinds
        mine = [flight.nxt, flight.lp] + list(
            (flight.counts or {}).values() if it is not None else ())
        landed.append(all(any(x is y for y in started) for x in mine))
        return land(flight, it, *a, **k)

    monkeypatch.setattr(engine_mod, "_start_copy", counted_copy)
    monkeypatch.setattr(engine, "_land", watched_land)
    ticks = engine.ticks_launched
    done = threading.Event()
    out = []

    def run():
        out.append(engine.generate(PROMPTS[0], 8))
        done.set()

    threading.Thread(target=run, daemon=True).start()
    assert done.wait(60.0)
    launched = engine.ticks_launched - ticks
    assert launched >= 7 and len(out[0]) == 8
    # tokens and log-probabilities a tick; Llama's tick has no counters
    assert len(started) == 2 * launched
    assert landed and all(landed)


def test_a_ticks_counters_are_copied_with_its_tokens(monkeypatch):
    """A family whose tick hands counters back (Granite's expert
    layers): their copy starts at the launch too, with the recorder on,
    which is when `_land` reads them."""
    from ray_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig.tiny()
    params = gh.granite_hybrid_init(cfg, jax.random.PRNGKey(0))
    started = []
    start_copy = engine_mod._start_copy
    monkeypatch.setattr(
        engine_mod, "_start_copy",
        lambda x: (started.append(x), start_copy(x))[1])
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2)
    try:
        assert len(eng.generate(list(range(1, 9)), 4)) == 4
        launched = eng.ticks_launched
    finally:
        eng.stop()
    ticks = [r for r in _ring(eng) if "moe_pairs_held" in r]
    assert ticks
    counters = len([k for k in ticks[0] if k.startswith("moe_")])
    assert counters >= 2
    assert len(started) == (2 + counters) * launched
