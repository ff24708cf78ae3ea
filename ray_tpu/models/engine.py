"""Continuous-batching generation engine — the LLM serving throughput
story (BASELINE "Llama JAX replica, batched inference"; the reference
serves torch models and leaves batching to the replica, Serve's @batch
being request-level — this is TOKEN-level continuous batching in the
vLLM sense, rebuilt TPU-first).

Design: one fixed-shape decode loop over `max_batch` slots. Every tick
runs ONE jitted ragged-batch step (the model family's per-slot decode —
llama_decode / gpt2_decode — with per-slot positions and masking,
static shapes throughout, so XLA compiles exactly one program no
matter how requests interleave). New requests prefill into a
free slot (one jitted prefill per distinct (cached-prefix, suffix)
length pair — exact lengths, so cache rows beyond a slot's own depth
are never attended) and JOIN the running batch between ticks; finished
sequences (EOS or their token budget) free their slot between ticks
(the loop keeps one tick queued on the chip meanwhile: below).
Slots the engine isn't using decode garbage that nothing reads — the
cost of static shapes, paid once, instead of a recompile per batch
composition.

Prefill rides the paged KV prefix cache (models/kvcache.py): admission
looks up the longest cached block-aligned prefix of the prompt, gathers
those blocks from the pool, and prefills ONLY the suffix; the filled
prompt region is then SPLICED into the slot's rows of the decode slab —
an O(prompt_len) in-place update, not the O(max_batch x max_len)
full-cache copy the old `_adopt_slot` paid per admission. Admissions
between ticks are capped at ``RAY_TPU_MAX_PREFILLS_PER_TICK`` (default
1) so a burst of arrivals cannot head-of-line-block every in-flight
decode for the whole drain.

Disaggregated serving (serve/disagg.py) splits the two phases across
replicas: a decode replica's engine never prefills at all — it ADOPTS a
prompt's already-computed KV rows via ``adopt_prefill()``, which splices
them into a free slot between ticks through the same `_splice_slot`
program (O(prompt_len), never a full-cache copy) and emits the
prefill-produced first token. Adoption is its own admission phase with
its own per-tick cap (``RAY_TPU_MAX_ADOPTIONS_PER_TICK``, default 4 —
splices are cheap relative to prefills) and its own counters
(``adopted`` / ``max_adoptions_admitted_per_tick`` vs
``prefill_admitted`` / ``max_prefills_admitted_per_tick``), so the
kvcache CLI/dashboard numbers stay truthful in both modes.

Speculative decoding (``speculate_k`` / ``RAY_TPU_SPECULATE_K``): the
tick loop above is one-token-per-step per slot — serving throughput
pinned to sequential forward passes even though the verify side is
embarrassingly batchable. With speculation on, a PROMPT-LOOKUP
proposer drafts up to k tokens per slot between ticks (no draft model,
no extra compile): first from the paged prefix index's exact token
chains (``PagedKVCache.propose`` — drafting from cache is nearly
free), then from the most recent match of the slot's own trailing
n-gram in its context. The engine then verifies all k in ONE batched
forward — ``_tick`` is shape-polymorphic from seqlen-1 to seqlen-(k+1)
per slot (the model families' ``*_decode`` take tokens [B] or
[B, k+1] with per-slot base positions) — and accepts the longest
prefix of the draft that agrees with the greedy argmax chain. Greedy
bit-identity to the unspeculated engine is the correctness oracle: an
accepted token IS the token sequential decode would have produced, and
a rejected draft's KV rows need no copy-back — per-position masking
keeps them invisible until the real decode overwrites them, and the
only pooled state drafting touches is read-only (proposals pin
nothing; the request's block refcounts alone govern pool reclamation,
so rejection rolls back by refcount, never by copy).
Surfaces: ``util.state.speculation_stats()``, ``ray_tpu speculate``,
``/api/speculation``, lazy Prometheus
(``ray_tpu_spec_proposed_total`` / ``_accepted_total`` /
``ray_tpu_spec_acceptance_rate``), and spec_accept / spec_reject
instant markers in the merged timeline's kvcache lane.

The cache protocol is one for every family, and `models/family.py`
holds it: the family's record (`family_of`), the four kinds of entry its
cache may be made of (keys and values in pairs, a latent row alone, a
RING shorter than ``config.max_seq_len``, a slot's STATE with no sequence
axis), the slab's layout learnt from its shapes and never from a family's
name (`slab_spec`), and what each kind is refused in words (`refuse`: a
prefix pool, speculation, a ``lora_pool``, ``adopt_prefill``, the
transfer between replicas; an option left to its default asks for
nothing, so such a family simply gets no pool and prefills every prompt
from position 0). What the engine does with them: a prefill hands the
sequence entries back stacked `[L_kv, S, H, hd]` (what the paged pool
commits, a disaggregated transfer ships and `_splice_slot` writes by rows
`[0, plen)`), with `cv` None where a latent row serves for keys and
values both, ONE STACK FOR EACH (ROW COUNT, ROW SHAPE) where the slab
holds a ring or entries of several widths (`family.stacks`: `ck`, `cv`
are then tuples, in the order the slab first shows each; a family with
one count and one width gets the one stack it always got), a
ring's stack as the ring would lie after the prompt (the last `min(plen,
rows)` positions, each at `p mod rows`); and the state it ended in, which
the splice writes WHOLE. `_splice_slot` writes rows `[0, min(plen,
rows))` of each entry, O(rows held) and in place; a dead slot's position
stands still at 0 (`_chosen`, `_finish`), so its ring row does too, at
row 0; the loop's record of a tick gains ``live_rows_window`` (the sum
over the live slots of `min(position, rows)` for the shortest row count:
what of the rings the tick had a reason to read) and
``kv_stats()["slab"]`` says, for each row count, the layers that hold it
and the bytes a slot costs.

The loop keeps one tick ahead. The token vector and the position vector
of the decode step live on the chip: `_tick` hands back, beside the
cache, the chosen tokens and the positions advanced, which are, where
they lie, the next tick's inputs. A steady pass of ``_loop`` launches
tick N+1 from the outputs of tick N, which is still in flight, and only
then blocks on tick N's tokens, walks the slots, emits, and at the top
of the next pass applies swaps and cancels and admits: the host's whole
pass runs while the chip computes, and nothing is uploaded. What the
host learns a tick late is put right at the next boundary, in program
order. A request whose budget ends with the tick in flight is known
before the launch and is not decoded for again. One that ends by EOS or
by a cancel has a row in the tick already launched: that token is
DISCARDED on the host (never emitted, scored or drafted from), and the
slot is free after the walk as ever. The rows of which the host knows
better (a slot admitted or adopted: its first token and its prompt's
length; a slot that finished: dead) are written into the device vectors
by one small program (`_set_rows`) queued behind the tick in flight and
ahead of the next launch, as the splice of an admission is, so the
host's word wins; a splice writes a slot's state whole, so nothing of
a discarded step survives in a family that has state, and for keys and
values the stale row lies past the new prompt and stays masked until
overwritten. A dead slot is HELD inside the program (`_chosen`: its
token comes back as it went in, its position stands still), so the
scatter and the position lookup of every family see for it what they
saw when the host uploaded its mirror every tick, and no position runs
past the window. It stands still AT 0: `_finish` parks the host's
mirror there, and the `_set_rows` that tells the chip of the death
carries the position with it. The tick's attention walks each slot's
rows up to its position (`ops/swa.decode_attention` over keys and
values, `ops/mla.absorbed_attention` over latent rows), so a slot left
where its last request ended would have that request's rows read tick
after tick for nobody; parked, it costs one block, and its scatter lands
in row 0, which the next `_splice_slot` overwrites (a ring's row 0
likewise). And it costs NO state where the family's state step walks
(`Family.state_walks`: `ops/mamba2.ssd_step`, `ops/kda.kda_step`): the
step is handed the device's liveness vector, the one `_set_rows` writes
and `_chosen` reads, visits the slots it holds live and neither reads
nor writes a dead slot's state, which the next `_splice_slot` writes
whole (a slot whose budget ends with the tick ahead is still live on the
chip for one step more, as its row is still decoded). A family whose
step does not walk steps every slot's state, tick after tick, for
nobody. A weight swap holds from the next LAUNCH: the tick in
flight finishes on the weights it was launched with. The depth is what
the engine can see, not a knob: a pass that carries drafts needs the
host's tokens to draft from, so a speculating engine reads the tick in
flight first and runs the verify tick with nothing queued behind it;
every other pass keeps one tick queued unless no slot's budget outlives
the tick it reads, with one exception: a prompt longer than half the
window is not prefilled behind the tick in flight
(`_long_prompt_waits`). Its prefill would hold that tick's tokens, ready
within a tick, for as long as it runs, so the loop reads the tick and
emits first, admits with nothing on the chip as it did before the
lookahead, launches the next tick and ends the pass without reading it;
the pass after keeps one queued again. ``kv_stats()`` counts
``lookahead_ticks`` (launched behind a tick in flight) and
``lookahead_discarded``.

The loop keeps a clock of its own. While the per-request flight
recorder is on (``RAY_TPU_REQTRACE``, observability/requests.py), every
pass of ``_loop`` that read a tick back or admitted something leaves ONE
record (a speculating pass that reads the tick in flight before it
drafts leaves one there and one at its end: a record holds one landing)
in the recorder's process-local store
(``reqtrace.store().loop_records()``; a bounded ring, nothing is pushed
to the conductor), all from ``time.perf_counter()`` on this thread:
``engine_id``; ``pass`` (the engine's count of recorded passes: this
record's number); ``ts`` (``time.time()`` at the top of the pass);
``max_batch``; ``pending`` (requests waiting in ``_pending`` at the
top); ``admit_ms`` (inside ``_admit``; 0 where nothing was admitted or
adopted); ``admissions``,
one entry per request admitted in the pass (``rid``, ``prompt_tokens``,
``suffix_tokens``, ``reused_tokens`` and the self times ``lookup_ms``
(lookup + gather), ``prefill_ms`` (the ``_prefill_paged`` call and the
read-back of its logits, the commit between them taken out; the prefill
queues behind the tick in flight, whose rest it therefore holds),
``commit_ms`` with ``commit_dispatches`` (programs the pool commit
launched: its one program once for the keys' pool and once for the
values', whatever the blocks; 0 where nothing was new) and
``commit_blocks``, ``splice_ms``, for a family with state
``state_bytes``, what the splice wrote whole, and where the family's
``forward_cached`` has a form that counts the run, its counters under their own
names (models/deepseek_v2.py: ``moe_pairs_held`` and ``moe_rows_max``,
what the grouped products saw over the prompt, and ``attn_blocks``, the
blocks of scores the prompt form computed; models/smallthinker.py also
``attn_blocks_causal``, what a causal walk with no window would have
visited; models/jamba.py ``scan_blocks``, the blocks of tokens the
selective scan walked over all Mamba layers; ``kv_stats()`` holds their
totals as ``prefill_counters``); an
adoption has ``prefill_ms`` 0);
``dispatch_ms`` (inside ``_launch``, every launch of the pass: the
lookahead's dispatch, before it ``_set_rows`` in a pass that follows an
admission or a finish, and the tick's own where nothing was in flight;
NO uploads in a steady pass; 0 in a pass that launches nothing because
no budget outlives the tick it reads; the speculative tick uploads its
tokens as ever, its drafting is bookkeeping); ``readback_ms`` (the wait
for the tokens and log-probabilities of the tick launched a pass
earlier, whose copy to the host that launch began: BLOCKED on the
device, so not host work; most of a tick where the chip bounds the pass,
next to nothing where the host does, since the tick had ended and its
tokens were on the host before they were asked for); ``emit_ms`` (the
walk over that tick's slots, emit, finish, queue puts, discards, and the
pass's hand-over: the sink calls); ``handed`` and ``handovers`` (tokens
that left by a sink in the pass, and the sink calls that took them: 1
where every stream is one consumer's; "A tick lands once" below);
``inflight``
(ticks queued on the chip while the pass was blocked on its read-back:
1 in a steady pass, 0 in a pass with drafts, with nothing left to
decode for, or that admits a long prompt) and ``discarded`` (rows of
the tick read thrown away because their request had finished by EOS or
been cancelled since the launch); ``total_ms`` (the whole pass; what
the parts leave is bookkeeping: swap, cancels, drafting, telemetry
push). ``live`` (the
slots the tick decoded for), ``live_rows`` (the sum of their positions:
the cache rows the tick had a reason to read), ``slab_rows_read`` (the
rows one layer's walk over the slab's longest entries visits, ALL slots
at the positions the tick was launched with: whole blocks, a dead slot's
one block, by the function the kernels' walks use,
`ops/swa.decode_rows_read`, for keys and values and for latent rows
alike; ``max_batch`` x rows for a family whose tick reads every row or
the rows a selection marks, `dots3_note`:
`Family.decode_walks`), for a family with state
``state_slots_stepped`` (the slot-states ONE layer's state step visits:
the slots the chip held live at the launch, counted on the host from
what `_set_rows` last wrote, where the step walks them,
`Family.state_walks`; ``max_batch`` for a program that steps every
slot's), where the slab holds a
ring ``live_rows_window`` (above) and, where the family's
decode hands back counters of the step, those under their own names
(``moe_pairs_held``, token-expert pairs that fell on experts held here,
summed over the expert layers, ``moe_rows_max``, the most rows one held
expert got, and where the family counts them ``moe_experts_hit``, the
held experts that got a row, summed likewise) all describe ONE tick: the
one the pass READ BACK, as the host knew it at its launch (a row
discarded later is still among ``live``). A request
carries three stamps of the same clock (``submit()`` returns, ``_admit``
pops it, ``_emit`` puts its first token) and ``TokenStream`` exposes
their differences as ``queue_ms`` and ``prefill_ms``, which the router
hands to the flight recorder as the two parts of
``decode_first_token``. The same boundaries are profiler spans
(``jax.profiler.TraceAnnotation``) on this thread (``engine.admit``,
``engine.prefill``, ``engine.pool_commit``, ``engine.splice``,
``engine.tick_dispatch``, ``engine.tick_readback``, ``engine.emit``),
so a ``jax.profiler`` session shows them beside the device's programs;
each carries ``pass``, the number its pass's record takes in the ring
(the engine's count of recorded passes), and ``rid`` where it serves one
request: the spans are the ring's picture in the profiler, the ring is
what is read.
With the recorder off the loop reads no clock and builds no record.

A tick lands once. Between a tick's end on the chip and its tokens in
the hands of the thread that frames them lie a copy and a crossing of
threads, and the loop pays each once a pass. The COPY of what `_land`
reads (the tokens, their log-probabilities and, with the recorder on,
the family's counters) starts at the launch, right behind the tick
(`_launch`: ``copy_to_host_async``; no program changes): a host-bound
pass finds the data on the host a pass later, a device-bound one saves
the round trip after the tick. The tokens are the next tick's input too
and are not donated, so the copy and that tick both read them. An
admission's prefill is read in the breath it is launched in and is left
as it was. The CROSSING: a consumer may hand ``stream()`` a `StreamSink`.
EVERY token of such a stream, the prefill's first too, then leaves by
the pass's hand-over and none by the queue, so that one thread alone
orders them: `_emit` appends to the pass's batch, and after the walk
(`_land`, `_spec_emit`'s; at once after an admission's first `_emit`
and a cancel's `_finish`) `_hand_over` calls each DISTINCT sink ONCE
with ``[(stream, [tokens...], ended), ...]`` in the order the walk met
the slots, a stream's end in the call that holds its last token (alone,
with no token, where a cancel ended it). No token is held back to fill
a batch. The stream's OWNER sleeps on the sink's ``wake``, which the
loop sets once the first token and once the end have been handed over,
and reads ``TokenStream.produced``, ``ended`` and at the end
``tokens()``, the engine's own history (`ctx`). A request without a sink
keeps its queue, token by token: the plain iterator is untouched. Two
paths that share the walk and differ in where a token is put, chosen by
what the code can see (a sink was registered), not by a knob.
``kv_stats()["handover"]`` counts ``handed``, ``handovers`` and
``queued`` (tokens that went by a queue).

The loop keeps a ledger of the gaps it makes (`_GapLedger`, PR 35). A
tick's tokens go out when its read-back returns (a LANDING); the time
from one landing to the next is the gap every stream that took a token
from both ticks sees between them. The record of a pass that landed a
tick carries what that gap held, all from the stamps above and two more
(around the read of a prefill's logits): ``gap_ms`` (this landing less
the one before; ABSENT where no request took a token from both ticks:
the engine stood idle, or every stream is new); ``gap_streams`` (the
requests that took a token from both: a slot that joined, one that
finished and a row discarded count in neither, so a reader weighs a gap
once a stream that felt it, as a client's percentile does; a request's
own first gap, from its prefill's token to its first tick's, is in no
count); ``gap_admissions`` (the admissions, adoptions among them, whose
programs were launched between the two landings: an accumulator emptied
at each landing, so an admission is counted in the gap that ENDS with
the first landing after its launch, wherever the loop puts ``_admit``;
their prompts' lengths are in ``admissions``);
``gap_blocked_ms`` (of the gap, the time this thread was blocked on a
chip at work: the tick's read-back, a prefill's logits); and
``gap_empty_ms`` with ``gap_empty_by``, the time the chip was STARVED
and the host's step that ran meanwhile. The chip runs its programs in
the order they were launched, and the ledger counts the launches at the
sites this thread has, the implicit ones too (the slice of a prefill's
logits is a program; the pool's gather and its commit's; `_splice_slot`,
`_set_rows`; reading a prefill's counters launches nothing, they are
there once its logits are). When a blocking read-back returns and the
program it read is the newest launched, the chip is empty from that
return, to within a launch's latency, and it does none of the streams'
or a prompt's work until the next ``_tick`` or ``_prefill_paged`` is
launched: the stretch ends at that launch's return, and what the chip
runs between (`_splice_slot`, `_set_rows`: well under a millisecond
together) counts as starved, it IS the admission's chain. The steps,
under the span's name where there is one: ``emit`` (the walk over a
tick read with nothing queued behind it), ``lookup`` (a second
admission of a pass, or one after a hold), ``prefill`` (the launch of
such an admission's ``_prefill_paged``), ``first_token`` (argmax and
log-sum over the vocabulary, the counters' read, the first ``_emit``),
``splice``, ``tick_dispatch`` (with its ``_set_rows``) and
``bookkeeping`` for what has no span. The parts need not sum to the gap
(the rest is the host's work under a busy chip);
``gap_blocked_ms + gap_empty_ms <= gap_ms`` holds. A speculative verify
tick is one landing like any other. An entry of ``admissions`` carries
``prefills_waited``: ``prefill_admitted`` at the pop in ``_admit`` less
its value at ``submit()``, the other requests' prefills that ran while
this one waited (``TokenStream.prefills_waited``, which the router puts
on the request's ``decode_first_token`` phase beside the two parts;
counted with the recorder off too). ``kv_stats()["gaps"]`` holds the
totals an operator reads without a benchmark, over the gaps that have a
``gap_ms``: ``stream_gaps`` (the sum of ``gap_streams``),
``with_admission`` (those of them whose gap held an admission),
``chip_blocked_ms`` and ``chip_empty_ms`` by step. Their rates over the
wall's time say what bounds the decode: blocked near 1, the chip; empty
above a few hundredths, the host's chain at admissions; the rest is the
host's work under a busy chip. All zero while the recorder is off.

Per-request token queues make it the natural producer for Serve's
streaming path (the gateway's streams take the hand-over above
instead); `ContinuousBatchingEngine` is thread-safe for
concurrent submit/iterate from replica request threads. The streamed
iterator exposes ``cache_outcome`` (hit|partial|miss) so the replica's
TTFT histogram can label prefix-cache wins.
"""
from __future__ import annotations

import functools
import itertools
import os
import queue
import threading
import time
from collections import OrderedDict
from typing import (Any, Callable, Dict, Iterator, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.observability import requests as reqtrace
from ray_tpu.ops import dispatch
from ray_tpu.ops.swa import decode_block, decode_rows_read
from ray_tpu.util.profiling import name_thread
from ray_tpu.util.telemetry import Pusher

from .family import family_of, refuse, slab_spec, stacks
from .generate import merge_lora_params
from .kvcache import PagedKVCache, resolve_pool_config

_DONE = object()
_ENGINE_SEQ = itertools.count()
_SPEC_EVENTS_KEPT = 512
# the loop's one clock; tests swap it to count the reads
_now = time.perf_counter


def _start_copy(x: jax.Array) -> None:
    """Begin `x`'s copy to the host and return; tests swap it to count
    the copies a launch starts."""
    x.copy_to_host_async()


def _clock(rec: Optional[dict]) -> float:
    """A reading for the loop record `rec`; none is taken without one."""
    return _now() if rec is not None else 0.0


class _GapLedger:
    """What the loop keeps between two landings of a tick (module
    docstring, "a ledger of the gaps"). One an engine, touched by its
    loop's thread alone; `kv_stats()` reads the totals from any thread.
    Counting launches reads no clock, and nothing else here runs until
    `read` has been called, which the loop does with the recorder on."""

    def __init__(self) -> None:
        self.passes = 0      # passes recorded in the ring so far
        self.launches = 0    # programs this thread has sent to the chip
        self.done = 0        # the newest a read-back has shown finished
        # since when the chip has been starved, and the host's step now
        self.empty_from: Optional[float] = None
        self.step = "bookkeeping"
        # the last landing, and the number of landings so far (a
        # request keeps the number of the last it took a token from)
        self.t_land: Optional[float] = None
        self.landing = 0
        # emptied at each landing
        self.admissions = 0
        self.blocked_s = 0.0
        self.empty_s: Dict[str, float] = {}
        # nothing read or admitted since `idle` emptied them
        self.clean = True
        # kv_stats()["gaps"]
        self.stream_gaps = 0
        self.with_admission = 0
        self.chip_blocked_ms = 0.0
        self.chip_empty_ms: Dict[str, float] = {}

    def _charge(self, t: float) -> None:
        self.empty_s[self.step] = (self.empty_s.get(self.step, 0.0)
                                   + t - self.empty_from)
        self.empty_from = t

    def launched(self, n: int = 1, work: bool = False) -> None:
        """`n` programs went to the chip. `work`: the last of them is a
        `_tick` or a `_prefill_paged`, whose launch's return ends a
        starved stretch."""
        self.launches += n
        if work and self.empty_from is not None:
            self._charge(_now())
            self.empty_from = None

    def mark(self, step: str, t: Optional[float] = None) -> None:
        """The host's step from here on (`t`: a reading of now, where
        the caller has one). It matters while the chip is starved
        alone (`read` names the step a starved stretch begins in), so
        the two callers a steady pass goes through ask first."""
        if self.empty_from is not None:
            self._charge(_now() if t is None else t)
        self.step = step

    def read(self, t0: float, t1: float, seq: int, step: str) -> None:
        """A blocking read-back of what launch number `seq` made took
        from `t0` to `t1`, and the host goes on to `step`. It shows
        every launch up to `seq` finished: where that is the newest,
        the chip is empty from `t1`. A read with the chip already empty
        is not time blocked on its work."""
        self.clean = False
        if self.empty_from is not None:
            self._charge(t1)
        else:
            self.blocked_s += t1 - t0
            if seq > self.done:
                self.done = seq
            if self.done == self.launches:
                self.empty_from = t1
        self.step = step

    def admitted(self) -> None:
        self.clean = False
        self.admissions += 1

    def land(self, t1: float, streams: int, it: Dict[str, Any]) -> None:
        """Close the gap that ends with the landing at `t1`, which
        `streams` requests felt (they took a token from landing number
        `landing` too: `_land`'s walk), into the pass's record. Starved
        time is charged at the next `mark` or launch, and the walk over
        the tick's slots makes neither: what the accumulators hold here
        lies before `t1`. A steady pass finds them empty."""
        by: Dict[str, float] = {}
        empty_ms = 0.0
        if self.empty_s:
            by = {k: v * 1e3 for k, v in self.empty_s.items()}
            empty_ms = sum(by.values())
            self.empty_s = {}
        it["gap_streams"] = streams
        it["gap_admissions"] = self.admissions
        it["gap_blocked_ms"] = self.blocked_s * 1e3
        it["gap_empty_ms"] = empty_ms
        it["gap_empty_by"] = by
        if streams and self.t_land is not None:
            it["gap_ms"] = (t1 - self.t_land) * 1e3
            self.stream_gaps += streams
            if self.admissions:
                self.with_admission += streams
            self.chip_blocked_ms += it["gap_blocked_ms"]
            for k, v in by.items():
                self.chip_empty_ms[k] = self.chip_empty_ms.get(k, 0.0) + v
        self.t_land = t1
        self.landing += 1
        self.admissions = 0
        self.blocked_s = 0.0

    def idle(self) -> None:
        """Nothing decodes (or the recorder is off): the next landing
        ends no gap, and an idle chip is not a starved one."""
        if self.clean:
            return
        self.clean = True
        self.empty_from = self.t_land = None
        self.admissions = 0
        self.blocked_s = 0.0
        self.empty_s = {}

    def totals(self) -> Dict[str, Any]:
        return {"stream_gaps": self.stream_gaps,
                "with_admission": self.with_admission,
                "chip_blocked_ms": self.chip_blocked_ms,
                "chip_empty_ms": dict(self.chip_empty_ms)}


class _NoLedger(_GapLedger):
    """The prefill tier's, which has no loop and no landing: it counts
    nothing, and its spans take no `pass`."""

    def launched(self, n: int = 1, work: bool = False) -> None:
        pass

    def mark(self, step: str, t: Optional[float] = None) -> None:
        pass


_NO_LEDGER = _NoLedger()


def _span(name: str, gaps: _GapLedger, **stats: Any):
    """An `engine.*` span of the pass under way, the one way this
    module opens one: `pass` is the number its record takes in the ring.
    (What `util.profiling.annotate` is, less a frame and an import a
    span: a steady pass opens four, and its Python runs cold.)"""
    if gaps is not _NO_LEDGER:
        stats["pass"] = gaps.passes
    return jax.profiler.TraceAnnotation(name, **stats)


def default_speculate_k() -> int:
    """The ``RAY_TPU_SPECULATE_K`` env default (0 = speculation off)
    every engine owner resolves through."""
    from ray_tpu.util import envknobs

    return max(0, envknobs.get_int("RAY_TPU_SPECULATE_K", 0))


# ----------------------------------------------------- prometheus (lazy)
# Created on first speculating engine, never at import (the kvcache /
# lora pattern — rebound ONCE to a complete dict).

_spec_metrics: Optional[Dict[str, Any]] = None
_spec_metrics_lock = threading.Lock()


def spec_metrics() -> Dict[str, Any]:
    global _spec_metrics
    m = _spec_metrics
    if m is not None:
        return m
    with _spec_metrics_lock:
        if _spec_metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _spec_metrics = dict(
                proposed=Counter(
                    "ray_tpu_spec_proposed_total",
                    "draft tokens proposed to the verify pass"),
                accepted=Counter(
                    "ray_tpu_spec_accepted_total",
                    "draft tokens accepted (greedy-agreeing prefix)"),
                acceptance_rate=Gauge(
                    "ray_tpu_spec_acceptance_rate",
                    "lifetime accepted/proposed draft-token ratio per "
                    "engine (counters are process-global; the gauge is "
                    "engine-tagged so co-resident engines can't "
                    "last-writer-wins each other)",
                    tag_keys=("engine",)))
    return _spec_metrics


@functools.partial(jax.jit, static_argnums=(2,))
def _prefill_paged(params, suffix, config, prefix_k, prefix_v, lora=None):
    """Prefill a single sequence's SUFFIX on top of a cached prefix
    ([L, c, H, hd]; c=0 is the full-prefill program, and the only one a
    family with state has). The cache is the full max_seq_len slab and
    the family's `forward_cached` is generate()'s prefill, so an engine
    and generate() run one program over a prompt. A cached prefix and
    none need NOT share reduction shapes: a family may attend over the
    run alone at c=0 and over the slab on top of a prefix
    (`models/llama.py` for a prompt over one block of its prompt form),
    so a replay through the prefix cache can let a near-tie fall the
    other way. One compile per distinct (cached, suffix) length pair.

    The family's single-sequence cache (`init_cache(config, 1)`) is laid
    out with the cached prefix in the rows of its key-value entries, the
    family's `forward_cached` fills it from position c on, and it comes
    back as the engine passes a sequence around: (last logits, ck, cv,
    state, counters). `ck`, `cv`: the key-value entries stacked [L_kv, S,
    H, hd] (what the paged pool, the splice and the transfer between
    replicas speak); a family whose sequence entries hold one array (a
    latent row: "k" alone) gets `cv` None, and `prefix_v` is not read; a
    family whose sequence entries have MORE THAN ONE row count (a ring
    beside full-length entries) or width (an indexer's narrow keys
    beside latent rows) gets `ck` and `cv` as tuples, one stack a (row
    count, row shape) in the order the cache first shows each
    (`family.stacks`), each at its own width, a stack's `cv` None where
    ITS entries hold no values (`SlabSpec.paired_stacks`: an indexer's
    keys beside keys and values in pairs), and has no cached prefix to
    lay out. `state`:
    the entries that have no sequence axis, each as the family left it
    (the empty list for a family that has none). `counters`: those of
    the run where the family's record has a `forward_counted` (a dict of
    small numbers, models/deepseek_v2.py), else None.

    `lora`: ONE tenant's adapter, its low-rank deltas merged into the
    target leaves INSIDE the jit (prefill is per-request single-tenant,
    so the merged weights never persist; only the decode tick pays the
    scatter-gathered per-slot form). One more compile per rank."""
    if lora is not None:
        params = merge_lora_params(params, config, lora)
    family, spec = family_of(config), slab_spec(config, 1)
    c = prefix_k.shape[1]
    cache = list(family.init_cache(config, 1))
    by_shape, pairs = spec.stacks, spec.paired_stacks
    if c and (spec.stateful or len(by_shape) > 1):
        raise ValueError(
            "a cached prefix cannot resume a recurrent state or a ring: "
            "this family prefills every prompt from position 0")
    for ((rows, *row_shape), at), paired in zip(by_shape.items(), pairs):
        base_k = jnp.zeros((len(at), rows, *row_shape), prefix_k.dtype)
        base_v = jnp.zeros_like(base_k) if paired else None
        if c:
            base_k = base_k.at[:, :c].set(prefix_k)
            if paired:
                base_v = base_v.at[:, :c].set(prefix_v)
        for j, i in enumerate(at):
            cache[i] = {"k": base_k[j][None]}
            if paired:
                cache[i]["v"] = base_v[j][None]
    if family.forward_counted is None:
        (logits, cache), counts = family.forward_cached(
            params, suffix, config, cache, c), None
    else:
        logits, cache, counts = family.forward_counted(
            params, suffix, config, cache, c)
    ck = tuple(jnp.stack([cache[i]["k"][0] for i in at])
               for at in by_shape.values())
    cv = tuple(jnp.stack([cache[i]["v"][0] for i in at]) if paired else None
               for at, paired in zip(by_shape.values(), pairs))
    if len(by_shape) == 1:    # the one stack every consumer speaks
        ck, cv = ck[0], cv[0]
    state = [blk for blk in cache if "k" not in blk]
    return logits[:, -1], ck, cv, state, counts


def _prefill_with_cache(params, config, kv_cache, prompt, empty_prefix,
                        event_extra=None, adapter=None, namespace=None,
                        parts=None, gaps=_NO_LEDGER):
    """The prefill-behind-the-prefix-cache sequence shared by the
    colocated engine's `_admit_one` and the disagg `PrefillServer`:
    lookup → gather → `_prefill_paged` on the suffix → commit +
    prefix_hit event → greedy first token + its logprob score. ONE
    implementation keeps the two paths bit-identical (the disagg
    equivalence tests depend on it). Returns `(ck, cv, state,
    block_table, first, score, outcome, reused, suffix_len, counters)`
    (`state` and `counters`, a dict of ints or None: `_prefill_paged`);
    the caller owns the returned pins (empty list when no cache).

    `adapter`/`namespace` (multi-tenant LoRA, serve/lora.py): prefill
    under one tenant's adapter slice, with the prefix cache keyed by
    (namespace, prompt) so one tenant's KV can never match
    another's.

    `parts`: a dict the engine's loop record wants filled with this
    admission's self times and commit counts (module docstring); None
    reads no clock. The profiler spans are there either way.

    `gaps`: the engine's `_GapLedger`, which counts the programs
    launched here, takes the read of the logits (with `parts`) and gives
    the spans their `pass`; the prefill tier has none."""
    plen = prompt.shape[1]
    prompt_np = prompt[0]
    rid = (event_extra or {}).get("rid", -1)
    outcome, reused = "miss", 0
    t0 = _clock(parts)
    gaps.mark("lookup", t0)
    if kv_cache is not None:
        match = kv_cache.lookup(prompt_np, max_tokens=plen - 1,
                                namespace=namespace)
        outcome, reused = match.outcome, match.tokens
        prefix_k, prefix_v = kv_cache.gather(match)
        gaps.launched(int(match.tokens > 0))
    else:
        match = None
        prefix_k = prefix_v = empty_prefix
    cached = int(prefix_k.shape[1])
    suffix = prompt[:, cached:]
    t1 = t2 = t3 = _clock(parts)
    gaps.mark("prefill", t1)
    # engine.prefill runs to the read-back of the logits, the commit
    # nested in it: the device works on the prefill while the host
    # plans the commit and queues its writes behind it, so the span's
    # self time is the prefill's
    with _span("engine.prefill", gaps, rid=rid, prompt_tokens=plen):
        last_logits, ck, cv, state, counts = _prefill_paged(
            params, suffix, config, prefix_k, prefix_v, adapter)
        gaps.launched(work=True)
        table: List[Any] = []
        if kv_cache is not None:
            kv_cache.note_prefilled(suffix.shape[1])
            if parts is not None:
                t2 = _now()
            with _span("engine.pool_commit", gaps, rid=rid):
                table = kv_cache.commit(prompt_np, ck, cv, match,
                                        namespace=namespace)
            gaps.launched(kv_cache.last_commit[0])
            if parts is not None:
                t3 = _now()
                parts["commit_dispatches"], parts["commit_blocks"] = \
                    kv_cache.last_commit
            if match.tokens:
                event = {"kind": "prefix_hit", "outcome": outcome,
                         "reused_tokens": reused, "prompt_tokens": plen}
                if event_extra:
                    event.update(event_extra)
                kv_cache.record_event(event)
        # the slice is a program of its own, the newest launched: the
        # chip is empty once it is read
        live = last_logits[0, :config.vocab_size]
        gaps.launched()
        t4 = _clock(parts)
        live = np.asarray(live, np.float32)
        if parts is not None:
            gaps.read(t4, _now(), gaps.launches, "first_token")
        if counts is not None:
            counts = {k: int(v) for k, v in jax.device_get(counts).items()}
    first = int(np.argmax(live))
    m = float(live[first])
    score = -float(np.log(np.exp(live - m).sum()))  # m - logsumexp
    if parts is not None:
        commit_ms = (t3 - t2) * 1e3
        parts.update(lookup_ms=(t1 - t0) * 1e3, commit_ms=commit_ms,
                     prefill_ms=(_now() - t1) * 1e3 - commit_ms)
    return (ck, cv, state, table, first, score, outcome, int(reused),
            int(suffix.shape[1]), counts)


@functools.partial(jax.jit, static_argnums=(4, 5),
                   donate_argnums=(0,))
def _splice_slot(cache, ck, cv, slot, config, plen, state=()):
    """Write a prefilled sequence into batch slot `slot` of the decode
    slab, which is the family's own pytree: an entry with "k" has a
    sequence axis and takes rows [0, min(plen, rows)) of its layer of ck
    (and of cv where it holds "v" too; a latent entry holds "k" alone):
    all of a ring the prompt has wrapped, which the prefill handed back
    as it lies. `ck` and `cv` are one stack, or a tuple of stacks, one a
    (row count, row shape) in the order the slab first shows each
    (`_prefill_paged`).
    Any other entry is a slot's state and takes the next entry of `state`
    WHOLE. With the slab donated this lowers to an in-place update per
    entry, O(rows held) and the state's bytes, never a full-cache copy."""
    del config
    stack_of = {shape: j for j, shape in enumerate(stacks(cache))}
    if not isinstance(ck, tuple):
        ck, cv = (ck,), (cv,)
    out, layer, states = [], [0] * len(stack_of), iter(state)
    for blk in cache:
        if "k" in blk:
            rows = blk["k"].shape[1]
            j, n = stack_of[tuple(blk["k"].shape[1:])], min(plen, rows)
            at = (slot,) + (0,) * (blk["k"].ndim - 1)
            new = {"k": jax.lax.dynamic_update_slice(
                blk["k"], ck[j][layer[j], :n][None], at)}
            if "v" in blk:
                new["v"] = jax.lax.dynamic_update_slice(
                    blk["v"], cv[j][layer[j], :n][None], at)
            out.append(new)
            layer[j] += 1
        else:
            out.append(jax.tree.map(
                lambda slab, one: jax.lax.dynamic_update_slice(
                    slab, one.astype(slab.dtype),
                    (slot,) + (0,) * (slab.ndim - 1)),
                blk, next(states)))
    return out


def _chosen(logits, config, tokens, pos_vec, live):
    """What both ticks hand back beside the cache: the greedy token of
    each slot, its log-probability, and the position vector advanced, so
    that a tick's outputs are, as they lie, the next tick's inputs.
    `live` [B] (int32, 1 or 0) HOLDS a dead slot: its token comes back
    as it went in and its position stands still (at 0, where `_finish`
    parked it), so a slot nobody decodes for feeds the program the same
    row tick after tick, never a position past the window. None
    advances every slot."""
    lv = logits[..., :config.vocab_size].astype(jnp.float32)
    nxt = jnp.argmax(lv, axis=-1).astype(jnp.int32)
    # per-slot logprob of the chosen (greedy = max-logit) token — the
    # rollout score stream (ray_tpu.online samplers record it per token)
    lp = jnp.max(lv, axis=-1) - jax.nn.logsumexp(lv, axis=-1)
    if live is None:
        return nxt, lp, pos_vec + 1
    return jnp.where(live != 0, nxt, tokens), lp, pos_vec + live


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _tick(params, config, cache, tokens, pos_vec, live=None, lora=None):
    """One decode step — shape-polymorphic over the token axis:
    tokens [B] is the classic one-token tick; tokens [B, k+1] is the
    speculative VERIFY pass (column 0 each slot's last token, columns
    1..k its drafted continuation — slots with a shorter/no draft pad
    by repeating column 0; padded rows are never accepted and their
    KV rows stay masked until overwritten). jit specializes per shape,
    and the verify's row j is bit-identical to j sequential one-token
    ticks — the accept rule's whole contract, shared math by
    construction because this IS the same function.

    Returns (cache, tokens, log-probabilities, counters, positions):
    the chosen tokens and the positions advanced are what the NEXT
    one-token tick takes, so the loop launches it from them while they
    are still on the chip (`_chosen`: `live` holds the dead slots). A
    family's decode may hand back a third value, a dict of small
    counters of the step (models/nemotron_h.py: what its expert layers
    saw); it comes back beside the tokens, None for a family that has
    none. A family whose state step walks the live slots
    (`Family.state_walks`) is handed `live` too, so a dead slot's state
    is neither read nor written.

    `lora` makes it the mixed-tenant tick: PER-SLOT adapter indices
    (`lora["idx"]`) gather each slot's low-rank deltas out of the
    resident adapter-pool stacks, ``base @ x + scatter-gathered (B·A)
    @ x`` at the LoRA-target leaves (serve/lora.py), at every position
    of a verify pass too. Slots on the null adapter (index 0: zero A/B,
    scale 0) compute a bit-identical base-only step, so mixed batches
    never perturb base traffic. Passed only when a live slot actually
    holds an adapter; pool shapes are static, so this is ONE extra
    compiled program per engine."""
    args = (params, tokens, config, cache, pos_vec)
    if lora is not None:
        args += (lora,)
    family = family_of(config)
    # a state step that walks the live slots walks by THIS vector, the
    # one `_set_rows` writes and `_chosen` reads (the host's mirror is a
    # tick stale: the loop launches a tick ahead)
    walk = {"live": live} if family.state_walks and live is not None else {}
    logits, cache, *counts = family.decode(*args, **walk)
    nxt, lp, pos_next = _chosen(logits, config, tokens, pos_vec, live)
    return cache, nxt, lp, (counts[0] if counts else None), pos_next


@jax.jit
def _set_rows(tokens, pos_vec, live, fresh):
    """The one way the host writes into the tick's device vectors: `fresh`
    int32 [4, B] holds a mark on the slots of which the host knows
    better (admitted, adopted, finished or cancelled since the last
    launch), and their token, position and liveness from the host's
    mirror. Queued behind the tick in flight, whose outputs the vectors
    are, so the host's word wins in program order."""
    take = fresh[0] != 0
    return tuple(jnp.where(take, new, old) for new, old in
                 zip(fresh[1:], (tokens, pos_vec, live)))


class _Flight(NamedTuple):
    """A tick on the chip whose tokens the host has not read yet: the
    device outputs, and what the host knew at the launch. `reqs[slot]`
    is the request the tick decodes for at that slot (None: a dead slot,
    or one whose budget ends with the tick ahead of this one); `live`
    and `live_rows` are their count and the sum of their positions,
    `live_rows_window` the sum of `min(position, rows)` for the slab's
    ring (None without one), `slab_rows_read` the rows one layer's walk
    reads over ALL slots, `state_slots_stepped` the slot-states one
    layer's state step visits (None for a family without state); `seq`
    is the ledger's count of launches with this tick the newest."""

    nxt: Any
    lp: Any
    counts: Optional[Dict[str, Any]]
    reqs: List[Optional["_Request"]]
    live: int
    live_rows: int
    live_rows_window: Optional[int]
    slab_rows_read: int
    state_slots_stepped: Optional[int]
    drafts: Optional[Dict[int, List[int]]]
    seq: int


class _Adoption:
    """A pending slot adoption: a prompt's prefilled KV rows computed
    elsewhere (a prefill replica) plus the first token its last-position
    logits produced. The decode loop splices it between ticks."""

    __slots__ = ("req", "plen", "ck", "cv", "first_token", "score")

    def __init__(self, req: "_Request", plen: int, ck, cv,
                 first_token: int, score: float):
        self.req = req
        self.plen = int(plen)
        self.ck = ck
        self.cv = cv
        self.first_token = int(first_token)
        self.score = float(score)


class _Request:
    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 eos_token: Optional[int]):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.eos_token = eos_token
        self.out: "queue.Queue" = queue.Queue()
        self.produced = 0
        # per-request speculation tally (engine-wide counters can't
        # attribute accepts to one request) — the flight recorder's
        # decode_steady span reads these off the final chunked pull
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.slot: Optional[int] = None
        self.cache_outcome: Optional[str] = None  # hit|partial|miss
        self.reused_tokens = 0
        self.block_table: List[int] = []
        # per-token logprob of each emitted token (same order as the
        # token stream) — the rollout score channel
        self.scores: List[float] = []
        # the KNOWN token context (prompt + emitted) the speculative
        # proposer drafts from — empty for adoptions whose transfer
        # didn't carry the prompt (drafting then waits for history).
        # ctx_has_prompt marks that ctx[:plen] really IS the prompt:
        # the output-memory key is (adapter, prompt), and a promptless
        # adoption's first plen EMITTED tokens must neither store under
        # nor match such a key (it would evict genuine hot-prompt
        # chains from the capped LRU)
        self.ctx: List[int] = []
        self.ctx_has_prompt = False
        # incremental n-gram index over ctx for the self-lookup draft
        # fallback: {n-gram tuple: latest start position of an
        # occurrence ending BEFORE the current tail}. Amortized O(1)
        # per emitted token — a per-tick backward rescan would be
        # O(len(ctx)^2) over a long generation. `ng_indexed` = ctx
        # positions whose ending n-grams are already in.
        self.ngram_last: Dict[tuple, int] = {}
        self.ng_indexed = 0
        # multi-tenant LoRA (serve/lora.py): the tenant tag and its
        # pinned adapter-pool slot (0 = the null/base adapter)
        self.adapter_id: Optional[str] = None
        self.lora_slot = 0
        # cancel_slot() lifecycle: cancelled requests free their slot
        # (and pins) at the next tick boundary instead of decoding to
        # completion; finished guards double-release. cancel_reason
        # attributes the cancel (deadline | disconnect | preempt |
        # failover | idle_reap) in the engine's accounting.
        self.cancelled = False
        self.cancel_reason: Optional[str] = None
        self.finished = False
        # the engine's clock (module docstring): submit() or
        # adopt_prefill() returns, _admit pops the request, _emit puts
        # its first token; None while the flight recorder is off
        self.t_submit: Optional[float] = None
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        # the engine's `prefill_admitted` when the request was handed
        # in, and at the pop in _admit how far it had moved on: the
        # other requests' prefills this one waited behind
        self.prefills_before = 0
        self.prefills_waited: Optional[int] = None
        # the ledger's number of the last landing this request took a
        # token from (recorder on)
        self.landed = -1
        # where the emitted tokens begin in `ctx` (the prompt's length,
        # 0 for an adoption that carried no prompt)
        self.ctx_base = 0
        # the hand-over (module docstring): the consumer's `StreamSink`
        # (None: the queue, token by token), the tokens of the pass
        # under way that have not been handed over yet (None: the
        # request is not in `_batch`), and whether its END has left, by
        # the sink or by the queue
        self.sink: Optional["StreamSink"] = None
        self.batch: Optional[List[int]] = None
        self.closed = False
        self.stream: Optional["TokenStream"] = None


class StreamSink:
    """What a consumer registers with ONE stream (``stream(sink=)``) to
    take its tokens by hand-over (module docstring, "A tick lands
    once"). `call` is the consumer's one callable, shared by all its
    streams (a gateway has one a server): the loop calls each DISTINCT
    `call` once a pass with ``[(stream, [tokens...], ended), ...]``, on
    the loop's thread, so it must not block. `tag` is the consumer's own
    handle on this stream (``TokenStream.tag``; the gateway's: the
    stream's asyncio queue). `wake` is the event the stream's OWNER
    sleeps on: the loop sets it twice a stream, once its first token and
    once its end have been handed over, and anyone who wants the owner
    to look up sets it too (the gateway at a disconnect). One sink may
    serve a request's streams in turn (a replay after a preemption)."""

    __slots__ = ("call", "tag", "wake")

    def __init__(self, call: Callable[[List[tuple]], None],
                 tag: Any = None):
        self.call = call
        self.tag = tag
        self.wake = threading.Event()


class TokenStream:
    """Iterator over one request's tokens with the prefix-cache outcome
    attached (``cache_outcome``: hit|partial|miss, None until the
    request is admitted — always set before the first token arrives).
    Serve's streaming replica reads it to label the TTFT histogram.
    ``queue_ms`` / ``prefill_ms`` split the first token's wait on the
    engine's own clock, set before the first token arrives too (None
    with the flight recorder off); ``prefills_waited`` counts the other
    requests' prefills that ran in ``queue_ms``. A stream that was given
    a `StreamSink` is no iterator: its tokens leave by the sink, and its
    owner reads ``produced``, ``ended`` and, at the end, ``tokens()``."""

    def __init__(self, req: _Request, timeout_s: float):
        self._req = req
        self._timeout_s = timeout_s

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> int:
        if self._req.sink is not None:
            raise TypeError("this stream's tokens leave by its sink")
        tok = self._req.out.get(timeout=self._timeout_s)
        if tok is _DONE:
            raise StopIteration
        return int(tok)

    @property
    def tag(self) -> Any:
        """The consumer's handle, as its `StreamSink` carries it."""
        sink = self._req.sink
        return None if sink is None else sink.tag

    @property
    def produced(self) -> int:
        """Tokens emitted so far."""
        return self._req.produced

    @property
    def ended(self) -> bool:
        """The stream's end has LEFT the engine: handed to the sink
        (after its last token, in the same call) or put on the queue."""
        return self._req.closed

    def tokens(self) -> List[int]:
        """Every token emitted so far, in order: the engine's own
        history (`ctx`), no second copy kept. Whole once ``ended``."""
        r = self._req
        return r.ctx[r.ctx_base:]

    @property
    def cache_outcome(self) -> Optional[str]:
        return self._req.cache_outcome

    @property
    def reused_tokens(self) -> int:
        return self._req.reused_tokens

    @property
    def queue_ms(self) -> Optional[float]:
        """From submit()'s return to the pop in `_admit`: the wait for
        a tick boundary, a free slot and the prefills ahead."""
        r = self._req
        if r.t_submit is None or r.t_admit is None:
            return None
        return (r.t_admit - r.t_submit) * 1e3

    @property
    def prefill_ms(self) -> Optional[float]:
        """From that pop to the first token's `_emit`: this request's
        own lookup, prefill, pool commit and splice."""
        r = self._req
        if r.t_admit is None or r.t_first is None:
            return None
        return (r.t_first - r.t_admit) * 1e3

    @property
    def prefills_waited(self) -> Optional[int]:
        """Prefills of other requests the engine ran between this
        request's hand-in and its own admission (None until then): each
        is a stair in its time to first token."""
        return self._req.prefills_waited

    @property
    def scores(self) -> List[float]:
        """Per-token logprobs of the tokens emitted SO FAR (aligned
        with the token stream; complete once iteration finishes)."""
        return list(self._req.scores)


class ContinuousBatchingEngine:
    """Greedy continuous-batching decode over `max_batch` slots."""

    def __init__(self, params: Any, config: Any, *,
                 max_batch: int = 8, idle_sleep_s: float = 0.002,
                 params_version: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 max_prefills_per_tick: Optional[int] = None,
                 max_adoptions_per_tick: Optional[int] = None,
                 lora_pool: Optional[Any] = None,
                 speculate_k: Optional[int] = None,
                 draft_source: Optional[Callable[[List[int], int],
                                                 List[int]]] = None,
                 kv_int8: Optional[bool] = None):
        # config: of any family that has a record (models/family.py)
        self.params = params
        self.config = config
        self.max_batch = max_batch
        self.idle_sleep_s = idle_sleep_s
        self.engine_id = f"cb-{os.getpid()}-{next(_ENGINE_SEQ)}"
        # live-weight hot swap (ray_tpu.weights): a queued (params,
        # version) is applied by the decode loop BETWEEN ticks — the
        # params pytree is a plain jit argument, so swapping it never
        # invalidates compiled programs or in-flight slots' KV caches
        self.params_version = params_version
        self._pending_swap: Optional[tuple] = None
        self.swap_count = 0
        family = family_of(config)
        # the slab is the family's own pytree; what it is made of, from
        # its shapes (models/family.py): kv_stats() reads it
        self._spec = spec = slab_spec(config, max_batch)
        self.stateful = spec.stateful
        self.latent_only = spec.latent_only
        self.ring_rows = spec.ring_rows
        # the block by which the tick's attention walks the slab's
        # longest entries, keys and values or latent rows (None: it
        # reads every row of every slot)
        self._walk_block = None
        if family.decode_walks:
            self._walk_block = decode_block(spec.longest.shape,
                                            spec.longest.dtype)
        # the tick's state step visits the slots the DEVICE's liveness
        # vector holds live, and no other (else: every slot's)
        self._state_walks = spec.stateful and family.state_walks
        if speculate_k is None:
            speculate_k = default_speculate_k()
        # what a cache that is not full-length keys and values cannot
        # give is refused in words; left to its default it gets no pool
        refuse(spec, "prefix_cache", prefix_cache)
        refuse(spec, "speculate_k", speculate_k, k=speculate_k)
        refuse(spec, "lora_pool", lora_pool is not None)
        if spec.kind is not None:
            prefix_cache = False
        self._cache = family.init_cache(config, max_batch)
        # paged KV prefix cache (models/kvcache.py); RAY_TPU_KV_* env
        # knobs supply defaults, constructor args win
        from ray_tpu.util import envknobs

        if prefix_cache is None:
            prefix_cache = envknobs.get_str(
                "RAY_TPU_KV_CACHE", "1") != "0"
        if max_prefills_per_tick is None:
            max_prefills_per_tick = envknobs.get_int(
                "RAY_TPU_MAX_PREFILLS_PER_TICK", 1)
        self.max_prefills_per_tick = max(1, int(max_prefills_per_tick))
        # adoptions (disaggregated decode) are capped per-phase: a
        # splice is O(prompt) and never compiles a prefill program, so
        # its default budget is looser than the prefill cap
        if max_adoptions_per_tick is None:
            max_adoptions_per_tick = envknobs.get_int(
                "RAY_TPU_MAX_ADOPTIONS_PER_TICK", 4)
        self.max_adoptions_per_tick = max(1, int(max_adoptions_per_tick))
        if kv_int8 is None:
            from .kvcache import kv_int8_default

            kv_int8 = kv_int8_default()
        self.kv_int8 = bool(kv_int8)
        block_size, pool_blocks = resolve_pool_config(
            config, kv_block_size, kv_pool_blocks, slots=max_batch,
            int8=self.kv_int8)
        self.kv_cache: Optional[PagedKVCache] = (
            PagedKVCache(config, block_size=block_size,
                         num_blocks=pool_blocks, int8=self.kv_int8)
            if prefix_cache else None)
        # speculative decoding (module docstring): k drafted tokens per
        # slot verified in one widened tick; 0 = the classic loop.
        # `draft_source(ctx, k) -> tokens` overrides the prompt-lookup
        # proposer (tests script full/partial/zero acceptance with it).
        self.speculate_k = max(0, int(speculate_k))
        self.draft_source = draft_source
        # cross-request output memory: greedy decode under fixed
        # weights is a FUNCTION of (adapter, prompt), so a finished
        # request's token chain is a near-perfect draft for the next
        # request with the same prompt — the Zipf-hot-prompt case the
        # serving replay is made of. Wrong-by-staleness entries cost
        # acceptance, never correctness (the verify pass is the only
        # accept authority); a weight swap clears it anyway.
        self._output_memory: "OrderedDict[tuple, List[int]]" = \
            OrderedDict()
        self._output_memory_cap = 128
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_verify_ticks = 0
        self.spec_emitted = 0       # tokens emitted by DRAFTING slots
        self._spec_events: List[Dict[str, Any]] = []
        if self.speculate_k:
            spec_metrics()  # lazy registration before the first tick
        self._empty_prefix = jnp.zeros(spec.stack_shape(0), spec.dtype)
        # admission accounting (kv_stats / acceptance surface) — split
        # per phase: prefill admissions vs adoptions of KV prefilled on
        # another replica (serve/disagg.py)
        self.prefill_calls = 0
        self.prefilled_tokens = 0
        # totals of what the family's prefills counted (module docstring)
        self.prefill_counters: Dict[str, int] = {}
        self.spliced_tokens = 0
        self.admitted = 0            # total slots admitted (both phases)
        self.prefill_admitted = 0
        self.adopted = 0
        self.cancelled = 0           # slots freed early by cancel_slot()
        # the same count split by the caller-supplied cancel reason
        # (deadline | disconnect | preempt | failover | idle_reap |
        # unspecified) — a QoS preemption must never read as a shed
        self.cancelled_by_reason: Dict[str, int] = {}
        self.max_prefills_admitted_per_tick = 0
        self.max_adoptions_admitted_per_tick = 0
        self._pusher = Pusher("kvcache", self.engine_id)
        # the host's mirror of each slot's last token and position, as
        # of the newest tick READ; the tick's own copies stay on the
        # chip (`_dev`: tokens, positions, liveness, the outputs of the
        # newest tick launched) and the host writes only the rows it
        # knows better (`_dirty`, `_set_rows`)
        self._tokens = np.zeros(max_batch, np.int32)
        self._pos = np.zeros(max_batch, np.int32)
        self._dev = tuple(jnp.zeros(max_batch, jnp.int32)
                          for _ in range(3))
        self._dirty = np.zeros(max_batch, bool)
        # the liveness `_set_rows` last wrote into `_dev`: what the chip
        # holds, which the host's `_slot_req` runs a launch ahead of
        self._dev_live = np.zeros(max_batch, bool)
        self.ticks_launched = 0
        self.state_slots_stepped = 0  # over them, one layer's step
        self.lookahead_ticks = 0      # launched behind a tick in flight
        self.lookahead_discarded = 0  # their rows a finished slot left
        self._gaps = _GapLedger()     # module docstring
        # the hand-over: the requests that hold tokens (or an end) of
        # the pass under way for their sinks, in the order the walk met
        # them; tokens that left by a sink, the sink calls that took
        # them, and tokens that went by a queue
        self._batch: List[_Request] = []
        self.handed = 0
        self.handovers = 0
        self.queued = 0
        self._slot_req: List[Optional[_Request]] = [None] * max_batch
        self._free = list(range(max_batch))
        # multi-tenant LoRA (serve/lora.py AdapterPool, duck-typed so
        # models/ never imports serve/): per-slot adapter-pool indices
        # (0 = null/base adapter). Adapter acquisition — including a
        # cold page-in — happens on the SUBMITTING thread, never here,
        # so paging one tenant's adapter can't stall another's ticks.
        self.lora_pool = lora_pool
        self._slot_adapter = np.zeros(max_batch, np.int32)
        if lora_pool is not None and self.kv_cache is not None:
            # prefix-cache namespaces are (tenant, adapter-version)
            # stamped, so a hot-swap can never serve old-version KV —
            # this listener only EAGERLY reclaims the superseded
            # version's blocks (they would otherwise LRU out)
            lora_pool.add_swap_listener(
                lambda tenant, old, _p=lora_pool:
                self.kv_cache.invalidate(
                    namespace=_p.cache_namespace(tenant, old)))
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._pending_adopt: "queue.Queue[_Adoption]" = queue.Queue()
        self._cancels = 0  # cancelled-but-unfreed request count
        self._lock = threading.Lock()
        self._next_rid = 0
        self._stopped = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="cb-engine")
        self._thread.start()

    # ------------------------------------------------------------- API
    def submit(self, prompt_tokens, max_new_tokens: int,
               eos_token: Optional[int] = None,
               adapter_id: Optional[str] = None,
               sink: Optional[StreamSink] = None,
               timeout_s: float = 120.0) -> "_Request":
        """`adapter_id` (multi-tenant LoRA): decode this request under
        that tenant's adapter. The pool pin — and a cold adapter's
        page-in — happens HERE on the caller's thread, so paging never
        blocks the decode loop; the pin is released when the slot
        frees (finish or cancel). `sink`: EVERY token of the request,
        the prefill's first too, leaves by the hand-over and none by the
        queue (module docstring); its `TokenStream` is `.stream`."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(1, -1)
        if prompt.shape[1] + max_new_tokens > self.config.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        lora_slot = 0
        if adapter_id is not None:
            if self.lora_pool is None:
                raise ValueError(
                    f"request for adapter {adapter_id!r} but this "
                    f"engine has no lora_pool (serve/lora.AdapterPool)")
            lora_slot = self.lora_pool.acquire(adapter_id)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        req = _Request(rid, prompt, max_new_tokens, eos_token)
        req.ctx = [int(t) for t in prompt[0]]
        req.ctx_base = len(req.ctx)
        req.ctx_has_prompt = True
        req.sink = sink
        req.stream = TokenStream(req, timeout_s)
        req.adapter_id = adapter_id
        req.lora_slot = lora_slot
        req.prefills_before = self.prefill_admitted
        if reqtrace.enabled():
            req.t_submit = _now()
        self._pending.put(req)
        return req

    def stream(self, prompt_tokens, max_new_tokens: int,
               eos_token: Optional[int] = None,
               timeout_s: float = 120.0,
               adapter_id: Optional[str] = None,
               sink: Optional[StreamSink] = None) -> Iterator[int]:
        """Submit and yield tokens as the shared loop produces them.
        Returns a TokenStream whose ``cache_outcome`` labels the
        admission's prefix-cache result. With a `sink` the stream yields
        nothing: the loop hands its tokens to the sink, once a pass."""
        return self.submit(prompt_tokens, max_new_tokens, eos_token,
                           adapter_id=adapter_id, sink=sink,
                           timeout_s=timeout_s).stream

    def generate(self, prompt_tokens, max_new_tokens: int,
                 eos_token: Optional[int] = None,
                 timeout_s: float = 120.0,
                 adapter_id: Optional[str] = None) -> List[int]:
        return list(self.stream(prompt_tokens, max_new_tokens, eos_token,
                                timeout_s, adapter_id=adapter_id))

    def adopt_prefill(self, prompt_len: int, first_token: int, ck, cv,
                      max_new_tokens: int,
                      eos_token: Optional[int] = None, *,
                      score: float = 0.0,
                      cache_outcome: Optional[str] = None,
                      reused_tokens: int = 0,
                      adapter_id: Optional[str] = None,
                      prompt_tokens: Optional[List[int]] = None,
                      timeout_s: float = 120.0) -> TokenStream:
        """Adopt a prompt whose prefill ran ELSEWHERE (a disaggregated
        prefill replica): ``ck/cv [L, prompt_len, H, hd]`` are the
        prompt's KV rows and `first_token` the token its last-position
        logits produced. The decode loop splices the rows into a free
        slot between ticks (`_splice_slot`, O(prompt_len) — never a
        full-cache copy) and this engine NEVER runs a prefill program
        for the request, so a decode replica's `_prefill_paged` compile
        cache stays flat. Returns the request's TokenStream, whose
        first yielded token is `first_token`. `prompt_tokens`
        (optional) hands the speculative proposer the prompt's actual
        tokens — the transfer record carries them under disaggregation
        so decode-side drafting sees the same context the colocated
        engine would; without them drafting starts from the emitted
        history alone (correctness unaffected)."""
        plen = int(prompt_len)
        refuse(self._spec, "adopt_prefill")
        if plen < 1:
            raise ValueError("prompt_len must be >= 1")
        if plen + max_new_tokens > self.config.max_seq_len:
            # the first token is already produced, so the exact bound
            # would allow one more token than submit() — but the two
            # admission paths must reject IDENTICALLY or the disagg
            # tier and the colocated fallback diverge at the
            # sequence-length boundary
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        # validate the FULL layout on the caller's thread: a mismatch
        # surfacing inside _splice_slot would kill the decode loop
        # thread and wedge every request on this engine. Dtype is part
        # of the layout — asarray below would otherwise silently cast
        # a float32 prefill tier into a bfloat16 decode pool and break
        # bit-identity with no error anywhere.
        want, dtype = self._spec.stack_shape(plen), self._spec.dtype
        got_k = jnp.asarray(ck)
        got_v = jnp.asarray(cv)
        if (tuple(got_k.shape) != want or tuple(got_v.shape) != want
                or got_k.dtype != dtype or got_v.dtype != dtype):
            raise ValueError(
                f"adopted KV layout k={tuple(got_k.shape)}:{got_k.dtype} "
                f"v={tuple(got_v.shape)}:{got_v.dtype} does not match "
                f"this engine's cache layout {want}:{dtype} — the "
                f"prefill and decode tiers must run the same model "
                f"config")
        ck, cv = got_k, got_v
        lora_slot = 0
        if adapter_id is not None:
            if self.lora_pool is None:
                raise ValueError(
                    f"adoption for adapter {adapter_id!r} but this "
                    f"engine has no lora_pool (the prefill and decode "
                    f"tiers must both be LoRA-enabled)")
            # caller's thread, like submit(): a cold page-in here never
            # stalls the decode loop
            lora_slot = self.lora_pool.acquire(adapter_id)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        req = _Request(rid, np.zeros((1, plen), np.int32),
                       max_new_tokens, eos_token)
        if prompt_tokens is not None:
            req.ctx = [int(t) for t in prompt_tokens]
            req.ctx_base = len(req.ctx)
            req.ctx_has_prompt = True
        req.stream = TokenStream(req, timeout_s)
        req.cache_outcome = cache_outcome
        req.reused_tokens = int(reused_tokens)
        req.adapter_id = adapter_id
        req.lora_slot = lora_slot
        req.prefills_before = self.prefill_admitted
        if reqtrace.enabled():
            req.t_submit = _now()
        self._pending_adopt.put(_Adoption(req, plen, ck, cv,
                                          first_token, score))
        return req.stream

    def update_params(self, params: Any,
                      version: Optional[int] = None) -> threading.Event:
        """Queue a live weight swap; the decode loop applies it between
        ticks (never mid-tick), so in-flight requests keep their KV
        caches and keep decoding — under the new weights from the next
        tick LAUNCHED on; the one already on the chip finishes on the
        old and its token is still emitted — with no restart and no
        drop. Returns an Event set once the swap has been applied. Two
        swaps queued between the same two ticks coalesce: the newer
        wins, both events fire."""
        ev = threading.Event()
        with self._lock:
            prev = self._pending_swap
            self._pending_swap = (params, version,
                                  (prev[2] + [ev]) if prev else [ev])
        if self._stopped.is_set() and not self._thread.is_alive():
            # decode loop confirmed exited (not merely stop-requested —
            # the loop may still be inside its final tick): apply
            # synchronously so a caller's wait() never strands on a
            # stopped engine, without ever swapping mid-tick
            self._apply_pending_swap()
        return ev

    def _apply_pending_swap(self) -> None:
        """Decode-loop only, between ticks."""
        with self._lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        params, version, events = pending
        self.params = params
        self.params_version = version
        self.swap_count += 1
        # every cached block's KV was computed under the old weights:
        # drop the prefix index so no post-swap admission matches it
        # (in-flight slots decode off their own slab copy, unaffected).
        # The speculative output memory is stale the same way — keeping
        # it would only burn verify width on rejected drafts.
        self._output_memory.clear()
        if self.kv_cache is not None:
            self.kv_cache.invalidate()
            self.publish_kv_telemetry(force=True)
        for ev in events:
            ev.set()

    def cancel_slot(self, stream_or_req: Any,
                    reason: Optional[str] = None) -> bool:
        """Cancel a live request (its TokenStream or the _Request
        itself): the decode loop frees its slot — and releases its KV
        pins and LoRA adapter pin — at the NEXT TICK BOUNDARY instead
        of decoding the abandoned request to completion (the PR-12
        deadline path used to waste every remaining tick on it; the
        tick already launched for it is read and its row discarded,
        never emitted). The
        freed slot is immediately re-admittable. Returns False when the
        request already finished (or was already cancelled); the
        stream's consumer sees a normal end-of-stream. `reason`
        attributes the cancel in ``cancelled_by_reason`` (the QoS
        preemption path tags ``preempt`` so its cancels never read as
        sheds)."""
        req = getattr(stream_or_req, "_req", stream_or_req)
        with self._lock:
            if req.finished or req.cancelled:
                return False
            req.cancelled = True
            req.cancel_reason = reason
            self._cancels += 1
        return True

    def _apply_cancels(self, it: Optional[Dict[str, Any]] = None) -> None:
        """Decode-loop only, between ticks: free cancelled ACTIVE slots
        (queued cancelled requests are dropped at admission instead)."""
        with self._lock:
            if self._cancels == 0:
                return
        for req in list(self._slot_req):
            if req is not None and req.cancelled and not req.finished:
                self._count_cancel(req)
                self._finish(req)
        self._hand_over(it)
        self.publish_kv_telemetry()

    def _count_cancel(self, req: "_Request") -> None:
        key = req.cancel_reason or "unspecified"
        self.cancelled += 1
        self.cancelled_by_reason[key] = \
            self.cancelled_by_reason.get(key, 0) + 1

    def stop(self) -> None:
        self._stopped.set()
        self._thread.join(timeout=10.0)
        self._apply_pending_swap()  # fire waiters a dead loop would strand
        self.publish_kv_telemetry(force=True)

    @property
    def active_slots(self) -> int:
        with self._lock:
            return self.max_batch - len(self._free)

    @property
    def free_slots(self) -> int:
        """Open decode slots right now (the disagg router's decode-pick
        signal; pending-but-unadmitted requests do not subtract)."""
        with self._lock:
            return len(self._free)

    # ------------------------------------------------------- telemetry
    def kv_stats(self) -> Dict[str, Any]:
        """Prefix-cache + admission counters — the snapshot pushed to
        the conductor for util.state.kv_cache_stats(), the CLI, and the
        dashboard (all surfaces report THIS dict's numbers)."""
        s: Dict[str, Any] = (self.kv_cache.stats() if self.kv_cache
                             else {"enabled": False})
        programs = _prefill_paged._cache_size()
        s.update(
            engine_id=self.engine_id,
            max_batch=self.max_batch,
            max_prefills_per_tick=self.max_prefills_per_tick,
            max_adoptions_per_tick=self.max_adoptions_per_tick,
            admitted=self.admitted,
            prefill_admitted=self.prefill_admitted,
            adopted=self.adopted,
            max_prefills_admitted_per_tick=(
                self.max_prefills_admitted_per_tick),
            max_adoptions_admitted_per_tick=(
                self.max_adoptions_admitted_per_tick),
            prefill_calls=self.prefill_calls,
            prefill_programs=programs,
            spliced_tokens=self.spliced_tokens,
            cancelled=self.cancelled,
            cancelled_by_reason=dict(self.cancelled_by_reason),
            lora=self.lora_pool is not None,
            stateful=self.stateful,
            latent_only=self.latent_only,
            prefill_counters=dict(self.prefill_counters),
            state_bytes_per_slot=self._spec.state_bytes_per_slot,
            kv_bytes_per_token=self._spec.kv_bytes_per_token,
            ring_rows=self.ring_rows,
            slab=[dict(entry) for entry in self._spec.slab],
            lookahead_ticks=self.lookahead_ticks,
            lookahead_discarded=self.lookahead_discarded,
            # over the ticks launched, the slot-states ONE layer's state
            # step visited (`max_batch` a tick where it steps every slot)
            ticks_launched=self.ticks_launched,
            state_slots_stepped=self.state_slots_stepped,
            # stream-gaps counted, those that held an admission, the
            # milliseconds blocked on the chip and those it was starved
            # for by the host's step (module docstring: what the
            # benchmark's gap readers read)
            gaps=self._gaps.totals(),
            # tokens that left by a sink, the sink calls that took them
            # (one a pass where every stream is one consumer's), and
            # tokens that went by a stream's queue
            handover={"handed": self.handed,
                      "handovers": self.handovers,
                      "queued": self.queued},
            # which traced shapes of the expert layers' grouped product
            # took the streamed kernel (the process's, not this engine's)
            grouped_product=dispatch.kernel_choices("grouped_product"),
            # and of the grouped-query prompt form (ops/swa.py)
            gqa_prefill=dispatch.kernel_choices("gqa_prefill"),
            # and of the decode form, shapes (B, t, H, G, d, S)
            gqa_decode=dispatch.kernel_choices("gqa_decode"),
            # and of the absorbed form over latent rows (ops/mla.py),
            # shapes (B, t, H, width, rank, S)
            mla_decode=dispatch.kernel_choices("mla_decode"),
            # and of the Mamba-1 selective scan (ops/mamba1.py)
            selective_scan=dispatch.kernel_choices("selective_scan"),
            # and of the Mamba-2 state step, shapes (B, H, P, G, N)
            state_step=dispatch.kernel_choices("state_step"),
        )
        s.update(self.speculation_stats())
        if self.kv_cache is None:
            # uncached engines still account their prefill work
            s.setdefault("prefilled_tokens", self.prefilled_tokens)
            s.setdefault("reused_tokens", 0)
        return s

    def speculation_stats(self) -> Dict[str, Any]:
        """The speculative-decoding snapshot every surface reports —
        embedded in kv_stats() so one conductor push feeds
        util.state.speculation_stats(), `ray_tpu speculate`,
        /api/speculation, and Prometheus with the same numbers."""
        proposed = self.spec_proposed
        ticks = self.spec_verify_ticks
        return {
            "speculate_k": self.speculate_k,
            "spec_proposed": proposed,
            "spec_accepted": self.spec_accepted,
            "spec_verify_ticks": ticks,
            "spec_emitted_tokens": self.spec_emitted,
            "acceptance_rate": (self.spec_accepted / proposed
                                if proposed else 0.0),
            "tokens_per_verify": (self.spec_emitted / ticks
                                  if ticks else 0.0),
            "kv_int8": self.kv_int8,
        }

    def publish_kv_telemetry(self, force: bool = False) -> None:
        """Best-effort push of kv_stats + pending timeline events to the
        conductor (no-op without a live cluster); throttled unless
        forced."""
        def events():
            kv = (self.kv_cache.drain_events()
                  if self.kv_cache is not None else [])
            for ev in kv:
                ev.setdefault("engine", self.engine_id)
            # spec_accept/spec_reject markers ride the kvcache timeline
            # lane — the engine buffers them itself because a decode
            # replica (prefix cache disabled) has no kv_cache to carry
            # events through
            return kv + self._drain_spec_events()

        self._pusher.push(self.kv_stats, events, force=force)

    # ------------------------------------------------------- admission
    def _admit(self, it: Optional[Dict[str, Any]] = None) -> None:
        # adoptions first (disaggregated decode: splices, no prefill
        # program), then prefill admissions — each against its own
        # per-phase cap so the counters stay truthful in both modes.
        # `it` is the pass's loop record (None: flight recorder off).
        adopted = 0
        while self._free and adopted < self.max_adoptions_per_tick:
            try:
                adoption = self._pending_adopt.get_nowait()
            except queue.Empty:
                break
            if self._adopt_one(adoption, it):
                adopted += 1
            self._hand_over(it)
        admitted = 0
        while self._free and admitted < self.max_prefills_per_tick:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if self._admit_one(req, it):
                admitted += 1
            # the first token goes at once, a batch of one: the next
            # admission's prefill does not hold it
            self._hand_over(it)
        if adopted:
            self.max_adoptions_admitted_per_tick = max(
                self.max_adoptions_admitted_per_tick, adopted)
        if admitted:
            self.max_prefills_admitted_per_tick = max(
                self.max_prefills_admitted_per_tick, admitted)
        if adopted or admitted:
            self.publish_kv_telemetry()

    def _long_prompt_waits(self) -> bool:
        """Whether the next request to admit has a prompt longer than
        half the window, and a slot to go to. Its prefill would hold the
        tokens of the tick in flight, which are ready within a tick, for
        as long as it runs (130 to 170 ms for 3,072 and 4,096 tokens of
        Mistral's eight layers on a v5e, PERF.md PR 32): a finished
        request would learn of its last token that much later. So the
        loop reads that tick first and lets the chip wait for the
        host's part of the admission, as it did before the lookahead;
        a shorter prompt is queued behind the tick in flight. Not in the
        pass that follows an admission: its tick was launched behind
        that prefill and has only begun."""
        if not self._free:
            return False
        with self._pending.mutex:
            head = self._pending.queue[0] if self._pending.queue else None
        return (head is not None
                and 2 * head.prompt.shape[1] > self.config.max_seq_len)

    def _splice(self, req: _Request, ck, cv, slot: int, plen: int,
                entry: Optional[Dict[str, Any]], state=()) -> None:
        """Both admission paths' write into the decode slab; the
        request's first `_emit` follows it."""
        t0 = _clock(entry)
        self._gaps.mark("splice", t0)
        with _span("engine.splice", self._gaps, rid=req.rid):
            self._cache = _splice_slot(self._cache, ck, cv,
                                       np.int32(slot), self.config, plen,
                                       tuple(state))
        self._gaps.launched()
        self._gaps.mark("first_token")
        if entry is not None:
            self._gaps.admitted()
            entry["splice_ms"] = (_now() - t0) * 1e3
            if self.stateful:
                entry["state_bytes"] = self._spec.state_bytes_per_slot
        self.spliced_tokens += plen

    def _admission(self, it: Optional[Dict[str, Any]], req: _Request,
                   plen: int) -> Optional[Dict[str, Any]]:
        """Open `req`'s entry in the pass's record and stamp the pop."""
        req.prefills_waited = self.prefill_admitted - req.prefills_before
        if it is None:
            return None
        req.t_admit = _now()
        entry = {"rid": req.rid, "prompt_tokens": plen,
                 "prefills_waited": req.prefills_waited,
                 "suffix_tokens": 0, "reused_tokens": 0,
                 "lookup_ms": 0.0, "prefill_ms": 0.0, "commit_ms": 0.0,
                 "commit_dispatches": 0, "commit_blocks": 0,
                 "splice_ms": 0.0}
        it["admissions"].append(entry)
        return entry

    def _adopt_one(self, adoption: _Adoption,
                   it: Optional[Dict[str, Any]] = None) -> bool:
        req = adoption.req
        if req.cancelled:
            # cancelled before admission: never occupies a slot
            self._count_cancel(req)
            self._finish(req)
            return False
        plen = adoption.plen
        entry = self._admission(it, req, plen)
        if entry is not None:
            entry["reused_tokens"] = req.reused_tokens
        with self._lock:
            slot = self._free.pop()
        self._splice(req, adoption.ck, adoption.cv, slot, plen, entry)
        self.admitted += 1
        self.adopted += 1
        req.slot = slot
        self._slot_req[slot] = req
        self._slot_adapter[slot] = req.lora_slot
        self._tokens[slot] = adoption.first_token
        self._pos[slot] = plen
        self._dirty[slot] = True
        self._emit(req, adoption.first_token, adoption.score)
        self._gaps.mark("bookkeeping")
        return True

    def _admit_one(self, req: _Request,
                   it: Optional[Dict[str, Any]] = None) -> bool:
        if req.cancelled:
            # cancelled before admission: never occupies a slot
            self._count_cancel(req)
            self._finish(req)
            return False
        plen = req.prompt.shape[1]
        entry = self._admission(it, req, plen)
        with self._lock:
            slot = self._free.pop()
        adapter = None
        namespace = None
        if self.lora_pool is not None and req.adapter_id is not None:
            # slice + version read atomically: the (tenant, version)-
            # stamped namespace must describe exactly the adapter this
            # prefill computes under, even if the row hot-swaps
            # mid-compute
            adapter, aver = self.lora_pool.adapter_slice(
                req.lora_slot, with_version=True)
            namespace = self.lora_pool.cache_namespace(req.adapter_id,
                                                       aver)
        (ck, cv, state, table, first, score, outcome, reused, suffix_len,
         counts) = _prefill_with_cache(
            self.params, self.config, self.kv_cache, req.prompt,
            self._empty_prefix, event_extra={"rid": req.rid},
            adapter=adapter, namespace=namespace, parts=entry,
            gaps=self._gaps)
        if entry is not None:
            entry["suffix_tokens"] = suffix_len
            entry["reused_tokens"] = reused
            entry.update(counts or {})
        for name, n in (counts or {}).items():
            self.prefill_counters[name] = \
                self.prefill_counters.get(name, 0) + n
        if self.kv_cache is not None:
            req.cache_outcome = outcome
            req.reused_tokens = reused
            req.block_table = table
        self.prefill_calls += 1
        self.prefilled_tokens += suffix_len
        self._splice(req, ck, cv, slot, plen, entry, state)
        self.admitted += 1
        self.prefill_admitted += 1
        req.slot = slot
        self._slot_req[slot] = req
        self._slot_adapter[slot] = req.lora_slot
        self._tokens[slot] = first
        self._pos[slot] = plen
        self._dirty[slot] = True
        self._emit(req, first, score)
        self._gaps.mark("bookkeeping")
        return True

    def _finish(self, req: _Request) -> None:
        """Decode-loop only: end a request's stream and free its slot,
        KV pins, and LoRA adapter pin (normal completion, admission-
        time cancel drop, and the tick-boundary cancel all share this
        one path so nothing is ever released twice)."""
        if self.speculate_k and not req.cancelled \
                and req.ctx_has_prompt:
            plen = req.prompt.shape[1]
            if len(req.ctx) > plen:
                # remember (adapter, prompt) -> full greedy chain for
                # the cross-request proposer (decode-loop-only state)
                key = (req.adapter_id, tuple(req.ctx[:plen]))
                self._output_memory[key] = list(req.ctx)
                self._output_memory.move_to_end(key)
                while len(self._output_memory) > self._output_memory_cap:
                    self._output_memory.popitem(last=False)
        if req.sink is None:
            req.out.put(_DONE)
            req.closed = True
        else:
            # the end rides the pass's hand-over, behind the last token
            self._batched(req)
        slot = req.slot
        if slot is not None:
            self._slot_req[slot] = None
            self._slot_adapter[slot] = 0
            # dead from the next launch on, and PARKED: the same
            # `_set_rows` puts its position at 0, so the tick's walk
            # reads one block for it and not the dead request's rows
            self._pos[slot] = 0
            self._dirty[slot] = True
        if self.kv_cache is not None and req.block_table:
            self.kv_cache.release(req.block_table)
            req.block_table = []
        if self.lora_pool is not None and req.adapter_id is not None:
            self.lora_pool.release(req.adapter_id)
        with self._lock:
            req.finished = True
            if slot is not None:
                self._free.append(slot)
            if req.cancelled:
                self._cancels -= 1

    def _emit(self, req: _Request, tok: int, score: float = 0.0) -> None:
        req.ctx.append(int(tok))
        req.scores.append(score)
        if req.t_first is None and req.t_admit is not None:
            req.t_first = _now()
        if req.sink is None:
            req.out.put(tok)
            self.queued += 1
        else:
            self._batched(req).append(tok)
        req.produced += 1
        if (req.eos_token is not None and tok == req.eos_token) \
                or req.produced >= req.max_new:
            self._finish(req)

    def _batched(self, req: _Request) -> List[int]:
        """`req`'s tokens in the hand-over under way, which it joins."""
        if req.batch is None:
            req.batch = []
            self._batch.append(req)
        return req.batch

    def _hand_over(self, it: Optional[Dict[str, Any]] = None) -> None:
        """Decode-loop only: what the walk just made (`_land`, an
        admission's first `_emit`, a cancel's `_finish`) crosses to the
        consumers, each DISTINCT sink called ONCE with its streams'
        tokens in the order the walk met them, a stream's end in the
        call that holds its last token. Nothing is held back to fill a
        batch. Then, and only then, the owners that have something to do
        are woken: at a stream's first token and at its end, so that an
        owner who sees ``ended`` knows the consumer has every token."""
        reqs = self._batch
        if not reqs:
            return
        self._batch = []
        by_sink: Dict[Any, List[tuple]] = {}
        wake: List[_Request] = []
        handed = 0
        for req in reqs:
            toks, req.batch = req.batch, None
            handed += len(toks)
            by_sink.setdefault(req.sink.call, []).append(
                (req.stream, toks, req.finished))
            # its end, or every token it has made so far: its first
            if req.finished or req.produced == len(toks):
                wake.append(req)
        for call, batch in by_sink.items():
            try:
                call(batch)
            except Exception:  # noqa: BLE001 - the consumer's, not the loop's
                pass
        for req in wake:
            req.closed = req.finished
            req.sink.wake.set()
        self.handed += handed
        self.handovers += len(by_sink)
        if it is not None:
            it["handed"] += handed
            it["handovers"] += len(by_sink)

    # ------------------------------------------------------- speculation

    def _propose(self, req: _Request, k: int) -> List[int]:
        """Draft up to `k` tokens continuing `req.ctx` — the prompt-
        lookup proposer: exact chains from the paged prefix index
        first (nearly free; strongest when many requests share
        prompts), then the most recent earlier occurrence of the
        context's own trailing n-gram (decode loops repeat themselves).
        Drafts pin nothing and may be arbitrarily wrong — the verify
        pass is the only accept authority."""
        if self.draft_source is not None:
            return [int(t) for t in self.draft_source(req.ctx, k)][:k]
        ctx = req.ctx
        plen = req.prompt.shape[1]
        if req.ctx_has_prompt and len(ctx) >= plen:
            # cross-request memory first: a finished request with the
            # SAME (adapter, prompt) decoded this exact greedy chain —
            # acceptance is ~total unless the weights moved
            mem = self._output_memory.get(
                (req.adapter_id, tuple(ctx[:plen])))
            if mem is not None and len(mem) > len(ctx) \
                    and mem[:len(ctx)] == ctx:
                return mem[len(ctx):len(ctx) + k]
        if self.kv_cache is not None and req.adapter_id is None:
            # tenant requests draft from history only: their chains
            # live under a (tenant, version) namespace this loop does
            # not re-derive per tick
            draft = self.kv_cache.propose(ctx, k)
            if draft:
                return draft
        # self n-gram lookup over the incremental index: fold in the
        # n-grams ending at positions < L-1 (the tail's own occurrence
        # must stay OUT of the index so a hit is always an EARLIER one)
        ng = req.ngram_last
        ll = len(ctx)
        for end in range(req.ng_indexed, ll - 1):
            for n in (2, 3):
                if end + 1 >= n:
                    start = end + 1 - n
                    ng[tuple(ctx[start:end + 1])] = start
        req.ng_indexed = max(req.ng_indexed, ll - 1)
        for n in (3, 2):
            if ll <= n:
                continue
            start = ng.get(tuple(ctx[-n:]))
            if start is not None:
                return ctx[start + n:start + n + k]
        return []

    def _collect_drafts(self) -> Dict[int, List[int]]:
        drafts: Dict[int, List[int]] = {}
        for slot, req in enumerate(self._slot_req):
            if req is None or req.cancelled:
                continue
            # never draft past the request's budget: tokens beyond it
            # would be verified and thrown away
            budget = req.max_new - req.produced - 1
            if budget <= 0:
                continue
            d = self._propose(req, min(self.speculate_k, budget))
            if d:
                drafts[slot] = d
        return drafts

    def _spec_event(self, ev: Dict[str, Any]) -> None:
        ev.setdefault("ts", time.time())
        ev.setdefault("engine", self.engine_id)
        with self._lock:
            self._spec_events.append(ev)
            if len(self._spec_events) > _SPEC_EVENTS_KEPT:
                del self._spec_events[:len(self._spec_events)
                                      - _SPEC_EVENTS_KEPT]

    def _drain_spec_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._spec_events = self._spec_events, []
        return out

    def _launch(self, it: Optional[Dict[str, Any]],
                behind: Optional[_Flight] = None,
                drafts: Optional[Dict[int, List[int]]] = None
                ) -> Optional[_Flight]:
        """Queue one decode step on the chip and return without reading
        it. Its tokens and positions are the newest launched tick's
        outputs where they lie (`_dev`); nothing is uploaded but the
        rows the host knows better (`_dirty`), by one small program
        queued ahead of the step (`_set_rows`). With `behind`, the tick
        in flight whose tokens the host has not read, this is the
        LOOKAHEAD: it decodes for the slots whose budget outlives that
        tick (what ends a request one tick late, an EOS or a cancel,
        leaves a row for `_land` to discard) and is not launched, None,
        where no slot's does. `drafts` makes it the speculative verify
        tick, [B, k+1] built from the host's mirror, which is whole
        because nothing is in flight; the device vectors are stale after
        it."""
        reqs: List[Optional[_Request]] = []
        rows = 0
        ring = self.ring_rows
        rows_window = 0 if ring else None
        at = self._pos + (self.speculate_k if drafts else 0)
        for slot, req in enumerate(self._slot_req):
            ahead = int(behind is not None and req is not None
                        and behind.reqs[slot] is req)
            at[slot] += ahead
            if req is None or req.produced + ahead >= req.max_new:
                reqs.append(None)
            else:
                reqs.append(req)
                rows += int(self._pos[slot]) + ahead
                if ring:
                    rows_window += min(int(self._pos[slot]) + ahead, ring)
        live = sum(r is not None for r in reqs)
        if not live:
            return None
        # what one layer's walk reads of the slab at the positions this
        # tick is launched with, ALL slots: the dead ones' one block too
        rows_read = self.max_batch * self._spec.rows \
            if self._walk_block is None else decode_rows_read(
                at, self._walk_block, self._spec.rows)
        gaps = self._gaps
        t0 = _clock(it)
        if gaps.empty_from is not None:
            gaps.mark("tick_dispatch", t0)
        with _span("engine.tick_dispatch", gaps, live=live):
            if drafts:
                tok = jnp.asarray(self._spec_tokens(drafts))
                pos, mask = jnp.asarray(self._pos), None
                self._dirty[:] = True
            else:
                if self._dirty.any():
                    fresh = np.empty((4, self.max_batch), np.int32)
                    fresh[0], fresh[1] = self._dirty, self._tokens
                    fresh[2] = self._pos
                    fresh[3] = [r is not None for r in self._slot_req]
                    self._dev = _set_rows(*self._dev, fresh)
                    self._dev_live[self._dirty] = fresh[3][self._dirty]
                    self._dirty[:] = False
                    gaps.launched()
                tok, pos, mask = self._dev
            if (self.lora_pool is not None
                    and bool(self._slot_adapter.any())):
                cache, nxt, lp, counts, pos_next = \
                    self.lora_pool.dispatch_tick(
                        lambda la: _tick(
                            self.params, self.config, self._cache, tok,
                            pos, mask, la),
                        self._slot_adapter)
            else:
                cache, nxt, lp, counts, pos_next = _tick(
                    self.params, self.config, self._cache, tok, pos, mask)
            gaps.launched(work=True)
            # what `_land` will read starts for the host NOW, behind the
            # tick: a host-bound pass finds it there a pass later, a
            # device-bound one saves the round trip after the tick.
            # `nxt` is the next tick's input too and is not donated, so
            # the copy and that tick both read it
            _start_copy(nxt)
            _start_copy(lp)
            if it is not None and counts:
                for v in counts.values():
                    _start_copy(v)
            self._cache = cache
            if not drafts:
                self._dev = (nxt, pos_next, mask)
        # the slot-states one layer's step visits: the slots the chip
        # holds live (one whose budget ends with the tick ahead among
        # them: the host learns of its end a launch before the chip)
        stepped = None
        if self.stateful:
            stepped = int(self._dev_live.sum()) \
                if self._state_walks and mask is not None \
                else self.max_batch
            self.state_slots_stepped += stepped
        self.ticks_launched += 1
        if behind is not None:
            self.lookahead_ticks += 1
        if it is not None:
            it["dispatch_ms"] += (_now() - t0) * 1e3
        return _Flight(nxt, lp, counts, reqs, live, rows, rows_window,
                       rows_read, stepped, drafts, gaps.launches)

    def _land(self, flight: _Flight, it: Optional[Dict[str, Any]],
              inflight: int = 0) -> None:
        """Read a tick's tokens and log-probabilities back (BLOCKED on
        the device; `inflight`: the ticks queued behind it meanwhile)
        and walk its slots: emit, finish, queue puts. A row whose
        request finished or was cancelled after the launch is DISCARDED:
        never emitted, never scored, never in `req.ctx`. The pass's
        record takes the tick's `live`, `live_rows` and counters, so
        that they describe one tick, the one read here, and the gap
        this landing ends (`_GapLedger.land`)."""
        gaps = self._gaps
        t0 = _clock(it)
        with _span("engine.tick_readback", gaps):
            nxt_np = np.asarray(flight.nxt)
            lp_np = np.asarray(flight.lp)
            if it is not None and flight.counts:
                # the step is over once the tokens are here: these come
                # without another wait for the device
                it.update({k: int(v) for k, v in flight.counts.items()})
        t1 = _clock(it)
        # the ledger's number of this landing (0: recorder off), and the
        # requests that take a token from it and took one from the last
        landing = streams = 0
        if it is not None:
            gaps.read(t0, t1, flight.seq, "emit")
            landing = gaps.landing + 1
        discarded = 0
        with _span("engine.emit", gaps):
            if flight.drafts:
                for req in self._slot_req if landing else ():
                    if req is not None:
                        streams += req.landed == landing - 1
                        req.landed = landing
                self._spec_emit(flight.drafts, nxt_np, lp_np)
            else:
                for slot, req in enumerate(flight.reqs):
                    if req is None:
                        continue
                    if req.finished:
                        discarded += 1
                        continue
                    if landing:
                        streams += req.landed == landing - 1
                        req.landed = landing
                    self._pos[slot] += 1
                    tok = int(nxt_np[slot])
                    self._tokens[slot] = tok
                    self._emit(req, tok, float(lp_np[slot]))
            self._hand_over(it)
        self.lookahead_discarded += discarded
        if it is not None:
            gaps.land(t1, streams, it)
            t2 = _now()
            if gaps.empty_from is not None:
                gaps.mark("bookkeeping", t2)
            it["readback_ms"] += (t1 - t0) * 1e3
            it["emit_ms"] += (t2 - t1) * 1e3
            it["discarded"] += discarded
            it.update(live=flight.live, live_rows=flight.live_rows,
                      slab_rows_read=flight.slab_rows_read,
                      inflight=inflight)
            if flight.live_rows_window is not None:
                it["live_rows_window"] = flight.live_rows_window
            if flight.state_slots_stepped is not None:
                it["state_slots_stepped"] = flight.state_slots_stepped

    def _spec_tokens(self, drafts: Dict[int, List[int]]) -> np.ndarray:
        """The widened verify tick's input: [last_token, draft...] per
        slot. Slots without a draft pad by repeating their last token —
        their column-0 output is bit-identical to the plain tick's, so
        mixed batches cost one program and zero correctness."""
        toks = np.repeat(self._tokens[:, None], self.speculate_k + 1,
                         axis=1)
        for slot, d in drafts.items():
            toks[slot, 1:1 + len(d)] = d
        return toks

    def _spec_emit(self, drafts: Dict[int, List[int]], nxt_np,
                   lp_np) -> None:
        """The verify tick's walk over the slots: emit the greedy
        chain's longest agreement with each draft."""
        self.spec_verify_ticks += 1
        m = spec_metrics()
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            proposal = drafts.get(slot, ())
            tok = int(nxt_np[slot, 0])
            self._pos[slot] += 1
            self._tokens[slot] = tok
            self._emit(req, tok, float(lp_np[slot, 0]))
            accepted = 0
            for i, d in enumerate(proposal):
                # accept d_i only while it equals the greedy chain's
                # last token AND the request is still live (eos /
                # budget finish must stop the stream exactly where the
                # sequential engine would)
                if req.finished or int(d) != tok:
                    break
                tok = int(nxt_np[slot, i + 1])
                self._pos[slot] += 1
                self._tokens[slot] = tok
                self._emit(req, tok, float(lp_np[slot, i + 1]))
                accepted += 1
            if proposal:
                # spec_emitted counts DRAFTING slots only, so
                # tokens-per-verify measures the speculation gain (an
                # undrafted slot's base token would make the metric
                # scale with batch width, not acceptance)
                self.spec_emitted += 1 + accepted
                self.spec_proposed += len(proposal)
                self.spec_accepted += accepted
                req.spec_proposed += len(proposal)
                req.spec_accepted += accepted
                m["proposed"].inc(len(proposal))
                if accepted:
                    m["accepted"].inc(accepted)
                self._spec_event({
                    "kind": "spec_accept" if accepted else "spec_reject",
                    "rid": req.rid, "slot": slot,
                    "proposed": len(proposal), "accepted": accepted})
        if self.spec_proposed:
            m["acceptance_rate"].set(
                self.spec_accepted / self.spec_proposed,
                tags={"engine": self.engine_id})

    # ------------------------------------------------------------ loop

    def _loop(self) -> None:
        name_thread(threading.current_thread().name)
        # the tick on the chip whose tokens the host has not read
        flight: Optional[_Flight] = None
        # the pass before this one admitted a prompt
        chained = False
        while not self._stopped.is_set():
            # the pass's record (module docstring); None, and no clock
            # read anywhere below, while the flight recorder is off
            it, t_top = self._open_record()
            busy = flight is not None or len(self._free) < self.max_batch
            # a swap holds from the next LAUNCH: the tick in flight
            # finishes on the weights it was launched with
            self._apply_pending_swap()
            self._apply_cancels(it)
            # a long prompt's prefill is not queued behind the tick in
            # flight (`_long_prompt_waits`): that tick is read and its
            # tokens go out first, and the pass ends with the next tick
            # launched and not read
            held = (flight is not None and not chained
                    and not self.speculate_k and self._long_prompt_waits())
            if held:
                self._land(flight, it)
                flight = None
            prefills = self.prefill_admitted
            t_admit = _clock(it)
            with _span("engine.admit", self._gaps):
                self._admit(it)
            if it is not None and it["admissions"]:
                it["admit_ms"] = (_now() - t_admit) * 1e3
            chained = self.prefill_admitted != prefills
            drafts: Dict[int, List[int]] = {}
            if self.speculate_k:
                if flight is not None:
                    # drafts are made from the host's tokens: the tick
                    # in flight is read first
                    self._land(flight, it)
                    flight = None
                    if it is not None:
                        # one landing a record: the tick launched below
                        # lands in the next
                        self._record_pass(it, t_top)
                        it, t_top = self._open_record()
                drafts = self._collect_drafts()
            ahead = None
            if drafts:
                flight = self._launch(it, drafts=drafts)
            else:
                if flight is None:
                    flight = self._launch(it)
                if flight is not None and not held:
                    ahead = self._launch(it, behind=flight)
            # one tick read a pass, the one its record describes: after
            # a hold the tick just launched is the next pass's to read
            if flight is not None and not held:
                self._land(flight, it, int(ahead is not None))
                flight = ahead
            elif not (held or busy
                      or (it is not None and it["admissions"])):
                self._gaps.idle()
                self._stopped.wait(self.idle_sleep_s)
                continue
            if it is not None:
                self._record_pass(it, t_top)

    def _open_record(self) -> Tuple[Optional[Dict[str, Any]], float]:
        """A pass's record and the clock at its top; (None, 0.0) while
        the flight recorder is off."""
        if not reqtrace.enabled():
            self._gaps.idle()
            return None, 0.0
        return {"engine_id": self.engine_id, "ts": time.time(),
                "pass": self._gaps.passes,
                "live": 0, "live_rows": 0,
                "max_batch": self.max_batch,
                "pending": self._pending.qsize(),
                "admit_ms": 0.0, "admissions": [],
                "dispatch_ms": 0.0, "readback_ms": 0.0,
                "emit_ms": 0.0, "total_ms": 0.0,
                "handed": 0, "handovers": 0,
                "inflight": 0, "discarded": 0}, _now()

    def _record_pass(self, it: Dict[str, Any], t_top: float) -> None:
        it["total_ms"] = (_now() - t_top) * 1e3
        self._gaps.passes += 1
        reqtrace.store().record_loop(it)
