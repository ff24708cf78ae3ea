"""Speculative decoding + int8 KV blocks (ISSUE-15 acceptance
surface): greedy bit-identity to the unspeculated engine under full /
partial / zero draft acceptance, refcount rollback leaving the pool
leak-free, int8 pool equivalence (rtol contract) + capacity doubling,
the disaggregated and LoRA mixed-batch paths, and the
one-set-of-numbers consistency check across state API / CLI /
dashboard / Prometheus / timeline markers.

The `speculate` marker tags the scenarios; everything here is
tier-1-safe on CPU — the e2e surface check runs on a virtual cluster
with log_to_driver=0 per the established fixture pattern."""
from __future__ import annotations

import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.kvcache import (PagedKVCache, kv_int8_default,
                                    resolve_pool_config)
from ray_tpu.models.llama import LlamaConfig, llama_init

pytestmark = pytest.mark.speculate

CFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
BS = 4


@pytest.fixture(scope="module")
def model():
    return llama_init(CFG, jax.random.PRNGKey(0))


def _engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("kv_pool_blocks", 32)
    return ContinuousBatchingEngine(model, CFG, **kw)


def _reference(model, prompt, n):
    return np.asarray(generate(model, CFG, jnp.asarray([prompt],
                                                       jnp.int32),
                               max_new_tokens=n))[0].tolist()


def _prompts(seed=3, n=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, ln).tolist()
            for ln in rng.integers(6, 20, n)]


# ------------------------------------------------ acceptance spectrum

def _scripted_source(chain, corrupt_at=None):
    """A draft source replaying the TRUE greedy chain (full
    acceptance), optionally corrupting one position (partial), for the
    single-request tests that pin the acceptance spectrum."""
    def src(ctx, k):
        if chain[:len(ctx)] != ctx:
            return []
        out = list(chain[len(ctx):len(ctx) + k])
        if corrupt_at is not None and len(out) > corrupt_at:
            out[corrupt_at] = (out[corrupt_at] + 1) % CFG.vocab_size
        return out
    return src


@pytest.mark.parametrize("mode", ["full", "partial", "zero"])
def test_bit_identity_across_acceptance_spectrum(model, mode):
    """The oracle: speculated output == unspeculated greedy output
    whether the drafts are perfect, half-wrong, or garbage — and the
    acceptance counters reflect which it was."""
    prompt = _prompts(seed=7, n=1)[0]
    ref = _reference(model, prompt, 24)
    chain = prompt + ref
    src = {"full": _scripted_source(chain),
           "partial": _scripted_source(chain, corrupt_at=2),
           "zero": lambda ctx, k: [0] * k}[mode]
    eng = _engine(model, speculate_k=4, draft_source=src)
    try:
        assert eng.generate(prompt, 24) == ref
        st = eng.speculation_stats()
    finally:
        eng.stop()
    assert st["spec_proposed"] > 0
    if mode == "full":
        assert st["acceptance_rate"] == 1.0
        # k accepted drafts + the verify's own token per tick
        assert st["tokens_per_verify"] > 4.0
    elif mode == "zero":
        assert st["spec_accepted"] == 0
    else:
        assert 0.0 < st["acceptance_rate"] < 1.0


def test_default_proposer_bit_identity_and_memory(model):
    """The real prompt-lookup proposer (prefix-index chains, output
    memory, self n-gram) against the unspeculated engine: identical
    outputs over a mixed workload with repeated prompts, and the
    repeat drafts actually accept (the output-memory path — greedy
    decode is a function of the prompt, so the second pass of a prompt
    should draft at ~full acceptance)."""
    prompts = _prompts(seed=11, n=3)
    jobs = prompts + prompts  # repeats hit the output memory
    base = _engine(model)
    try:
        want = [base.generate(p, 20) for p in jobs]
    finally:
        base.stop()
    eng = _engine(model, speculate_k=4)
    try:
        got = [eng.generate(p, 20) for p in jobs]
        st = eng.speculation_stats()
    finally:
        eng.stop()
    assert got == want
    assert st["spec_proposed"] > 0 and st["spec_accepted"] > 0
    assert st["acceptance_rate"] > 0.4


def test_concurrent_mixed_batch_bit_identity(model):
    """Slots at different depths, some drafted and some not, share one
    widened verify program — concurrent speculated outputs must equal
    the sequentially computed references."""
    prompts = _prompts(seed=13, n=4)
    want = {i: _reference(model, p, 16) for i, p in enumerate(prompts)}
    eng = _engine(model, speculate_k=4)
    got = {}
    try:
        ths = [threading.Thread(
            target=lambda i=i, p=p: got.update({i: eng.generate(p, 16)}))
            for i, p in enumerate(prompts)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60.0)
    finally:
        eng.stop()
    assert got == want


# -------------------------------------------------- rollback / pool

def test_rollback_leaves_pool_leak_free(model):
    """Rejected drafts roll back by refcount, never by copy: after a
    speculated workload over shared prefixes (hits, COW, rejections),
    no pin survives and every block is either free or cached — the
    pool reconciles exactly."""
    shared = [41, 42, 43, 44, 45, 46, 47, 48]
    eng = _engine(model, speculate_k=4)
    try:
        for i in range(4):
            eng.generate(shared + [60 + i], 12)
        for i in range(2):  # repeats: memory drafts + cache hits
            eng.generate(shared + [60 + i], 12)
        st = eng.kv_stats()
    finally:
        eng.stop()
    assert st["spec_verify_ticks"] > 0
    assert st["pinned_blocks"] == 0
    assert st["free_blocks"] + st["cached_blocks"] == st["num_blocks"]


def test_weight_swap_paths_with_speculation(model):
    """Mid-stream and between-request weight swaps under speculation:
    a same-weights swap mid-stream must not perturb the stream (the
    swap machinery runs — invalidation, output-memory clear — but the
    function being decoded is unchanged), and a post-swap request must
    match a fresh engine on the new weights, never a stale draft's
    acceptance."""
    params_b = jax.tree.map(lambda x: x * 1.25, model)
    prompt = _prompts(seed=17, n=1)[0]
    ref_a = _reference(model, prompt, 24)
    eng = _engine(model, speculate_k=4)
    try:
        eng.generate(prompt, 24)            # seeds the output memory
        stream = eng.stream(prompt, 24)
        first = next(stream)
        applied = eng.update_params(model, version=2)  # same weights
        rest = list(stream)
        assert applied.wait(timeout=30.0)
        assert [first] + rest == ref_a
        assert len(eng._output_memory) <= 1  # cleared at the swap
        # different weights: post-swap outputs == fresh params_b engine
        applied = eng.update_params(params_b, version=3)
        assert applied.wait(timeout=30.0)
        fresh = _engine(params_b, prefix_cache=False)
        try:
            assert eng.generate(prompt, 16) == fresh.generate(prompt, 16)
        finally:
            fresh.stop()
    finally:
        eng.stop()


# ------------------------------------------------------- int8 blocks

def test_int8_capacity_doubling_and_knobs(monkeypatch):
    bs, pb = resolve_pool_config(CFG, None, None, slots=4)
    bs8, pb8 = resolve_pool_config(CFG, None, None, slots=4, int8=True)
    assert bs8 == bs and pb8 == 2 * pb
    # an explicit pool size is always honored as-is
    assert resolve_pool_config(CFG, None, 40, int8=True)[1] == 40
    assert kv_int8_default() is False
    monkeypatch.setenv("RAY_TPU_KV_INT8", "1")
    assert kv_int8_default() is True


def test_int8_pool_roundtrip_within_rtol(model):
    """The int8 tolerance contract: commit a real prefill into the
    quantized pool and gather it back — the dequantized KV (and the
    logits computed from it) stay within rtol of the exact fill, while
    everything outside the pool is bit-exact plumbing."""
    from ray_tpu.models.engine import _prefill_paged

    prompt = np.asarray(_prompts(seed=19, n=1)[0] * 2, np.int32)[None]
    empty = jnp.zeros((CFG.num_layers, 0, CFG.num_kv_heads,
                       CFG.head_dim), jnp.float32)
    ref_logits, ck, cv, _, _ = _prefill_paged(model, prompt, CFG, empty,
                                              empty)
    kv = PagedKVCache(CFG, block_size=BS, num_blocks=32, int8=True)
    m = kv.lookup(prompt[0], max_tokens=prompt.shape[1] - 1)
    table = kv.commit(prompt[0], ck, cv, m)
    m2 = kv.lookup(prompt[0], max_tokens=prompt.shape[1] - 1)
    assert m2.tokens > 0
    gk, gv = kv.gather(m2)
    # KV-level: dequantized blocks stay close to the exact rows
    ref_k = np.asarray(ck[:, :m2.tokens], np.float32)
    got_k = np.asarray(gk, np.float32)
    denom = np.abs(ref_k).max() + 1e-9
    assert np.abs(got_k - ref_k).max() / denom < 0.05
    # logit-level: a suffix prefill over the dequantized prefix stays
    # within the rtol contract of the exact-prefix prefill
    q_logits, _, _, _, _ = _prefill_paged(model, prompt[:, m2.tokens:],
                                          CFG, gk, gv)
    ref = np.asarray(ref_logits[0, :CFG.vocab_size], np.float32)
    got = np.asarray(q_logits[0, :CFG.vocab_size], np.float32)
    assert np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9) < 0.05
    kv.release(table)
    kv.release(m2.bids)
    st = kv.stats()
    assert st["int8"] and st["capacity_factor"] == 2
    assert st["pinned_blocks"] == 0


def test_int8_engine_serves_with_prefix_reuse(model):
    """An int8-pool engine serves end-to-end: shared prefixes hit, the
    pool reports the int8 flag, and the uncached path (no gather —
    bit-exact plumbing) matches the fp engine exactly."""
    shared = [71, 72, 73, 74, 75, 76, 77, 78]
    eng = _engine(model, kv_int8=True, speculate_k=4)
    base = _engine(model, prefix_cache=False)
    try:
        first = eng.generate(shared + [80], 10)   # miss: no gather
        assert first == base.generate(shared + [80], 10)
        again = eng.generate(shared + [81], 10)   # hit: dequant path
        assert len(again) == 10
        st = eng.kv_stats()
    finally:
        eng.stop()
        base.stop()
    assert st["int8"] is True and st["kv_int8"] is True
    assert st["hits"] + st["partial_hits"] >= 1


# ----------------------------------------------------- disagg + LoRA

def test_disagg_spec_decode_bit_identical(model):
    """A speculating decode tier adopting prefilled KV: outputs match
    the colocated unspeculated engine bit-for-bit, and drafting works
    off the transfer's prompt_tokens (repeat prompts accept). (The
    decode-never-compiles-prefill assertion lives in test_disagg where
    the tiers are separate processes — in-process tiers share one jit
    cache.)"""
    from ray_tpu.serve.disagg import (DecodeServer, DisaggRouter,
                                      PrefillServer)

    base = _engine(model)
    prompts = _prompts(seed=23, n=2)
    jobs = prompts + prompts
    try:
        want = [base.generate(p, 14) for p in jobs]
    finally:
        base.stop()
    pf = PrefillServer(model, CFG, kv_block_size=BS, kv_pool_blocks=32)
    dec = DecodeServer(model, CFG, max_batch=4, speculate_k=4)
    router = DisaggRouter(decode=[dec], prefill=[pf])
    try:
        got = [router.generate(p, 14) for p in jobs]
        st = dec.stats()
    finally:
        dec.stop()
    assert got == want
    assert st["speculation"]["spec_accepted"] > 0


def test_lora_mixed_batch_spec_bit_identical(model):
    """Mixed-tenant batches under speculation: per-slot adapter deltas
    apply at every verify position, so speculated mixed batches equal
    the unspeculated mixed batches token-for-token."""
    from ray_tpu.serve.lora import (AdapterPool, LocalAdapterSource,
                                    make_lora_adapter)

    adapters = {"t1": make_lora_adapter(CFG, 4, seed=1),
                "t2": make_lora_adapter(CFG, 4, seed=2)}
    prompts = _prompts(seed=29, n=2)
    jobs = [(prompts[0], None), (prompts[1], "t1"),
            (prompts[0], "t2"), (prompts[1], None)]

    def run(k):
        pool = AdapterPool(CFG, slots=4, rank_max=4,
                           source=LocalAdapterSource(dict(adapters)))
        eng = _engine(model, speculate_k=k, lora_pool=pool)
        out = {}
        try:
            ths = [threading.Thread(
                target=lambda i=i, p=p, t=t:
                out.update({i: eng.generate(p, 16, adapter_id=t)}))
                for i, (p, t) in enumerate(jobs)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(timeout=60.0)
        finally:
            eng.stop()
        return out, eng.speculation_stats()

    want, _ = run(0)
    got, st = run(4)
    assert got == want
    assert st["spec_verify_ticks"] > 0


# ----------------------------------------------- e2e surface check

@pytest.fixture
def spec_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def test_all_surfaces_report_consistent_numbers(spec_cluster, capsys):
    """speculation_stats() / CLI / /api/speculation / Prometheus /
    the kvcache timeline lane's spec markers all report the SAME
    proposal/acceptance numbers for one engine's workload."""
    import time as time_mod
    import urllib.request

    from ray_tpu.dashboard import DashboardServer
    from ray_tpu.scripts import cli
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    w = spec_cluster
    model = llama_init(CFG, jax.random.PRNGKey(0))
    eng = _engine(model, speculate_k=4)
    try:
        p = _prompts(seed=31, n=1)[0]
        for _ in range(3):  # repeats: memory drafts -> spec counters
            eng.generate(p, 14)
        eng.publish_kv_telemetry(force=True)
        local = eng.speculation_stats()
    finally:
        eng.stop()
    metrics_mod.flush()
    assert local["spec_proposed"] > 0 and local["spec_accepted"] > 0

    key = f"{w.worker_id[:12]}:{eng.engine_id}"
    deadline = time_mod.monotonic() + 10.0
    while True:
        st = state.speculation_stats()
        mine = st["engines"].get(key)
        if mine is not None and \
                mine["spec_proposed"] == local["spec_proposed"]:
            break
        assert time_mod.monotonic() < deadline, st
        time_mod.sleep(0.1)
    for k in ("spec_proposed", "spec_accepted", "spec_verify_ticks",
              "spec_emitted_tokens"):
        assert mine[k] == local[k], k
    assert st["totals"]["spec_accepted"] == local["spec_accepted"]
    assert mine["speculate_k"] == 4

    # CLI (same conductor snapshot)
    host, port = w.conductor_address
    cli.main(["speculate", "--json", "--address", f"{host}:{port}"])
    cli_out = json.loads(capsys.readouterr().out)
    assert cli_out["totals"]["spec_proposed"] == local["spec_proposed"]

    # dashboard /api/speculation
    srv = DashboardServer(w.conductor_address, port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/api/speculation",
                                    timeout=10.0) as r:
            dash = json.loads(r.read())
    finally:
        srv.stop()
    assert dash["totals"]["spec_accepted"] == local["spec_accepted"]
    spec_events = [e for e in dash["events"]
                   if e.get("engine") == eng.engine_id]
    assert spec_events, dash["events"]
    assert sum(e["accepted"] for e in spec_events) == \
        local["spec_accepted"]

    # Prometheus exposition: spec families exist and cover this work
    prom = state.prometheus_metrics()
    assert "ray_tpu_spec_proposed_total" in prom
    assert "ray_tpu_spec_acceptance_rate" in prom
    accepted_total = sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ray_tpu_spec_accepted_total{"))
    assert accepted_total >= local["spec_accepted"]

    # merged timeline: the spec markers get their own speculation lane
    # (they ride the kvcache event channel but render separately)
    trace = state.timeline(merged=True)
    markers = [e for e in trace if e.get("cat") == "speculation"
               and e.get("args", {}).get("engine") == eng.engine_id
               and e.get("tid", "").startswith("spec_")]
    assert markers
    assert all(m["ph"] == "i" and m["pid"] == "speculation"
               for m in markers)
    # ...and they no longer double-render on the kvcache lane
    assert not any(e.get("cat") == "kvcache"
                   and e.get("tid", "").startswith("spec_")
                   for e in trace)
