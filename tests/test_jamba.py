"""`models/jamba.py` on the normal path: the program against its plain
reference at the family's `TOY` on seeded weights (full forward and loss;
prefill, then 8 tokens through the cache, logits not tokens), a prompt
fed as one run and as two, a ragged last block whose padding moves
neither the state nor the convolution's tail, the Pallas kernel in
interpret mode inside the program, the engine with two slots at
different depths, what its records and `kv_stats()` carry, each refusal's
words, the named scopes, and a slot's bytes at the published widths."""
import dataclasses
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import configs, reference  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models import jamba  # noqa: E402
from ray_tpu.models.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.generate import _model_fns, generate  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402
from ray_tpu.ops import dispatch  # noqa: E402

CONFIG = "jamba2-3b"
# float32 on both sides, different summation orders: a few 1e-6
TOL = 2e-4
TOKENS = np.random.default_rng(5).integers(1, 500, 60).astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    """The family at its `TOY` (M A M M A, a block of 4 tokens) in
    float32, every leaf moved off its initial value."""
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64),
                              dtype=jnp.float32)
    params = configs.init_params(conf, cfg, 7)
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 200))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                               x.dtype), params)
    return conf, cfg, params


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


def test_forward_and_loss_agree_with_the_reference(toy):
    conf, cfg, params = toy
    assert cfg.runs == (("M", 1), ("A", 1), ("M", 2), ("A", 1))
    assert cfg.token_block == 4 and cfg.num_kv_heads == 1
    got = jamba.jamba_forward(params, TOKENS[None, :38], cfg)
    want = reference.logits(conf, params, TOKENS[:38])
    _close(got[0], want)
    toks, tgts = TOKENS[None, :37], TOKENS[None, 1:38]
    assert float(jamba.jamba_loss(params, toks, tgts, cfg)) \
        == pytest.approx(reference.mean_loss(conf, params, toks, tgts),
                         abs=TOL)


def test_prefill_then_eight_tokens_through_the_cache(toy):
    """30 tokens prefilled (seven blocks of 4 and a ragged one of 2),
    then 8 a step: each step's logits against the reference's ONE full
    forward pass over all 38."""
    conf, cfg, params = toy
    step, init_cache, decode = _model_fns(cfg)
    want = reference.logits(conf, params, TOKENS[:38])
    logits, cache = step(params, TOKENS[None, :30], cfg, init_cache(cfg, 1),
                         0)
    assert logits.shape == (1, 1, cfg.vocab_size)   # the LAST position's
    _close(logits[0, 0], want[29])
    one_step = jax.jit(lambda t, c, at: decode(params, t, cfg, c, at))
    for pos in range(30, 38):
        logits, cache = one_step(TOKENS[pos:pos + 1], cache,
                                 jnp.asarray([pos], jnp.int32))
        _close(logits[0], want[pos])
    # a suffix through `forward_cached` (T > 1 at pos > 0) continues too
    logits, _ = step(params, TOKENS[None, 38:41], cfg, cache, 38)
    _close(logits[0, 0], reference.logits(conf, params, TOKENS[:41])[40])


def test_one_run_and_two_runs_leave_the_same_state_and_logits(toy):
    _conf, cfg, params = toy
    step, init_cache, _ = _model_fns(cfg)
    whole, c1 = step(params, TOKENS[None, :30], cfg, init_cache(cfg, 1), 0)
    _, c2 = step(params, TOKENS[None, :13], cfg, init_cache(cfg, 1), 0)
    parts, c2 = step(params, TOKENS[None, 13:30], cfg, c2, 13)
    _close(parts, whole, 1e-5)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        _close(a, b, 1e-5)


def test_padding_moves_neither_the_state_nor_the_tail(toy):
    """13 tokens in blocks of 4 (three of padding in the last) against
    the same 13 as ONE block with no padding, and against 13 steps."""
    _conf, cfg, params = toy
    step, init_cache, decode = _model_fns(cfg)
    one = dataclasses.replace(cfg, token_block=13)
    padded, cp = step(params, TOKENS[None, :13], cfg, init_cache(cfg, 1), 0)
    plain, c1 = _model_fns(one)[0](params, TOKENS[None, :13], one,
                                   init_cache(one, 1), 0)
    cs = init_cache(cfg, 1)
    # one compiled step for the thirteen
    one_step = jax.jit(lambda t, c, at: decode(params, t, cfg, c, at))
    for pos in range(13):
        steps, cs = one_step(TOKENS[pos:pos + 1], cs,
                             jnp.asarray([pos], jnp.int32))
    _close(padded, plain, 1e-5)
    _close(padded[0, 0], steps[0], 1e-5)
    for got, a, b in zip(cp, c1, cs):
        for name in got:
            rows = 13 if name in ("k", "v") else None
            _close(got[name][:, :rows], a[name][:, :rows], 1e-5)
            _close(got[name][:, :rows], b[name][:, :rows], 1e-5)
    assert cp[2]["ssm"].dtype == jnp.float32
    assert cp[2]["conv"].shape == (1, 1, 3, 128) \
        and cp[3]["conv"].shape == (1, 2, 3, 128)
    assert jamba.scan_blocks(cfg, 13) == 3 * 4 \
        and jamba.scan_blocks(cfg, 3) == 3


def test_the_kernel_in_interpret_mode_inside_the_program(toy):
    _conf, cfg, params = toy
    want = jamba.jamba_forward(params, TOKENS[None, :14], cfg)
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        got = jax.jit(jamba.jamba_forward, static_argnums=2)(
            params, TOKENS[None, :14], cfg)
    _close(got, want, 1e-5)
    scans = dispatch.kernel_choices("selective_scan")
    # one lowering a run of layers; the prompt's length in the record
    assert scans and all(c["choice"] == "pallas"
                         and tuple(c["shape"]) == (1, 4, 128, 4, 14)
                         for c in scans)


def test_the_ops_carry_their_layers_kind(toy):
    _conf, cfg, params = toy
    cache = _model_fns(cfg)[1](cfg, 2)
    text = engine_mod._tick.lower(
        params, cfg, cache, jnp.ones((2,), jnp.int32),
        jnp.ones((2,), jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("mamba1", "attention", "mlp", "head"):
        assert any(f"/{scope}/" in name for name in names), scope


def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(1, 500, n).tolist() for n in (9, 14, 6)]


def test_the_engine_with_slots_at_different_depths(toy):
    """State splice and per-slot positions: a stream that joins a running
    batch neither disturbs it nor is disturbed; the records and
    `kv_stats()` say what a slot owns and what the scan walked."""
    _conf, cfg, params = toy
    reqtrace._reset_store_for_tests()
    dispatch.reset_kernel_choices()
    engine = ContinuousBatchingEngine(params, cfg, max_batch=3)
    try:
        alone = [engine.generate(p, 10) for p in _prompts()]
        streams = []
        for p in _prompts():
            streams.append(engine.stream(p, 10))
            time.sleep(0.03)
        assert [list(s) for s in streams] == alone
        stats = engine.kv_stats()
    finally:
        engine.stop()
    records = reqtrace.store().loop_records()
    reqtrace._reset_store_for_tests()
    want = generate(params, cfg, jnp.asarray(_prompts()[0])[None],
                    max_new_tokens=10)
    assert alone[0] == [int(t) for t in want[0]]
    # three Mamba layers' float32 state [4, 128] and tail [3, 128]
    assert engine.kv_cache is None and stats["stateful"] is True
    assert stats["state_bytes_per_slot"] == 3 * (4 * 4 * 128 + 4 * 3 * 128)
    assert stats["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert stats["slab"] == [{"rows": 64, "layers": 2,
                              "bytes_per_slot": 2 * 2 * 64 * 16 * 4}]
    assert stats["ring_rows"] is None
    assert {tuple(c["shape"])[1:4] for c in stats["selective_scan"]} \
        == {(4, 128, 4)}
    entries = [a for r in records for a in r["admissions"]]
    assert len(entries) == 6
    for a in entries:
        assert a["state_bytes"] == stats["state_bytes_per_slot"]
        assert a["scan_blocks"] == 3 * -(-a["prompt_tokens"] // 4)
    assert stats["prefill_counters"]["scan_blocks"] \
        == sum(a["scan_blocks"] for a in entries)
    assert any(r["live"] >= 1 and r["live_rows"] > 0 for r in records)


@pytest.mark.parametrize("kwargs,reason", [
    ({"prefix_cache": True}, "cannot resume a recurrence"),
    ({"speculate_k": 2}, "cannot be un-advanced"),
    ({"lora_pool": object()}, "adapter pool"),
])
def test_the_engine_refuses_what_a_state_cannot_give(toy, kwargs, reason):
    _conf, cfg, params = toy
    with pytest.raises(ValueError, match="own recurrent state") as e:
        ContinuousBatchingEngine(params, cfg, max_batch=2, **kwargs)
    assert reason in str(e.value)


def test_adoption_a_cached_prefix_and_drafts_are_refused(toy):
    _conf, cfg, params = toy
    engine = ContinuousBatchingEngine(params, cfg, max_batch=2)
    kv = jnp.zeros((2, 4, 1, 16), cfg.dtype)
    try:
        with pytest.raises(ValueError, match="own recurrent state"):
            engine.adopt_prefill(4, 1, kv, kv, 4)
    finally:
        engine.stop()
    with pytest.raises(ValueError, match="from position 0"):
        engine_mod._prefill_paged(params, jnp.ones((1, 4), jnp.int32), cfg,
                                  kv, kv)
    with pytest.raises(ValueError, match="cannot verify drafted"):
        _model_fns(cfg)[2](params, jnp.ones((2, 3), jnp.int32), cfg,
                           _model_fns(cfg)[1](cfg, 2),
                           jnp.zeros((2,), jnp.int32))


def test_a_slot_at_the_published_widths():
    """2 x 33,280 rows of 2 x 128 bf16 and 26 x ([16, 5120] float32 +
    [3, 5120] bf16), the Mamba layers in runs of 7, 13 and 6: by shapes
    alone, nothing allocated."""
    conf = configs.load_config(CONFIG)
    cfg = configs.program_config(conf, 33280)
    assert cfg.runs == (("M", 7), ("A", 1), ("M", 13), ("A", 1), ("M", 6))
    cache = jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, 8))
    assert [sorted(blk) for blk in cache] == [["k", "v"]] * 2 \
        + [["conv", "ssm"]] * 3
    assert cache[3]["ssm"].shape == (8, 13, 16, 5120)
    state = sum(x.size * x.dtype.itemsize for blk in cache[2:]
                for x in jax.tree.leaves(blk))
    rows = sum(x.size * x.dtype.itemsize for blk in cache[:2]
               for x in jax.tree.leaves(blk))
    assert state == 8 * 9_318_400 and rows == 8 * 33280 * 1024
    shapes = jax.eval_shape(lambda: configs.init_params(conf, cfg, 0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_029_337_472
