"""What `smallthinker-longctx` brings to the yardstick: the control of
its `correct` at a size a test run holds (the program as configured keeps
the cell's mean limit; every matrix rounded to 8 bits, a program that
ignores the window and one whose ring is a row stale all fail it), the
bytes and operations of `smallthinker_cost` by hand on both sides of the
window, the family file's arithmetic and refusals, and the four new
readers on a hand-made trace and loop ring."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (configs, readers, reference,  # noqa: E402
                                smallthinker_cost as cost, traffic)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "smallthinker-21b-l8"
CELL = "smallthinker-longctx"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}
NEW = ["gqa_prefill_roofline.tput", "swa_tick_bytes_roofline.tput",
       "slab_rows_live_share.tput", "swa_blocks_visited_share.tput"]


# ----------------------------------------------------- the control of it

def _toy(dtype, noise=0.08, **changed):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype,
                              **changed)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper beside the
    # embedding: make the layers count, as far as the cell's limit, set
    # at the published widths, still holds bf16 at this one
    keys = iter(jax.random.split(jax.random.PRNGKey(12), 200))
    params = jax.tree.map(
        lambda x: x + (noise * jax.random.normal(
            next(keys), x.shape, jnp.float32)).astype(x.dtype), params)
    return conf, cfg, params


def _round_to_8_bits(params):
    """`benchmarks/probe_tolerance.fake_quantize`'s rounding: symmetric,
    one scale per output channel, stored back in the served type."""
    def one(w):
        if w.ndim < 2:
            return w
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(w32 / scale) * scale).astype(w.dtype)
    return jax.tree.map(one, params)


def _mean_gap(conf, cfg, served, true, stale=False):
    """The serving check's path and number: 14 tokens prefilled (longer
    than the toy's window of 4: the band hides whole blocks of 4 and
    masks part of one, and the ring has wrapped), 40 decoded through the
    rings; the program's log-probability of each token it chose against
    the reference's of the same token, the mean gap. `stale`: every ring
    turned by one row after the prefill, so that a tick overwrites the
    row of a position its window still sees and keeps one it has left."""
    step, init_cache, _ = _model_fns(cfg)
    tokens = [int(t) for t in TOKENS[:14]]
    logits, cache = step(served, jnp.asarray(tokens)[None], cfg,
                         init_cache(cfg, 1), 0)
    if stale:
        cache = [jax.tree.map(lambda x: jnp.roll(x, 1, axis=1), blk)
                 if blk["k"].shape[1] < cfg.max_seq_len else blk
                 for blk in cache]
    emitted, scores = [], []
    for pos in range(14, 54):
        lp = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        emitted.append(int(jnp.argmax(lp)))
        scores.append(float(lp[emitted[-1]]))
        logits, cache = step(served, jnp.asarray([[emitted[-1]]]), cfg,
                             cache, pos)
    ref = reference.score_emitted(conf, true, tokens, emitted)
    return float(np.mean([abs(s - r["logprob"])
                          for s, r in zip(scores, ref)]))


def test_the_cells_mean_limit_tells_the_configured_program_from_the_rest():
    limit = traffic.load_json("traffic", "longctx")["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    eight_bits = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    # a program that ignores the window: every layer sees every key
    no_window = _mean_gap(conf, dataclasses.replace(cfg, window=64),
                          params, params)
    stale = _mean_gap(conf, cfg, params, params, stale=True)
    assert good <= limit < min(eight_bits, no_window, stale), (
        good, eight_bits, no_window, stale)
    # float32 on both sides is the same function
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_widths_and_the_parameters_by_hand():
    c = configs.load_config(CONFIG)
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_ffn_hidden_size"], c["moe_num_primary_experts"],
            c["moe_num_active_primary_experts"], c["sliding_window_size"],
            c["rope_theta"], c["vocab_size"],
            c["max_position_embeddings"]) \
        == (2560, 28, 4, 128, 768, 64, 6, 4096, 1500000, 151936, 16384)
    assert c["num_hidden_layers"] == 8
    assert c["rope_layout"] == c["sliding_window_layout"] == [0, 1, 1, 1] * 2
    assert c["reduced"] == ["num_hidden_layers", "rope_layout",
                            "sliding_window_layout"]
    shape = configs.model_shape(c)
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512        # 20.97 M
    expert = 3 * 2560 * 768                        # 5.898 M
    head = 151936 * 2560
    assert shape["expert_params"] == expert == 5_898_240
    assert shape["always_params"] == 8 * (attn + 2 * 2560) + 2560 + head
    assert shape["matmul_params"] \
        == 8 * (attn + 2560 * 64 + 6 * expert) + head
    assert (shape["layers_global"], shape["layers_window"],
            shape["window"], shape["row_bytes"], shape["experts_held"],
            shape["expert_layers"]) == (2, 6, 4096, 2048, 64, 8)
    # 8 layers of 398.6 M and the embedding and head: 7.93 GB in bf16
    held = cost.held_bytes(shape)
    assert held == 2 * (8 * (attn + 2 * 2560 + 64 * expert) + 2560
                        + 2 * head) + 4 * 8 * 2560 * 64
    assert 7.92e9 < held < 7.95e9


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    assert configs.program_config(conf, 16384).window_layout \
        == (0, 1, 1, 1) * 2
    for changed in ({"tie_word_embeddings": True},
                    {"attention_bias": True},
                    {"moe_primary_router_apply_softmax": False},
                    {"norm_topk_prob": False},
                    {"rope_scaling": {"type": "yarn", "factor": 4}},
                    {"rope_layout": [0, 1, 1, 1]},
                    {"num_hidden_layers": 12}):
        with pytest.raises(ValueError, match="SmallThinker path has no"):
            configs.program_config({**conf, **changed}, 16384)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 16385)
    toy = configs.family(conf).toy
    assert set(toy) <= set(conf) and toy["sliding_window_size"] == 4
    assert len(toy["rope_layout"]) == toy["num_hidden_layers"] >= 5


# ------------------------------------------------------ the cost by hand

def test_the_slab_and_the_tick_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    # 2 x 16,384 + 6 x 4,096 rows of 2 KB a slot
    assert cost.slab_rows(shape, 16384) == 57_344
    assert cost.slot_bytes(shape, 16384) == 117_440_512
    # a cell of 2,048 positions has no ring: every layer keeps them all
    assert cost.slab_rows(shape, 2048) == 8 * 2048
    # twelve layers, as published in three periods: 176.2 MB
    twelve = dict(shape, layers_global=3, layers_window=9)
    assert cost.slot_bytes(twelve, 16384) == 176_160_768
    assert cost.expert_bytes(shape) == 11_796_480
    # a tick of 16 slots at 9,000 rows each: 4,096 of them in a window
    rows = cost.live_rows_read(shape, 144_000, 65_536)
    assert rows == 2 * 144_000 + 6 * 65_536
    assert cost.tick_bytes(shape, 400, 144_000, 65_536) \
        == 2 * shape["always_params"] + 4 * 8 * 2560 * 64 \
        + 400 * 11_796_480 + 2048 * rows


@pytest.mark.parametrize("tokens,window,pairs", [
    (2048, 0, 2048 * 2049 / 2), (2048, 4096, 2048 * 2049 / 2),
    (4096, 4096, 4096 * 4097 / 2),
    # past the window every query sees 4,096 keys
    (5120, 4096, 4096 * 4097 / 2 + 1024 * 4096),
    (15872, 4096, 4096 * 4097 / 2 + 11776 * 4096),
    (15872, 0, 15872 * 15873 / 2)])
def test_the_prompt_kernels_operations_by_hand(tokens, window, pairs):
    shape = configs.model_shape(configs.load_config(CONFIG))
    assert cost.visible_pairs(tokens, window) == pairs
    # 28 heads, q . k and p . v over 128
    assert cost.gqa_prefill_flops(shape, tokens, window) \
        == 28 * 4 * 128 * pairs
    # by summing a dense mask, at a size that can be
    if tokens == 2048:
        at = np.arange(300)
        seen = (at[None] <= at[:, None]) & (at[None] > at[:, None] - 100)
        assert cost.visible_pairs(300, 100) == seen.sum()


def test_the_cell_is_sized_as_the_issue_asked():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "longctx", 1)
    mix = traffic.load_json("traffic", "longctx")
    assert (mix["loop"], mix["clients"], mix["max_batch"],
            mix["max_queue_depth"], mix["max_seq_len"],
            mix["pool_requests_per_s"], mix["drain_s"],
            mix["request_timeout_s"], mix["replays"]) \
        == ("closed", 16, 16, 16, 16384, 6, 30, 120, 2)
    assert mix["prompt_tokens"] == {
        "values": [5120, 8192, 12288, 15872],
        "weights": [0.4, 0.3, 0.2, 0.1]}
    assert mix["output_tokens"] == {"values": [96, 208, 336],
                                    "weights": [0.3, 0.4, 0.3]}
    # a block of 20 holds the shares exactly
    assert traffic.apportion(20, [0.4, 0.3, 0.2, 0.1]) == [8, 6, 4, 2]
    assert traffic.apportion(20, [0.3, 0.4, 0.3]) == [6, 8, 6]
    # every prompt is longer than the window: the checked one too
    assert min(mix["prompt_tokens"]["values"]) > 4096 + 512
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert judged == {"serve_tokens_per_s", "setup_s"}
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW and all(
        m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        for m in mine)
    # weights and slab: over a quarter of the chip
    shape = configs.model_shape(configs.load_config(CONFIG))
    assert cost.held_bytes(shape) + 16 * cost.slot_bytes(shape, 16384) \
        > 0.55 * 16e9


# ------------------------------------------------------------ the readers

def _admission(prompt_tokens, blocks, causal):
    return {"rid": 0, "prompt_tokens": prompt_tokens,
            "suffix_tokens": prompt_tokens, "reused_tokens": 0,
            "lookup_ms": 0.0, "prefill_ms": 50.0, "commit_ms": 0.0,
            "commit_dispatches": 0, "commit_blocks": 0, "splice_ms": 0.5,
            "moe_pairs_held": 6 * 8 * prompt_tokens, "moe_experts_hit": 512,
            "moe_rows_max": 900, "attn_blocks": blocks,
            "attn_blocks_causal": causal}


def _record(ts, live, admissions=(), **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live,
            "max_batch": 16, "pending": 0, "admit_ms": 0.0,
            "admissions": list(admissions), "dispatch_ms": 1.0,
            "readback_ms": 10.0, "emit_ms": 0.5, "total_ms": 12.0, **more}


def _summary(store):
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})


def _cell():
    return {"seconds": 2.0, "conf": configs.load_config(CONFIG),
            "traffic": traffic.load_json("traffic", "longctx"),
            "peaks": PEAKS}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    for rec in [
            # before the window: the reference check's prefill
            _record(T0 - 4.0, 1, [_admission(5120, 8 * 55, 8 * 55)],
                    live_rows=5130, live_rows_window=4096,
                    moe_experts_hit=40),
            _record(T0 + 0.1, 12, [_admission(5120, 434, 440)],
                    live_rows=100_000, live_rows_window=49_152,
                    moe_experts_hit=380),
            _record(T0 + 0.2, 14, [_admission(15872, 2450, 3968)],
                    live_rows=140_000, live_rows_window=57_344,
                    moe_experts_hit=400),
            # a pass with no ring's count: left out of the ring's readers
            _record(T0 + 0.3, 14, live_rows=141_000, moe_experts_hit=410),
            # after the window
            _record(T0 + 5.0, 1, [_admission(8192, 10, 10)],
                    live_rows=300, live_rows_window=300,
                    moe_experts_hit=10)]:
        store.record_loop(rec)
    _summary(store)
    ms = 1e6
    trace = {
        "window": (0.0, 3000 * ms),
        "programs": {"_tick": [("jit__tick(3)", 10 * ms, 9 * ms),
                               ("jit__tick(3)", 400 * ms, 11 * ms)]},
        "ops": {
            "gqa_prefill_w0_t5120.1": [
                ("gqa_prefill_w0_t5120.1", 100 * ms, 4 * ms),
                ("gqa_prefill_w0_t5120.1", 900 * ms, 4 * ms)],
            "gqa_prefill_w4096_t5120.2": [
                ("gqa_prefill_w4096_t5120.2", 110 * ms, 4 * ms)],
            "gqa_prefill_w4096_t15872": [
                ("gqa_prefill_w4096_t15872", 500 * ms, 20 * ms)],
            "grouped_stream.3": [("grouped_stream.3", 12 * ms, 3 * ms)],
            "fusion.7": [("fusion.7", 150 * ms, 30 * ms)]}}
    yield {"phases": [{}], "trace": trace, "requests": [], "cell": _cell()}
    reqtrace._reset_store_for_tests()


def test_gqa_prefill_roofline_reads_window_and_length_from_the_name(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    flops = 2 * cost.gqa_prefill_flops(shape, 5120, 0) \
        + cost.gqa_prefill_flops(shape, 5120, 4096) \
        + cost.gqa_prefill_flops(shape, 15872, 4096)
    want = 100.0 * flops / 197e12 / 32e-3
    assert readers.load_reader(NEW[0])(obs) == pytest.approx(want)
    assert 10.0 < want < 100.0


def test_the_rings_readers_take_the_windows_decode_passes(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    least = (cost.tick_bytes(shape, 380, 100_000, 49_152)
             + cost.tick_bytes(shape, 400, 140_000, 57_344)) / 2
    assert readers.load_reader(NEW[1])(obs) \
        == pytest.approx(100.0 * least / 8.19e11 / 10e-3)
    read = (2 * 100_000 + 6 * 49_152 + 2 * 140_000 + 6 * 57_344) / 2
    assert readers.load_reader(NEW[2])(obs) \
        == pytest.approx(100.0 * read / (16 * 57_344))
    # the window's two admissions, not the check's nor the drain's
    assert readers.load_reader(NEW[3])(obs) \
        == pytest.approx(100.0 * (434 / 440 + 2450 / 3968) / 2)
    # the plain readers the cell reports read the same ring
    assert readers.load_reader("tick_live_slots_mean.tput")(obs) \
        == pytest.approx(40 / 3)
    assert readers.load_reader("expert_rows_max_over_mean.tput")(obs) \
        == pytest.approx(np.mean([900 * 512 / (48 * 5120),
                                  900 * 512 / (48 * 15872)]))


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A program without the kernel, the ring or the counters (the
    parent's), a run without a trace: no number, no error."""
    reqtrace._reset_store_for_tests()
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": _cell(), "trace": None,
                 "requests": []}) is None
    trace = {"window": (0.0, 3e9),
             "programs": {"_tick": [("jit__tick(1)", 1e8, 5e6)]},
             "ops": {"mla_prefill_t1024": [("mla_prefill_t1024", 1.1e8,
                                            1e6)]}}
    store = reqtrace.store()
    plain = {k: v for k, v in _admission(1024, 0, 0).items()
             if not k.startswith(("moe_", "attn_"))}
    store.record_loop(_record(T0 + 0.1, 1, [plain], live_rows=100))
    _summary(store)
    assert read({"phases": [{}], "cell": _cell(), "trace": trace,
                 "requests": []}) is None
    reqtrace._reset_store_for_tests()
