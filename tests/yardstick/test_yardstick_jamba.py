"""What `jamba2-docqa-32k` brings to the yardstick: the control of its
`correct` at a size a test run holds (the program as configured keeps the
cell's mean limit; every matrix rounded to 8 bits, a scan whose padding
feeds the state and a convolution tail that takes the padding all fail
it), the bytes and operations of `jamba_cost` by hand, the family file's
arithmetic and refusals, the cell's sizes as the issue gave them, and the
three new readers on a hand-made trace and loop ring."""
import dataclasses
import json
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

from benchmarks.harness import (configs, jamba_cost as cost,  # noqa: E402
                                readers, reference, traffic)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "jamba2-3b"
CELL = "jamba2-docqa-32k"
MIX = "docqa-32k"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}
NEW = ["selective_scan_roofline.tput", "scan_share_of_prefill.tput",
       "ssm_tick_bytes_roofline.tput"]
# the catalog row's `config` (model-configs guide, AI21-Jamba2-3B)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
THERE = ["compiles_in_window.tput", "prefill_device_ms_per_ktok.tput",
         "tick_device_ms_mean.tput", "tick_live_slots_mean.tput",
         "device_idle_share.tput", "chip_empty_share.tput",
         "ttft_collision_share.tput", "client_ttft_p50_ms.tput"]
# NOT `gqa_prefill_roofline.tput`, which ISSUE 39 lists too: its reader
# reads this family's shape (below), but `test_yardstick_smallthinker.py`
# holds its `workloads` to SmallThinker's cell alone


# ----------------------------------------------------- the control of it

def _toy(dtype, noise=0.3, **changed):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype,
                              **changed)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper and the
    # logits flat: at 0.3 the layers count and the gaps read what they
    # read at the published widths on the chip (bf16 0.022 here against
    # 0.026 to 0.033 there, 8-bit matrices 0.14 against 0.13 to 0.19),
    # so the cell's own limit is what is tested
    keys = iter(jax.random.split(jax.random.PRNGKey(12), 200))
    params = jax.tree.map(
        lambda x: x + (noise * jax.random.normal(
            next(keys), x.shape, jnp.float32)).astype(x.dtype), params)
    return conf, cfg, params


def _round_to_8_bits(params):
    """Every matrix through the probe's own rounding (symmetric, one scale
    per output channel, stored back as served), on a copy: it donates."""
    from benchmarks.probe_state_precision import round_in_place
    return round_in_place(jax.tree.map(jnp.copy, params), 8, 2)


def _mean_gap(conf, cfg, served, true, spoil=None):
    """The serving check's path and number: 14 tokens prefilled (three
    blocks of the toy's 4 and a RAGGED one of 2), 40 decoded through the
    cache; the program's log-probability of each token it chose against
    the reference's of the same token, the mean gap. `spoil(cache)`
    stands for a fault of the prefill: what the padding would have left
    behind."""
    step, init_cache, _ = _model_fns(cfg)
    prefill = jax.jit(lambda p, t, c: step(p, t, cfg, c, 0))
    decode = jax.jit(lambda p, t, c, pos: step(p, t, cfg, c, pos))
    tokens = [int(t) for t in TOKENS[:14]]
    logits, cache = prefill(served, jnp.asarray(tokens)[None],
                            init_cache(cfg, 1))
    if spoil is not None:
        cache = spoil(cache)
    emitted, scores = [], []
    for pos in range(14, 54):
        lp = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        emitted.append(int(jnp.argmax(lp)))
        scores.append(float(lp[emitted[-1]]))
        logits, cache = decode(served, jnp.asarray([[emitted[-1]]]), cache,
                               jnp.int32(pos))
    ref = reference.score_emitted(conf, true, tokens, emitted)
    return float(np.mean([abs(s - r["logprob"])
                          for s, r in zip(scores, ref)]))


def _decayed(cache):
    """Two steps of padding that DECAY the state (dt > 0 on padding)."""
    return [blk if "k" in blk else dict(blk, ssm=blk["ssm"] * 0.5)
            for blk in cache]


def _padded_tail(cache):
    """The convolution's tail taken from the padded block's end: the
    padding's zeros in place of the last real inputs."""
    return [blk if "k" in blk else dict(blk, conv=jnp.concatenate(
        [blk["conv"][:, :, 2:], jnp.zeros_like(blk["conv"][:, :, :2])], 2))
        for blk in cache]


def test_the_cells_mean_limit_tells_the_configured_program_from_the_rest():
    limit = traffic.load_json("traffic", MIX)["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    eight_bits = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    decayed = _mean_gap(conf, cfg, params, params, _decayed)
    tail = _mean_gap(conf, cfg, params, params, _padded_tail)
    assert good <= limit < min(eight_bits, decayed, tail), (
        good, eight_bits, decayed, tail)
    # float32 on both sides is the same function
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_keys_and_the_parameters_by_hand():
    c = configs.load_config(CONFIG)
    assert c["source"] == ("https://huggingface.co/ai21labs/AI21-Jamba2-3B/"
                           "blob/main/config.json")
    assert c["reduced"] == ["max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert c["max_position_embeddings"] == 33280 \
        < PUBLISHED["max_position_embeddings"]
    shape = configs.model_shape(c)
    mixer = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    rest = 4 * 5120 + 5120 + 5120 + 16 * 5120 + 5120 + 160 + 16 + 16
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    mlp = 3 * 2560 * 8192
    assert (mixer + rest, attn, mlp) == (41_241_792, 13_762_560, 62_914_560)
    assert mixer + rest + mlp + 5120 == 104_161_472
    assert attn + mlp + 5120 == 76_682_240
    emb = 65536 * 2560
    assert shape["params"] == 26 * 104_161_472 + 2 * 76_682_240 + emb \
        + 2560 == 3_029_337_472
    assert shape["matmul_params"] == 26 * mixer + 2 * attn + 28 * mlp + emb
    assert (shape["layers"], shape["heads"], shape["kv_heads"],
            shape["head_dim"], shape["d_model"], shape["vocab"],
            shape["mamba_layers"], shape["attention_layers"],
            shape["d_inner"], shape["d_state"], shape["token_block"]) \
        == (28, 20, 1, 128, 2560, 65536, 26, 2, 5120, 16, 2048)
    assert shape["float32_params"] == 26 * 18 * 5120
    # [5120, 16] float32 and the convolution's last 3 inputs, 26 times
    assert shape["state_bytes"] == 26 * (5120 * 16 * 4 + 3 * 5120 * 2) \
        == 9_318_400
    assert shape["row_bytes"] == 2 * 2 * 128 * 2 == 1024
    assert 6.06e9 < cost.held_bytes(shape) < 6.07e9


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    cfg = configs.program_config(conf, 33280)
    assert (cfg.attn_period, cfg.attn_offset, cfg.num_kv_heads,
            cfg.token_block) == (14, 7, 1, 2048)
    for changed in ({"num_experts": 16}, {"num_experts_per_tok": 2},
                    {"sliding_window": 4096},
                    {"tie_word_embeddings": False},
                    {"mamba_proj_bias": True}, {"mamba_conv_bias": False},
                    {"hidden_act": "gelu"}, {"num_logits_to_keep": 2},
                    {"num_attention_heads": 24}):
        with pytest.raises(ValueError, match="Jamba path has no"):
            configs.program_config({**conf, **changed}, 33280)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 33281)
    toy = configs.family(conf).toy
    assert set(toy) <= set(conf)
    kinds = [i % toy["attn_layer_period"] == toy["attn_layer_offset"]
             for i in range(toy["num_hidden_layers"])]
    # both kinds of layer, a period shorter than the depth, a block
    # shorter than the rehearsal's prompts of 8 and 16
    assert any(kinds) and not all(kinds)
    assert toy["attn_layer_period"] < toy["num_hidden_layers"]
    assert toy["prefill_token_block"] < 8


# ------------------------------------------------------ the cost by hand

def test_the_scan_and_the_tick_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    # a token and layer: u', z, y in bf16 and dt in float32 over 5,120
    # channels, B and C of 16 in float32
    per_token = 5120 * (2 + 2 + 2 + 4) + 2 * 16 * 4
    assert per_token == 51_328
    # A [16, 5120] and D [5120] once, the state in and out once
    once = 4 * (16 * 5120 + 5120) + 2 * 4 * 16 * 5120
    assert once == 1_003_520
    for tokens in (4000, 8192, 32768):
        assert cost.scan_bytes(shape, tokens) == tokens * per_token + once
        assert cost.scan_elementwise_ops(shape, tokens) \
            == tokens * 5120 * (7 * 16 + 8)
    assert cost.scan_bytes(shape, 32768) == 1_682_919_424
    # blocks of 2,048 tokens: a 4,000-token prompt walks two
    assert [cost.scan_calls(shape, t) for t in (1000, 2048, 4000, 8192,
                                                32768)] == [1, 1, 2, 4, 16]
    # a tick of 7 live slots at 12,000 rows each
    assert cost.tick_bytes(shape, 7, 84_000) \
        == 2 * 3_029_337_472 + 2 * 26 * 18 * 5120 \
        + 2 * 7 * 9_318_400 + 84_000 * 1024
    assert cost.tick_bytes(shape, 0, 0) == cost.held_bytes(shape)


def test_the_cell_is_sized_as_the_issue_asked():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    mix = traffic.load_json("traffic", MIX)
    assert (mix["loop"], mix["clients"], mix["max_batch"],
            mix["max_queue_depth"], mix["max_seq_len"], mix["drain_s"],
            mix["request_timeout_s"], mix["replays"],
            mix["reference_new_tokens"]) \
        == ("closed", 8, 8, 8, 33280, 30, 120, 2, 48)
    assert mix["prompt_tokens"] == {
        "values": [4000, 8192, 16384, 32768],
        "weights": [0.35, 0.30, 0.25, 0.10]}
    assert mix["output_tokens"] == {"values": [72, 152, 280],
                                    "weights": [0.3, 0.4, 0.3]}
    # a block of 20 holds the shares exactly
    assert traffic.apportion(20, [0.35, 0.30, 0.25, 0.10]) == [7, 6, 5, 2]
    assert traffic.apportion(20, [0.3, 0.4, 0.3]) == [6, 8, 6]
    # the checked prompt, the shortest, ends in a ragged block of the
    # program's and of the prompt kernel's
    shape = configs.model_shape(configs.load_config(CONFIG))
    assert 4000 % shape["token_block"] and 4000 % 256
    assert max(mix["prompt_tokens"]["values"]) \
        + max(mix["output_tokens"]["values"]) <= mix["max_seq_len"]
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert judged == {"serve_tokens_per_s", "setup_s"}
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW and all(
        m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        and m["unit"] == "%" for m in mine)
    assert mine[0]["layer"] == "kernels" \
        and mine[1]["layer"] == mine[2]["layer"] == "model step"
    there = {m["name"]: m for m in bench["per_layer"]}
    assert all(there[name]["workloads"][-1] == CELL for name in THERE)
    # weights and slab: over a quarter of the chip before a prefill runs
    assert cost.held_bytes(shape) + 8 * (
        33280 * shape["row_bytes"] + shape["state_bytes"]) > 0.4 * 16e9


# ------------------------------------------------------------ the readers

def _record(ts, live, **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 8,
            "pending": 0, "admit_ms": 0.0, "admissions": [],
            "dispatch_ms": 1.0, "readback_ms": 9.0, "emit_ms": 0.5,
            "total_ms": 11.0, **more}


def _summary(store):
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})


def _cell(config=CONFIG, mix=MIX):
    return {"seconds": 2.0, "conf": configs.load_config(config),
            "traffic": traffic.load_json("traffic", mix), "peaks": PEAKS}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    for rec in [_record(T0 - 4.0, 1, live_rows=4010),   # the check's
                _record(T0 + 0.1, 6, live_rows=60_000),
                _record(T0 + 0.2, 8, live_rows=100_000),
                _record(T0 + 0.3, 0, live_rows=0),      # nothing decoding
                _record(T0 + 5.0, 1, live_rows=300)]:   # the drain's
        store.record_loop(rec)
    _summary(store)
    ms = 1e6
    scan = lambda name, at, took: (name, at * ms, took * ms)
    trace = {
        "window": (0.0, 3000 * ms),
        "programs": {
            "_tick": [("jit__tick(3)", 10 * ms, 9 * ms),
                      ("jit__tick(3)", 400 * ms, 11 * ms)],
            "_prefill_paged": [("jit__prefill_paged(5)", 100 * ms, 200 * ms),
                               ("jit__prefill_paged(7)", 500 * ms,
                                400 * ms)]},
        "ops": {
            # a 4,000-token prompt: two calls a layer, 26 layers
            "selective_scan_t4000.1": [
                scan("selective_scan_t4000.1", 100 + i, 0.5)
                for i in range(52)],
            # an 8,192-token prompt, in two of its three loops
            "selective_scan_t8192.2": [
                scan("selective_scan_t8192.2", 500 + i, 0.5)
                for i in range(60)],
            "selective_scan_t8192.3": [
                scan("selective_scan_t8192.3", 700 + i, 0.5)
                for i in range(44)],
            "gqa_prefill_w0_t4000.4": [
                scan("gqa_prefill_w0_t4000.4", 160, 1.0)],
            "fusion.7": [scan("fusion.7", 150, 30)]}}
    yield {"phases": [{}], "trace": trace, "requests": [], "cell": _cell()}
    reqtrace._reset_store_for_tests()


def test_the_scans_readers_take_the_length_from_the_name(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    # 52 events of a 4,000-token prompt are 26 layers' worth, 104 of an
    # 8,192-token one (four calls a layer) 26 again
    moved = 26 * cost.scan_bytes(shape, 4000) \
        + 26 * cost.scan_bytes(shape, 8192)
    want = 100.0 * moved / 8.19e11 / (156 * 0.5e-3)
    assert readers.load_reader(NEW[0])(obs) == pytest.approx(want)
    assert 1.0 < want < 100.0
    assert readers.load_reader(NEW[1])(obs) \
        == pytest.approx(100.0 * 156 * 0.5 / 600.0)
    # the prompt kernel's reader takes 20 heads of 128 from the family
    from benchmarks.harness.smallthinker_cost import gqa_prefill_flops
    assert gqa_prefill_flops(shape, 4000, 0) \
        == 20 * 4 * 128 * 4000 * 4001 / 2
    assert readers.load_reader("gqa_prefill_roofline.tput")(obs) \
        == pytest.approx(100.0 * gqa_prefill_flops(shape, 4000, 0)
                         / 197e12 / 1e-3)


def test_the_ticks_reader_takes_the_windows_decode_passes(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    least = cost.tick_bytes(shape, 7.0, 80_000.0)
    assert readers.load_reader(NEW[2])(obs) \
        == pytest.approx(100.0 * least / 8.19e11 / 10e-3)
    assert readers.load_reader("tick_live_slots_mean.tput")(obs) \
        == pytest.approx(7.0)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A run without a trace; a program without the kernel and a family
    without this state (another cell's, the parent's): no number, no
    error."""
    reqtrace._reset_store_for_tests()
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": _cell(), "trace": None,
                 "requests": []}) is None
    trace = {"window": (0.0, 3e9),
             "programs": {"_tick": [("jit__tick(1)", 1e8, 5e6)],
                          "_prefill_paged": [("jit__prefill_paged(1)", 2e8,
                                              5e7)]},
             "ops": {"gqa_prefill_w0_t1024": [("gqa_prefill_w0_t1024",
                                               2.1e8, 1e6)]}}
    store = reqtrace.store()
    store.record_loop(_record(T0 + 0.1, 1, live_rows=100))
    _summary(store)
    assert read({"phases": [{}], "requests": [], "trace": trace,
                 "cell": _cell("mistral-7b-v0.3-l8", "summarize")}) is None
    reqtrace._reset_store_for_tests()
