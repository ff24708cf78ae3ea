"""What `deepseek-v2-longdoc` brings to the yardstick: the control of its
`correct` (the program with every matrix rounded to 8 bits must fail the
cell's mean limit, and the program as configured must keep it, at a size
a test run holds), the bytes and operations of `deepseek_v2_cost` by hand
at the cell's sizes, the family file's arithmetic and refusals, the cell
as the issue reckoned it, and the new readers on a hand-made trace and
loop ring."""
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

from benchmarks.harness import (configs, deepseek_v2_cost,  # noqa: E402
                                program_ops, readers, reference, traffic)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "deepseek-v2-l5-e40"
CELL = "deepseek-v2-longdoc"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}


# ----------------------------------------------------- the control of it

def _toy(dtype, noise=0.15):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper beside the
    # embedding (a matrix's gain is 0.02 x sqrt(5,120) = 1.4 at the
    # published width, 0.16 here): make the layers count
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


def _mean_gap(conf, cfg, served, true):
    """The serving check's path and number: 24 tokens prefilled (three
    blocks of the prompt form and of the feed-forward part), 32 decoded
    through the absorbed form; the program's log-probability of each token
    it chose against the reference's of the same token, the mean gap."""
    step, init_cache, _ = _model_fns(cfg)
    tokens = [int(t) for t in TOKENS[:24]]
    logits, cache = step(served, jnp.asarray(tokens)[None], cfg,
                         init_cache(cfg, 1), 0)
    emitted, scores = [], []
    for pos in range(24, 56):
        lp = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        emitted.append(int(jnp.argmax(lp)))
        scores.append(float(lp[emitted[-1]]))
        logits, cache = step(served, jnp.asarray([[emitted[-1]]]), cfg,
                             cache, pos)
    ref = reference.score_emitted(conf, true, tokens, emitted)
    return float(np.mean([abs(s - r["logprob"])
                          for s, r in zip(scores, ref)]))


def test_8_bit_matrices_fail_the_cells_mean_limit_and_bf16_keeps_it():
    limit = traffic.load_json("traffic", "longdoc")["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    bad = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    assert good <= limit < bad
    # float32 on both sides is the same function
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_widths_and_the_parameters_by_hand():
    c = configs.load_config(CONFIG)
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (5120, 128, 1536, 512, 128, 64, 128)
    assert (c["n_routed_experts"] * c["expert_parallel_size"], c["n_group"],
            c["topk_group"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["norm_topk_prob"],
            c["intermediate_size"], c["vocab_size"],
            c["first_k_dense_replace"]) \
        == (160, 8, 3, 6, 1536, 2, 16, False, 12288, 102400, 1)
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "max_position_embeddings"]
    assert set(c["reduced"]) == set(c["reduced_from"])
    for stated in ("vocab_size", "expert_parallel_size", "init",
                   "rotary layout", "row padding", "n_group", "router"):
        assert stated in c["assumed"]
    assert "15 pipeline stages" in c["deployment"]
    per = configs.family(c).config.__globals__["layer_params"](c)
    # q_a, q_b, kv_a, kv_b, o
    assert per["A"] == 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 \
        + 83_886_080 == 149_225_472
    assert per["dense"] == 3 * 5120 * 12288 == 188_743_680
    assert per["expert"] == 3 * 5120 * 1536 == 23_592_960
    assert per["shared"] == 47_185_920 and per["router"] == 819_200
    # router, 6 x 40 / 160 = 1.5 of the chosen experts, the shared two
    assert per["E"] == 819_200 + 1.5 * 23_592_960 + 47_185_920
    shape = configs.model_shape(c)
    assert shape["matmul_params"] == 5 * per["A"] + per["dense"] \
        + 4 * per["E"] + 102400 * 5120 == 1_792_737_280
    assert (shape["expert_layers"], shape["experts_held"],
            shape["heads"], shape["head_dim"]) == (4, 40, 128, 192)
    # what the program holds, leaf by leaf: the issue's 5.95 B and the
    # norms it left out (2,048 a layer in the low-rank paths, 10,240 a
    # layer around the parts, 5,120 at the end)
    cfg = configs.program_config(c, 8448)
    held = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: configs.init_params(c, cfg, 0))))
    assert held == shape["held_params"] == 1_048_576_000 + 337_971_200 \
        + 4 * 1_140_951_040 + 5 * 10_240 + 5_120 == 5_950_407_680
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.first_expert,
            cfg.n_group, cfg.topk_group, cfg.attn_block, cfg.ffn_block) \
        == (160, 40, 0, 8, 3, 512, 2048)
    assert cfg.softmax_scale == pytest.approx(0.114721, abs=1e-6)


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    for key, value in [("hidden_act", "gelu"), ("scoring_func", "sigmoid"),
                       ("topk_method", "greedy"), ("norm_topk_prob", True),
                       ("rope_scaling", None), ("q_lora_rank", None),
                       ("attention_bias", True), ("moe_layer_freq", 2),
                       ("num_key_value_heads", 8),
                       ("tie_word_embeddings", True)]:
        with pytest.raises(ValueError, match="DeepSeek-V2 path has no"):
            configs.program_config({**conf, key: value}, 8448)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 8449)
    with pytest.raises(ValueError, match="groups do not divide"):
        configs.program_config({**conf, "n_group": 7}, 8448)


# ------------------------------------------------------ the cost by hand

def test_the_bytes_of_the_share_of_a_slot_and_of_a_tick_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    mix = traffic.load_json("traffic", "longdoc")
    # 11.90 GB: bf16 throughout, but the four routers in float32
    assert deepseek_v2_cost.held_bytes(shape) \
        == 2 * 5_950_407_680 + 2 * 4 * 819_200 == 11_907_368_960
    assert round(deepseek_v2_cost.held_bytes(shape) / 1e9, 2) == 11.91
    assert round(2 * (shape["held_params"] - 56_320) / 1e9, 2) == 11.90
    # a row of 576 padded to 640 bf16 is 1,280 B a layer, 6,400 B a token
    assert deepseek_v2_cost.slot_bytes(shape, 1) == 5 * 1280 == 6400
    assert deepseek_v2_cost.slot_bytes(shape, mix["max_seq_len"]) \
        == 8448 * 6400 == 54_067_200
    assert 16 * 54_067_200 == 865_075_200        # the slab, 0.87 GB
    # 47.2 MB an expert
    assert deepseek_v2_cost.expert_bytes(shape) == 47_185_920
    # what every token of a tick reads: five attention parts, the dense
    # part, four pairs of shared experts, the norms, the head; routers
    always = 2 * (5 * 149_225_472 + 188_743_680 + 4 * 47_185_920
                  + 5 * 12_288 + 5_120 + 524_288_000) + 4 * 3_276_800
    assert always == 3_309_045_760
    assert deepseek_v2_cost.tick_bytes(shape, 0, 0) == always
    # a tick that hit 72 experts with 8 slots live at row 3,600
    got = deepseek_v2_cost.tick_bytes(shape, 72, 8 * 3600)
    assert got == always + 72 * 47_185_920 + 28_800 * 6400 \
        == 6_890_752_000
    # a full tick: every held expert hit, 16 slots at their last row
    full = deepseek_v2_cost.tick_bytes(shape, 160, 16 * 8448)
    assert round(full / 1e9, 2) == 11.72


def test_the_operations_of_the_three_hot_parts_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    # absorbed: 128 heads x (576 + 512) x 2 = 278,528 a row and layer, 218
    # for each of the row's 1,280 bytes
    assert deepseek_v2_cost.tick_mla_flops(shape, 1) == 5 * 278_528
    assert 278_528 / 1280 == pytest.approx(217.6)
    # the prompt form: T (T + 1) / 2 pairs x 2 x (192 + 128) x 128 heads
    assert deepseek_v2_cost.mla_prefill_flops(shape, 1) == 2 * 320 * 128
    assert deepseek_v2_cost.mla_prefill_flops(shape, 8192) \
        == 8192 * 8193 / 2 * 81_920 == pytest.approx(2.749e12, rel=1e-3)
    # a pair is a multiply-add a parameter of its expert
    assert deepseek_v2_cost.moe_prefill_flops(shape, 1) == 47_185_920
    # 12,288 pairs a layer at 8,192 tokens: compute holds (2.9 against
    # 2.6 ms); at 2,048 tokens the 40 experts' bytes do (0.7 against 2.4)
    from benchmarks.harness.roofline import least_seconds
    for tokens, bound in [(8192, "compute"), (2048, "memory")]:
        pairs = tokens * 6 / 4
        assert least_seconds(
            deepseek_v2_cost.moe_prefill_flops(shape, pairs),
            deepseek_v2_cost.moe_prefill_bytes(shape, pairs, 40),
            PEAKS)[1] == bound
    assert deepseek_v2_cost.moe_prefill_bytes(shape, 100, 3) \
        == 3 * 47_185_920 + 2 * 2 * 100 * 5120


def test_the_cell_is_sized_as_the_issue_reckoned_it():
    mix = traffic.load_json("traffic", "longdoc")
    assert (mix["loop"], mix["clients"], mix["pool_requests_per_s"],
            mix["max_batch"], mix["max_seq_len"], mix["max_queue_depth"],
            mix["drain_s"], mix["replays"], mix["reference_new_tokens"]) \
        == ("closed", 16, 8, 16, 8448, 16, 30, 2, 48)
    assert mix["prompt_tokens"] == {"values": [1024, 2048, 4096, 8192],
                                    "weights": [0.15, 0.35, 0.35, 0.15]}
    assert mix["output_tokens"] == {"values": [48, 112, 200],
                                    "weights": [0.3, 0.4, 0.3]}
    # the check's prompts are whole blocks of the prompt form
    conf = configs.load_config(CONFIG)
    assert traffic.prompt_lengths(mix)[0] == 2 * conf["mla_prefill_block"]
    # the limits carry their two sets of readings
    why = mix["tolerances"]["why"]
    assert "8 bits" in why and "configured" in why and "seeds" in why
    # the slab as the program lays it: rows alone, padded to 640
    cfg = configs.program_config(conf, mix["max_seq_len"])
    slab = jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, mix["max_batch"]))
    assert [sorted(e) for e in slab] == [["k"]] * 5
    assert slab[0]["k"].shape == (16, 8448, 640)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(slab)) \
        == 865_075_200
    # the cell is judged on tokens per second and set-up, nothing else
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert judged == ["serve_tokens_per_s", "setup_s"]


# ------------------------------------------------------------ the readers

def _admission(prompt_tokens, pairs, hit, rows_max):
    return {"rid": 0, "prompt_tokens": prompt_tokens,
            "suffix_tokens": prompt_tokens, "reused_tokens": 0,
            "lookup_ms": 0.0, "prefill_ms": 50.0, "commit_ms": 0.0,
            "commit_dispatches": 0, "commit_blocks": 0, "splice_ms": 0.5,
            "moe_pairs_held": pairs, "moe_experts_hit": hit,
            "moe_rows_max": rows_max, "attn_blocks": 15}


def _record(ts, live, admissions=(), **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live,
            "max_batch": 16, "pending": 0, "admit_ms": 0.0,
            "admissions": list(admissions), "dispatch_ms": 1.0,
            "readback_ms": 10.0, "emit_ms": 0.5, "total_ms": 12.0, **more}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    for rec in [
            # before the window: the reference check's prefill
            _record(T0 - 4.0, 1, [_admission(1024, 9000, 160, 90)],
                    live_rows=1030, moe_experts_hit=150),
            _record(T0 + 0.1, 8, [_admission(1024, 6144, 160, 48)],
                    live_rows=20_000, moe_experts_hit=70),
            _record(T0 + 0.2, 8, [_admission(2048, 12_000, 160, 100)],
                    live_rows=30_000, moe_experts_hit=74),
            _record(T0 + 0.3, 8, [_admission(2048, 12_576, 160, 110)],
                    live_rows=31_000),
            # after the window
            _record(T0 + 5.0, 1, [_admission(8192, 49_152, 160, 400)],
                    live_rows=300, moe_experts_hit=10)]:
        store.record_loop(rec)
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    ms = 1e6
    trace = {
        "window": (0.0, 3000 * ms),
        "programs": {
            "_tick": [("jit__tick(3)", 10 * ms, 12 * ms),
                      ("jit__tick(3)", 400 * ms, 14 * ms)],
            "_prefill_paged": [
                ("jit__prefill_paged(5)", 100 * ms, 90 * ms),    # 2,048
                ("jit__prefill_paged(4)", 500 * ms, 50 * ms),    # 1,024
                # cut by the window's end: left out
                ("jit__prefill_paged(5)", 2950 * ms, 90 * ms)]},
        "ops": {
            "mla_prefill_t2048.1": [("mla_prefill_t2048.1", 110 * ms,
                                     2 * ms)],
            "mla_prefill_t2048.2": [("mla_prefill_t2048.2", 130 * ms,
                                     2 * ms),
                                    ("mla_prefill_t2048.2", 2960 * ms,
                                     2 * ms)],
            "mla_prefill_t1024": [("mla_prefill_t1024", 510 * ms,
                                   0.6 * ms)],
            "ragged-dot-none.1": [
                ("ragged-dot-none.1", 15 * ms, 1 * ms),      # a tick's
                ("ragged-dot-none.1", 120 * ms, 8 * ms),
                ("ragged-dot-none.1", 520 * ms, 6 * ms),
                ("ragged-dot-none.1", 2970 * ms, 8 * ms)],
            "ragged-dot-none.2": [("ragged-dot-none.2", 140 * ms, 8 * ms)],
            "fusion.7": [("fusion.7", 150 * ms, 30 * ms)]}}
    yield {"phases": [{}], "trace": trace,
           "requests": [{"token_t": [1.30], "due_t": 1.0},
                        {"token_t": [2.10], "due_t": 2.0},
                        {"token_t": [9.90], "due_t": 9.0},
                        {"token_t": [], "due_t": 3.0}],
           "cell": {"seconds": 2.0, "conf": configs.load_config(CONFIG),
                    "peaks": PEAKS}}
    reqtrace._reset_store_for_tests()


def test_mla_prefill_roofline_reads_the_prompts_length_from_the_kernel(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    flops = 3 * deepseek_v2_cost.mla_prefill_flops(shape, 2048) \
        + deepseek_v2_cost.mla_prefill_flops(shape, 1024)
    want = 100.0 * flops / 197e12 / 6.6e-3
    assert readers.load_reader("mla_prefill_roofline.tput")(obs) \
        == pytest.approx(want)
    assert 20.0 < want < 100.0


def test_moe_prefill_roofline_takes_the_products_inside_whole_prefills(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    # the 2,048-token program: the mean of the window's two admissions of
    # that length; the 1,024-token one: the window's one, not the check's
    least = 0.0
    for pairs in (12_288.0, 6144.0):
        least += max(
            deepseek_v2_cost.moe_prefill_flops(shape, pairs) / 197e12,
            deepseek_v2_cost.moe_prefill_bytes(shape, pairs, 160)
            / 8.19e11)
    want = 100.0 * least / (8e-3 + 8e-3 + 6e-3)
    assert readers.load_reader("moe_prefill_roofline.tput")(obs) \
        == pytest.approx(want)
    assert want < 100.0


def test_the_tick_readers_and_the_plain_ones(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    assert readers.load_reader("tick_device_ms_mean.tput")(obs) == 13.0
    least = (deepseek_v2_cost.tick_bytes(shape, 70, 20_000)
             + deepseek_v2_cost.tick_bytes(shape, 74, 30_000)) / 2
    assert readers.load_reader("tick_bytes_roofline.tput")(obs) \
        == pytest.approx(100.0 * least / 8.19e11 / 13e-3)
    # rows max over mean: 160 groups; the window's three admissions
    assert readers.load_reader("expert_rows_max_over_mean.tput")(obs) \
        == pytest.approx(np.mean([48 * 160 / 6144, 100 * 160 / 12_000,
                                  110 * 160 / 12_576]))
    assert readers.load_reader("client_ttft_p50_ms.tput")(obs) \
        == pytest.approx(300.0)


def test_program_ops_by_hand(obs):
    trace = obs["trace"]
    whole = program_ops.whole_programs(trace, "_prefill_paged")
    assert [ev[1] for ev in whole] == [100e6, 500e6]
    grouped = program_ops.named(trace, ["ragged-dot"])
    assert len(grouped) == 5
    assert sorted(ev[1] for ev in program_ops.inside(grouped, whole[0])) \
        == [120e6, 140e6]
    kernels = program_ops.named(trace, ["mla_prefill_t"])
    assert program_ops.prompt_tokens(
        program_ops.inside(kernels, whole[1])) == 1024
    assert program_ops.prompt_tokens(grouped) is None


NEW = ["mla_prefill_roofline.tput", "moe_prefill_roofline.tput",
       "tick_bytes_roofline.tput", "tick_device_ms_mean.tput",
       "client_ttft_p50_ms.tput", "expert_rows_max_over_mean.tput"]


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A program without the kernel, the ring or the counters, a run
    without a trace: no number, no error."""
    reqtrace._reset_store_for_tests()
    read = readers.load_reader(name)
    cell = {"seconds": 2.0, "conf": configs.load_config(CONFIG),
            "peaks": PEAKS}
    assert read({"phases": [], "cell": cell, "trace": None,
                 "requests": []}) is None
    # a trace of the parent's: programs and grouped products, no kernel
    # of the prompt form; a ring without the counters
    trace = {"window": (0.0, 3e9),
             "programs": {"_prefill_paged": [("jit__prefill_paged(1)", 1e8,
                                              5e7)]},
             "ops": {"ragged-dot-none.1": [("ragged-dot-none.1", 1.1e8,
                                            1e6)]}}
    store = reqtrace.store()
    plain = {k: v for k, v in _admission(1024, 0, 0, 0).items()
             if not k.startswith(("moe_", "attn_"))}
    store.record_loop(_record(T0 + 0.1, 1, [plain]))
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 100.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    assert read({"phases": [{}], "cell": cell, "trace": trace,
                 "requests": []}) is None
    reqtrace._reset_store_for_tests()
