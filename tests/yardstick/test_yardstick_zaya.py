"""What `zaya1-rollouts` brings to the yardstick: the control of its
`correct` at a size a test run holds (the program as configured keeps the
cell's mean limit; every matrix rounded to 8 bits reads several times its
gap: the limit itself is told apart on the chip), the bytes of
`zaya_cost` by hand at the published sizes, the family file's arithmetic
and refusals, the cell's sizes as the issue gave them, and the four new
readers on a hand-made trace and loop ring: how a device operation is
told to be the attention's, from a compiled program's own text."""
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

from benchmarks.harness import (configs, readers, reference,  # noqa: E402
                                traffic, zaya_cost as cost)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "zaya1-8b-l16"
CELL = "zaya1-rollouts"
MIX = "rollouts"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}
NEW = ["zaya_tick_bytes_roofline.tput", "cca_share_of_tick.tput",
       "moe_skip_share.tput", "cca_prefill_ms_per_ktok.tput"]
THERE = ["compiles_in_window.tput", "prefill_device_ms_per_ktok.tput",
         "device_idle_share.tput", "tick_live_slots_mean.tput",
         "tick_device_ms_mean.tput", "client_ttft_p50_ms.tput",
         "expert_rows_max_over_mean.tput", "chip_empty_share.tput",
         "ttft_collision_share.tput"]


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return {r["name"]: r for r in rows}["ZAYA1-8B"]


# ----------------------------------------------------- the control of it

def _toy(dtype):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype)
    return conf, cfg, configs.init_params(conf, cfg, 11)


def _round_to_8_bits(params):
    from benchmarks.probe_state_precision import round_in_place
    return round_in_place(jax.tree.map(jnp.copy, params), 8, 2)


def _mean_gap(conf, cfg, served, true):
    """The serving check's path and number: 16 tokens prefilled (two
    blocks of the expert layer), 40 decoded through the rows and the
    carried tails; the program's log-probability of each token it chose
    against the reference's of the same token, the mean gap."""
    step, init_cache, _ = _model_fns(cfg)
    prefill = jax.jit(lambda p, t, c: step(p, t, cfg, c, 0))
    decode = jax.jit(lambda p, t, c, pos: step(p, t, cfg, c, pos))
    tokens = [int(t) for t in TOKENS[:16]]
    logits, cache = prefill(served, jnp.asarray(tokens)[None],
                            init_cache(cfg, 1))
    emitted, scores = [], []
    for pos in range(16, 56):
        lp = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        emitted.append(int(jnp.argmax(lp)))
        scores.append(float(lp[emitted[-1]]))
        logits, cache = decode(served, jnp.asarray([[emitted[-1]]]), cache,
                               jnp.int32(pos))
    ref = reference.score_emitted(conf, true, tokens, emitted)
    return float(np.mean([abs(s - r["logprob"])
                          for s, r in zip(scores, ref)]))


def test_the_mean_gap_tells_the_configured_program_from_8_bits():
    """The toy's two layers of 64 read thirty times under the chip's
    sixteen of 2,048 on BOTH sides (0.0013 and 0.0072 here against
    0.021 to 0.073 and 0.105 to 0.157 there: the traffic file's
    `tolerances.why`), so the cell's limit itself stands far over both
    toy readings. What a test run can hold: the configured program keeps
    the cell's limit, 8-bit matrices read several times the configured
    program's gap at the toy's own scale (the chip: 2.2 to 4.6 times,
    seed by seed), and float32 on both sides is the same function."""
    limit = traffic.load_json("traffic", MIX)["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    eight_bits = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    assert 0.0 < good <= limit and eight_bits > 3.0 * good, (
        good, eight_bits)
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_keys_and_the_parameters_by_hand():
    conf = configs.load_config(CONFIG)
    published = _catalog()["config"]
    changed = set(conf["reduced"])
    assert changed == {"num_hidden_layers", "layer_types"}
    for key, value in published.items():
        if key not in changed:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] == 16
    assert conf["layer_types"] == ["hybrid"] * 16
    assert conf["source"] == _catalog()["source_url"]
    assert set(conf["reduced_from"]) == changed
    for said in ("residual scales", "gamma", "router mlp", "skip",
                 "value halves", "convolution biases", "qk mean",
                 "temperature", "l2 norm", "rotary layout", "init",
                 "ffn_token_block", "max_position_embeddings"):
        assert said in conf["assumed"], said
    assert "16, 16 and 8" in conf["deployment"]
    shape = configs.model_shape(conf)
    d = 2048
    attention = d * (1024 + 256 + 128 + 128) + 10 * 2 * 128 * 128 \
        + 1024 * d
    router = d * 256 + 2 * 256 * 256 + 256 * 17
    expert = 3 * d * 2048
    assert (attention, router, expert) == (5_570_560, 659_712, 12_582_912)
    head = 262272 * d
    assert head == 537_133_056
    # ONE token's matrix products: its one expert of sixteen
    assert shape["matmul_params"] == head + 16 * (attention + router
                                                  + expert)
    assert round(shape["held_params"] / 1e9, 3) == 3.858
    # the program holds what the family file reckons, to the parameter
    cfg = configs.program_config(conf, 2816)
    params = jax.eval_shape(lambda: configs.init_params(conf, cfg, 0))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == shape["held_params"]
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert round(nbytes / 1e9, 2) == 7.74       # the routers in float32
    assert nbytes == 16 * (shape["layer_bytes"] + 16 * 2 * expert) \
        + shape["head_bytes"]
    assert (shape["layers"], shape["heads"], shape["kv_heads"],
            shape["head_dim"], shape["d_model"], shape["vocab"],
            shape["expert_layers"], shape["experts_held"],
            shape["expert_params"]) == (16, 8, 2, 128, d, 262272, 16, 16,
                                        expert)
    # a token's keys and values of two heads of 128 in sixteen layers;
    # a slot's tails (2 x 1,280) and last half-values (128) in each
    assert shape["row_bytes"] == 16 * 2 * 2 * 128 * 2 == 16 * 1024
    assert shape["state_bytes"] == 16 * (2 * 1280 + 128) * 2 == 16 * 5376


REFUSED = (
    ("attention_bias", True, "bias in the attention projections"),
    ("lm_head_bias", True, "bias on the head"),
    ("tie_word_embeddings", False, "untied head"),
    ("hidden_act", "gelu", "activation other than silu"),
    ("layer_types", ["hybrid"] * 15 + ["hybrid_sliding"],
     "layer other than hybrid"),
    ("layer_types", ["hybrid"] * 15, "one entry a layer"),
    ("sliding_window", 4096, "sliding window"),
    ("num_experts_per_tok", 2, "more than one expert a token"),
    ("partial_rotary_factor", 0.25, "differs between the two places"),
)


@pytest.mark.parametrize("key,value,words", REFUSED,
                         ids=[f"{k}-{i}" for i, (k, _v, _w)
                              in enumerate(REFUSED)])
def test_the_family_file_refuses_what_the_program_cannot_honour(key, value,
                                                                words):
    conf = configs.load_config(CONFIG)
    with pytest.raises(ValueError, match=words):
        configs.program_config({**conf, key: value}, 1024)


def test_the_family_file_gives_the_program_the_files_sizes():
    conf = configs.load_config(CONFIG)
    rope = conf["rope_parameters"]
    scaled = {**rope, "hybrid": {**rope["hybrid"], "rope_type": "yarn"}}
    with pytest.raises(ValueError, match="rotary scaling"):
        configs.program_config({**conf, "rope_parameters": scaled}, 1024)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 140_000)
    cfg = configs.program_config(conf, 2816)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_model, cfg.vocab_size, cfg.rope_theta, cfg.norm_eps,
            cfg.rotary_dim, cfg.max_seq_len) \
        == (16, 8, 2, 128, 2048, 262272, 5e6, 1e-5, 64, 2816)
    assert (cfg.cca_time0, cfg.cca_time1, cfg.num_experts,
            cfg.moe_intermediate_size, cfg.router_hidden_size,
            cfg.ffn_block) == (2, 2, 16, 2048, 256, 2048)
    assert cfg.dtype == jnp.bfloat16


# ------------------------------------------------------ the cost by hand

def test_the_ticks_bytes_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    # 64 slots live at 1,000 rows each, 15 of 16 experts hit a layer
    expert = 3 * 2048 * 2048 * 2
    attention = 5_570_560 * 2 + (2 * 1280 + 2 * 1280 + 2) * 2
    router = (659_712 + 256 + 1 + 17) * 4
    vectors = 10 * 2048 * 2
    assert shape["layer_bytes"] == attention + router + vectors
    assert shape["head_bytes"] == (262272 + 1) * 2048 * 2
    want = (240 * expert + 16 * (attention + router + vectors)
            + (262272 + 1) * 2048 * 2 + 64_000 * 16 * 1024
            + 2 * 64 * 16 * 5376)
    assert cost.tick_bytes(shape, 240, 64, 64_000) == want
    assert 8.3e9 < want < 8.4e9
    # at the peak bandwidth: what the issue reckoned a full tick to need
    assert 10.0 < 1e3 * want / 8.19e11 < 10.5
    # nothing live and nothing hit: the weights every tick reads
    assert cost.tick_bytes(shape, 0, 0, 0) == 16 * shape["layer_bytes"] \
        + shape["head_bytes"]


def test_the_cell_is_sized_as_the_issue_asked():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == configs.load_config(CONFIG)["reduced"] \
        == ["num_hidden_layers", "layer_types"]
    mix = traffic.load_json("traffic", MIX)
    assert mix["prompt_tokens"] == {"values": [288, 544, 1056],
                                    "weights": [0.4, 0.4, 0.2]}
    assert mix["output_tokens"] == {
        "values": [392, 648, 936, 1272, 1608], "weights": [0.2] * 5}
    values = mix["output_tokens"]["values"]
    assert (mix["loop"], mix["clients"], mix["max_batch"],
            mix["max_seq_len"], mix["pool_requests_per_s"],
            mix["drain_s"], mix["reference_new_tokens"]) \
        == ("closed", 64, 64, 2816, 8, 30, 32)
    assert mix["clients"] == mix["max_batch"]   # as many callers as slots
    assert values[-1] + 1056 <= mix["max_seq_len"]
    tol = mix["tolerances"]
    assert 0 < tol["logprob_mean_abs"] < tol["logprob_abs"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        # `in`, not "the only" or "the last": a later PR appends its cell
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
        assert by_name[name]["layer"] == "model step"
    assert by_name[NEW[0]]["unit"] == by_name[NEW[1]]["unit"] == "%"
    assert by_name[NEW[2]]["source"] == "program_counter"
    for name in THERE:
        assert CELL in by_name[name]["workloads"]
    assert "workloads" not in by_name["compile_cache_misses.setup"]
    tput = {e["name"]: e for e in bench["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in tput["workloads"] and tput["bound"] == 0.1
    # the slab: sixteen layers of keys and values, 64 slots, and the state
    shape = configs.model_shape(configs.load_config(CONFIG))
    slab = 64 * 2816 * shape["row_bytes"]
    assert round(slab / 1e9, 2) == 2.95
    assert 64 * shape["state_bytes"] == 5_505_024
    assert round((7.74e9 + 2 * slab) / 1e9, 1) == 13.6


# ------------------------------------------------------------ the readers

HLO = """HloModule jit__tick, entry_computation_layout={()}

%fused_computation.3 (p: f32[4]) -> f32[4] {
  %add.7 = f32[4]{0} add(%p, %p), metadata={op_name="jit(_tick)/jit(main)/moe/add" source_file="a.py" source_line=1}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%f, metadata={op_name="jit(_tick)/jit(main)/cca/dot_general" source_file="a.py" source_line=2}
  %gqa_decode_t1.2 = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_tick)/jit(main)/cca/jit(_decode_pallas)/pallas_call" source_file="b.py"}
  %fusion.3 = f32[4]{0} fusion(%gqa_decode_t1.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_tick)/jit(main)/router/reduce_sum"}
  %grouped_stream.4 = f32[4]{0} custom-call(%fusion.3), metadata={op_name="jit(_tick)/jit(main)/moe/jit(_streamed_product)/pallas_call"}
  %copy.5 = f32[4]{0} copy(%grouped_stream.4)
  ROOT %fusion.6 = f32[4]{0} fusion(%copy.5), kind=kOutput, calls=%g, metadata={op_name="jit(_tick)/jit(main)/head/dot_general"}
}
"""


def test_an_operation_is_told_by_its_scope_in_the_compiled_text():
    scopes = cost.scopes_of(HLO)
    assert scopes == {"add.7": "moe", "fusion.1": "cca",
                      "gqa_decode_t1.2": "cca", "fusion.3": "router",
                      "grouped_stream.4": "moe", "fusion.6": "head"}
    events = [("fusion.1", 0.0, 2e6), ("gqa_decode_t1.2", 2e6, 3e6),
              ("fusion.3", 5e6, 1e6), ("copy.5", 6e6, 1e6)]
    assert cost.scoped_seconds(events, scopes, "cca") == pytest.approx(5e-3)
    assert cost.scoped_seconds(events, scopes, "head") == 0.0


def test_the_programs_own_text_names_the_sublayers():
    """The engine's tick and a prefill at the toy size, compiled here
    from shapes alone: XLA carries the program's named scopes on the
    instructions of the compiled program, the attention's among them."""
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy, "name": "zaya-toy"}
    cell = {"conf": conf, "traffic": {"max_seq_len": 64, "max_batch": 2}}
    for tokens in (None, 16):
        scopes = cost.program_scopes(cell, tokens)
        assert set(scopes.values()) == set(cost.SCOPES), tokens
        assert sum(s == "cca" for s in scopes.values()) >= 4
    assert cost.program_scopes(cell, 16) is cost.program_scopes(cell, 16)
    # a cell whose program cannot be built: no map, no error
    assert cost.program_scopes(
        {"conf": {"name": "none", "family": "no-such-family"},
         "traffic": {"max_seq_len": 64, "max_batch": 2}}) == {}


def _record(ts, live, **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 64,
            "pending": 0, "admit_ms": 0.0, "admissions": [],
            "dispatch_ms": 1.0, "readback_ms": 6.0, "emit_ms": 0.5,
            "total_ms": 8.0, **more}


def _tick(ts, live, rows, hit, skipped):
    return _record(ts, live, live_rows=rows, moe_experts_hit=hit,
                   moe_pairs_skipped=skipped,
                   moe_pairs_held=64 * 16 - skipped, moe_rows_max=9)


def _cell(config=CONFIG, mix=MIX):
    return {"seconds": 2.0, "conf": configs.load_config(config),
            "traffic": traffic.load_json("traffic", mix), "peaks": PEAKS}


@pytest.fixture()
def obs(monkeypatch):
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    admitted = _record(T0 + 0.3, 63, admissions=[{
        "rid": 1, "prompt_tokens": 544, "moe_pairs_held": 16 * 544 - 500,
        "moe_pairs_skipped": 500, "moe_rows_max": 60}])
    for rec in [_tick(T0 - 4.0, 1, 300, 16, 1),          # the check's
                _tick(T0 + 0.1, 62, 60_000, 240, 50),
                _tick(T0 + 0.2, 64, 70_000, 250, 70),
                admitted,
                _record(T0 + 0.4, 0),                    # nothing decoding
                _tick(T0 + 5.0, 3, 900, 40, 60)]:       # the drain's
        store.record_loop(rec)
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    ms = 1e6
    ev = lambda name, at, took: (name, at * ms, took * ms)
    trace = {
        "window": (0.0, 3000 * ms),
        "programs": {
            # the third tick is cut by the window's end: left out
            "_tick": [("jit__tick(3)", 10 * ms, 12 * ms),
                      ("jit__tick(3)", 400 * ms, 14 * ms),
                      ("jit__tick(3)", 2995 * ms, 13 * ms)],
            # a prompt of 544 (the kernel names it) and one of 288 (the
            # plain form: the cell's one length under a block)
            "_prefill_paged": [("jit__prefill_paged(5)", 100 * ms, 60 * ms),
                               ("jit__prefill_paged(7)", 200 * ms,
                                30 * ms)]},
        "ops": {
            "fusion.1": [ev("fusion.1", 10, 1.0), ev("fusion.1", 400, 1.5),
                         ev("fusion.1", 2995, 1.0),
                         # another program's instruction of the same name
                         ev("fusion.1", 100, 5.0), ev("fusion.1", 200, 2.0)],
            "gqa_decode_t1.2": [ev("gqa_decode_t1.2", 12, 2.0),
                                ev("gqa_decode_t1.2", 402, 2.5)],
            "grouped_stream.4": [ev("grouped_stream.4", 15, 6.0),
                                 ev("grouped_stream.4", 405, 7.0)],
            "gqa_prefill_w0_t544.8": [ev("gqa_prefill_w0_t544.8", 110,
                                         4.0)],
            "fusion.9": [ev("fusion.9", 120, 3.0), ev("fusion.9", 210, 1.0)],
        }}
    maps = {None: {"fusion.1": "cca", "gqa_decode_t1.2": "cca",
                   "grouped_stream.4": "moe"},
            544: {"fusion.1": "cca", "gqa_prefill_w0_t544.8": "cca",
                  "fusion.9": "moe"},
            288: {"fusion.1": "moe", "fusion.9": "cca"}}
    monkeypatch.setattr(cost, "program_scopes",
                        lambda cell, tokens=None: maps.get(tokens, {}))
    from ray_tpu.ops import dispatch
    monkeypatch.setattr(
        dispatch, "kernel_choices", lambda op=None: [
            {"op": "gqa_prefill", "shape": (1, t, 8, 2, 128, 0),
             "choice": "pallas"} for t in (544, 1056)])
    yield {"phases": [{}], "trace": trace, "requests": [], "cell": _cell()}
    reqtrace._reset_store_for_tests()


def test_the_ticks_roofline_takes_the_windows_records(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    least = cost.tick_bytes(shape, 245, 63, 65_000)      # the two ticks'
    want = 100.0 * least / 8.19e11 / 13e-3
    assert readers.load_reader(NEW[0])(obs) == pytest.approx(want)
    assert 50.0 < want < 100.0
    assert readers.load_reader("tick_live_slots_mean.tput")(obs) \
        == pytest.approx((62 + 64 + 63) / 3)


def test_the_attentions_share_counts_a_ticks_own_operations(obs):
    # the two whole ticks: 1.0 + 2.0 and 1.5 + 2.5 ms of 12 and 14; the
    # prefills' `fusion.1` and the cut tick's are another program's
    assert readers.load_reader(NEW[1])(obs) \
        == pytest.approx(100.0 * 7.0 / 26.0)
    assert cost.tick_share(obs, "moe") == pytest.approx(100.0 * 13.0 / 26.0)
    assert cost.tick_share(obs, "head") is None


def test_the_prompt_forms_time_is_told_a_length_at_a_time(obs):
    # 544 tokens: fusion.1 5.0 + the kernel 4.0; 288: fusion.9 1.0 (its
    # fusion.1 is the expert layer's there)
    assert readers.load_reader(NEW[3])(obs) \
        == pytest.approx(10.0 / (832 / 1e3))
    ops = [[("gqa_prefill_w0_t1056.3", 0, 1)], [("fusion.2", 0, 1)]]
    assert cost.prompt_lengths_of(ops, [288, 544, 1056], [544, 1056]) \
        == [1056, 288]
    # two lengths that no kernel names: neither can be told
    assert cost.prompt_lengths_of(ops, [100, 288, 1056], [1056]) \
        == [1056, None]


def test_the_skips_share_is_over_ticks_and_admissions(obs):
    skipped = 50 + 70 + 500
    pairs = 2 * 64 * 16 + 16 * 544
    assert readers.load_reader(NEW[2])(obs) \
        == pytest.approx(100.0 * skipped / pairs)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A run without a trace; a program without the counters and a family
    without the sizes (another cell's, the parent's): no number, no
    error."""
    reqtrace._reset_store_for_tests()
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": _cell(), "trace": None,
                 "requests": []}) is None
    # the rehearsal's trace: no program on a device plane
    assert read({"phases": [], "cell": _cell(), "requests": [],
                 "trace": {"window": (0.0, 3e9), "programs": {},
                           "ops": {"bench_rehearsal_op": [
                               ("bench_rehearsal_op", 1e8, 1e7)]}}}) is None
    store = reqtrace.store()
    store.record_loop(_record(T0 + 0.1, 4, live_rows=4000))
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    if name in (NEW[0], NEW[2]):
        trace = {"window": (0.0, 3e9),
                 "programs": {"_tick": [("jit__tick(1)", 1e8, 5e6)]},
                 "ops": {}}
        other = _cell("jamba2-3b", "docqa-32k")
        assert read({"phases": [{}], "cell": other, "trace": trace,
                     "requests": []}) is None
    reqtrace._reset_store_for_tests()
