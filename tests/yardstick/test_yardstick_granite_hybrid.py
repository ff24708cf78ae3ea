"""What `granite-4.0-h-small-rag-12k` brings to the yardstick: the
control of its `correct` at a size a test run holds (the program as
configured keeps the cell's mean limit; every matrix rounded to 8 bits
reads several times its gap: the limit itself is told apart on the
chip), the parameters and the operations and bytes of
`granite_hybrid_cost` by hand at the published sizes, the published keys
against the catalog's row, the cell's sizes as the issue gave them, and
the four new readers on a hand-made trace and loop ring. Every entry of
`BENCHMARK.json` is looked up BY NAME."""
import dataclasses
import functools
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
                                traffic)
from benchmarks.harness import granite_hybrid_cost as cost  # noqa: E402
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "granite-4.0-h-small-l10-e36"
CELL = "granite-4.0-h-small-rag-12k"
MIX = "rag-12k"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}
NEW = ["ssd_scan_share_of_prefill.tput", "ssd_scan_roofline.tput",
       "moe_share_of_prefill.tput", "granite_tick_bytes_roofline.tput"]
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
    return {r["name"]: r for r in rows}["granite-4.0-h-small"]


# ----------------------------------------------------- the control of it

def _toy(dtype):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype)
    return conf, cfg, configs.init_params(conf, cfg, 11)


def _round_to_8_bits(params):
    from benchmarks.probe_state_precision import round_in_place
    return round_in_place(jax.tree.map(jnp.copy, params), 8, 2)


@functools.lru_cache(maxsize=None)
def _jitted(step, cfg, from_zero):
    if from_zero:
        return jax.jit(lambda p, t, c: step(p, t, cfg, c, 0))
    return jax.jit(lambda p, t, c, pos: step(p, t, cfg, c, pos))


def _mean_gap(conf, cfg, served, true):
    """The serving check's path and number: 16 tokens prefilled (three
    token blocks of the toy's 6, the last ragged), 24 decoded through the
    rows, the state and the tails; the program's log-probability of each
    token it chose against the reference's of the same token, the mean
    gap."""
    step, init_cache, _ = _model_fns(cfg)
    # one compile a config: the configured and the 8-bit weights share it
    prefill = _jitted(step, cfg, True)
    decode = _jitted(step, cfg, False)
    tokens = [int(t) for t in TOKENS[:16]]
    logits, cache = prefill(served, jnp.asarray(tokens)[None],
                            init_cache(cfg, 1))
    emitted, scores = [], []
    for pos in range(16, 40):
        lp = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        emitted.append(int(jnp.argmax(lp)))
        scores.append(float(lp[emitted[-1]]))
        logits, cache = decode(served, jnp.asarray([[emitted[-1]]]), cache,
                               jnp.int32(pos))
    ref = reference.score_emitted(conf, true, tokens, emitted)
    return float(np.mean([abs(s - r["logprob"])
                          for s, r in zip(scores, ref)]))


def test_the_mean_gap_tells_the_configured_program_from_8_bits():
    """The toy's four layers of 64 read far under the chip's ten of 4,096
    on BOTH sides (the traffic file's `tolerances.why` has the chip's
    readings), so the cell's limit stands over both toy readings. What a
    test run can hold: the configured program keeps the cell's limit,
    8-bit matrices read several times the configured program's gap at
    the toy's own scale, and float32 on both sides is the same
    function."""
    limit = traffic.load_json("traffic", MIX)["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    eight_bits = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    assert 0.0 < good <= limit and eight_bits > 2.0 * good, (
        good, eight_bits)
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_keys_and_the_parameters_by_hand():
    conf = configs.load_config(CONFIG)
    row = _catalog()
    published = row["config"]
    changed = set(conf["reduced"])
    assert changed == {"num_hidden_layers", "layer_types",
                       "num_local_experts", "max_position_embeddings"}
    for key, value in published.items():
        if key not in changed:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] == 10
    assert conf["layer_types"] == published["layer_types"][:10] \
        == 5 * ["mamba"] + ["attention"] + 4 * ["mamba"]
    assert conf["num_local_experts"] * conf["expert_parallel_size"] \
        == published["num_local_experts"] == 72
    assert conf["max_position_embeddings"] == 12800
    assert conf["source"] == row["source_url"]
    assert set(conf["reduced_from"]) == changed
    for said in ("head_dim", "expert width", "packing", "router",
                 "positions", "score scale", "state dtype", "gated norm",
                 "mamba_chunk_size", "init", "vocab_size",
                 "prefill_token_block", "expert_parallel_size"):
        assert said in conf["assumed"], said
    assert "4 pipeline stages" in conf["deployment"]
    assert "2 chips" in conf["deployment"]
    shape = configs.model_shape(conf)
    # by hand, in millions: a Mamba mixer, the shared MLP, the router,
    # the 36 experts held, the attention, the tied embedding
    w_in = 4096 * (8192 + (8192 + 2 * 128) + 128)
    mixer = w_in + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192
    shared, router = 3 * 4096 * 1536, 4096 * 72
    expert, attn = 3 * 4096 * 768, 2 * 4096 * 4096 + 2 * 4096 * 1024
    emb = 100352 * 4096
    assert round(mixer / 1e6, 2) == 102.29
    assert (round(shared / 1e6, 2), round(expert / 1e6, 3),
            round(attn / 1e6, 2), round(emb / 1e6, 1)) \
        == (18.87, 9.437, 41.94, 411.0)
    every = shared + router + 36 * expert + 2 * 4096
    held = 9 * (mixer + every) + attn + every + emb + 4096
    assert shape["held_params"] == held
    assert round(held / 1e6, 1) == 4962.7 and round(2 * held / 1e9, 2) \
        == 9.93
    # what ONE token's products touch: 5 of its 10 experts fall here
    touched = 9 * (w_in + 8192 * 4096) + attn \
        + 10 * (shared + router + 5 * expert) + emb
    assert shape["matmul_params"] == touched
    assert round(2 * (touched - emb) / 1e9, 2) == 3.25   # GFLOP a token
    # the program's own parameters are the same count
    from ray_tpu.models import granite_hybrid as gh
    cfg = configs.program_config(conf, 12800)
    tree = jax.eval_shape(lambda: gh.granite_hybrid_init(
        cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == held
    assert tree["blocks"][0]["moe"]["w1"].shape == (36, 4096, 1536)
    assert tree["blocks"][0]["moe"]["w2"].shape == (36, 768, 4096)
    assert (cfg.pattern, cfg.num_experts, cfg.mamba_n_groups,
            cfg.mamba_chunk_size, cfg.query_scale) \
        == ("MMMMM*MMMM", 72, 1, 256, 128 ** 0.5 / 128)


REFUSED = (
    ("position_embedding_type", "rope", "position embedding"),
    ("tie_word_embeddings", False, "untied head"),
    ("mamba_proj_bias", True, "bias on a projection"),
    ("hidden_act", "gelu", "activation"),
    ("layer_types", ["mamba"] * 9 + ["moe"], "layer kind"),
    ("mamba_expand", 3, "mamba_expand"),
)


@pytest.mark.parametrize("key,value,words", REFUSED,
                         ids=[r[0] for r in REFUSED])
def test_the_family_file_refuses_what_the_program_cannot_honour(key, value,
                                                                words):
    conf = {**configs.load_config(CONFIG), key: value}
    with pytest.raises(ValueError, match=words):
        configs.program_config(conf, 1024)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(configs.load_config(CONFIG), 12801)


def test_the_scans_operations_and_the_ticks_bytes_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    # a chunk of 256 and a head of 64 by 128: the output inside the
    # chunk, what the state gives and what it takes; C . B once a group
    head = 2 * 256 * 256 * 64 + 2 * 2 * 256 * 64 * 128
    chunk = 128 * head + 2 * 256 * 256 * 128
    assert cost.scan_flops(shape, 256) == 9 * chunk
    assert cost.scan_flops(shape, 3000) \
        == pytest.approx(9 * chunk * 3000 / 256)
    assert round(cost.scan_flops(shape, 1) / 9 / 1e6, 2) == 8.45  # a token
    token = 2 * (8192 + 2 * 128) + 4 * 128 + 4 * 8192
    state = 2 * 4 * 128 * 64 * 128
    assert cost.scan_bytes(shape, 3000, 2) == 9 * (3000 * token + 2 * state)
    # the two bounds stand close, the bytes ahead (y leaves in float32)
    ops_s = cost.scan_flops(shape, 4096) / 197e12
    mem_s = cost.scan_bytes(shape, 4096) / 8.19e11
    assert ops_s < mem_s < 2 * ops_s
    # the tick: 240 experts hit over ten layers, 7 slots live at 6,000 rows
    mixer = 4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192
    dense = 2 * (9 * mixer + 41_943_040
                 + 10 * (18_874_368 + 294_912 + 8192))
    assert shape["dense_bytes"] == dense
    assert shape["head_bytes"] == 2 * (100352 * 4096 + 4096)
    assert shape["row_bytes"] == 2 * 2 * 8 * 128 == 4096
    assert shape["state_bytes"] == 9 * (4 * 128 * 64 * 128
                                        + 2 * 3 * 8448)
    got = cost.tick_bytes(shape, 240, 7, 42_000)
    assert got == 240 * 9_437_184 * 2 + dense + shape["head_bytes"] \
        + 42_000 * 4096 + 2 * 7 * shape["state_bytes"]
    assert 8.0e9 < got < 8.6e9


def test_the_cell_is_sized_as_the_issue_asked():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == configs.load_config(CONFIG)["reduced"]
    assert entry["source"] == _catalog()["source_url"]
    mix = traffic.load_json("traffic", MIX)
    assert mix["prompt_tokens"]["values"] == [3000, 5120, 8192, 12288]
    assert mix["prompt_tokens"]["weights"] in ([0.30, 0.30, 0.25, 0.15],
                                               [0.35, 0.35, 0.20, 0.10])
    assert mix["output_tokens"] == {"values": [72, 152, 280],
                                    "weights": [0.3, 0.4, 0.3]}
    assert (mix["loop"], mix["clients"], mix["max_batch"],
            mix["max_seq_len"], mix["max_queue_depth"], mix["drain_s"],
            mix["request_timeout_s"], mix["replays"],
            mix["reference_new_tokens"]) \
        == ("closed", 8, 8, 12800, 8, 30, 120, 2, 48)
    assert 12288 + 280 <= mix["max_seq_len"] == 50 * 256
    # `correct` is checked at the shortest length: two token blocks, the
    # second ragged, and twelve chunks, the last ragged
    block = configs.load_config(CONFIG)["prefill_token_block"]
    assert divmod(3000, block) == (1, 952) and divmod(952, 256) == (3, 184)
    tol = mix["tolerances"]
    assert 0 < tol["logprob_mean_abs"] < tol["logprob_abs"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        # `in`, not "the only" or "the last": a later PR appends its cell
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["source"] == "device_trace"
    assert [by_name[n]["layer"] for n in NEW] \
        == ["model step", "kernels", "model step", "model step"]
    assert [by_name[n]["better"] for n in NEW] \
        == ["lower", "higher", "lower", "higher"]
    for name in THERE:
        assert CELL in by_name[name]["workloads"]
    tput = {e["name"]: e for e in bench["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in tput["workloads"] and tput["bound"] == 0.1
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    # the slab: one attention layer's rows, nine layers' states, 8 slots
    shape = configs.model_shape(configs.load_config(CONFIG))
    slot = 12800 * shape["row_bytes"] + shape["state_bytes"]
    assert round(12800 * shape["row_bytes"] / 1e6, 1) == 52.4
    assert round(slot / 1e6, 1) == 90.6 and round(8 * slot / 1e9, 2) == 0.73
    assert round((2 * shape["held_params"] + 8 * slot) / 1e9, 2) == 10.65


# ------------------------------------------------------------ the readers

HLO = """HloModule jit__prefill_paged, entry_computation_layout={()}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%f, metadata={op_name="jit(_prefill_paged)/mamba2/dot_general" source_file="a.py" source_line=2}
  %convolution.2 = f32[4]{0} convolution(%fusion.1, %fusion.1), metadata={op_name="jit(_prefill_paged)/mamba2/ssd_scan/while/body/closed_call/bqgn,bsgn->bgqs/dot_general" source_file="b.py"}
  %while.9 = (s32[], f32[4]{0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(_prefill_paged)/mamba2/ssd_scan/while" source_file="b.py"}
  %fusion.3 = f32[4]{0} fusion(%convolution.2), kind=kLoop, calls=%g, metadata={op_name="jit(_prefill_paged)/moe/sort"}
  %ragged-dot-none.4 = f32[4]{0} custom-call(%fusion.3), metadata={op_name="jit(_prefill_paged)/moe/ragged_dot"}
  %fusion.5 = f32[4]{0} fusion(%ragged-dot-none.4), kind=kOutput, calls=%h, metadata={op_name="jit(_prefill_paged)/shared_mlp/dot_general"}
  %gqa_prefill_w0_t3000.6 = f32[4]{0} custom-call(%fusion.5), metadata={op_name="jit(_prefill_paged)/attention/jit(_prefill_pallas)/pallas_call"}
  %copy.7 = f32[4]{0} copy(%gqa_prefill_w0_t3000.6)
  ROOT %fusion.8 = f32[4]{0} fusion(%copy.7), kind=kOutput, calls=%i, metadata={op_name="jit(_prefill_paged)/head/dot_general"}
}
"""


def test_an_operation_is_told_by_its_scope_and_the_scan_inside_its_mixer():
    # the chunks' loop is left out: its body's operations carry its time
    assert cost.scopes_of(HLO) == {
        "fusion.1": "mamba2", "convolution.2": "ssd_scan",
        "fusion.3": "moe", "ragged-dot-none.4": "moe",
        "fusion.5": "shared_mlp", "gqa_prefill_w0_t3000.6": "attention",
        "fusion.8": "head"}


def test_the_programs_own_text_names_the_sublayers():
    """A prefill at the toy size, compiled here from shapes alone: XLA
    carries the program's named scopes on the instructions of the
    compiled program, the scan's inside its mixer's among them."""
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy, "name": "granite-toy"}
    cell = {"conf": conf, "traffic": {"max_seq_len": 64, "max_batch": 2}}
    scopes = cost.prefill_scopes(cell, 16)
    assert set(scopes.values()) == set(cost.SCOPES)
    assert sum(s == "ssd_scan" for s in scopes.values()) >= 4
    assert cost.prefill_scopes(cell, 16) is cost.prefill_scopes(cell, 16)
    # a cell whose program cannot be built: no map, no error
    assert cost.prefill_scopes(
        {"conf": {"name": "none", "family": "no-such-family"},
         "traffic": {"max_seq_len": 64, "max_batch": 2}}, 16) == {}


def _record(ts, live, **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 8,
            "pending": 0, "admit_ms": 0.0, "admissions": [],
            "dispatch_ms": 1.0, "readback_ms": 6.0, "emit_ms": 0.5,
            "total_ms": 8.0, **more}


def _tick(ts, live, rows, hit):
    return _record(ts, live, live_rows=rows, moe_experts_hit=hit,
                   moe_pairs_held=5 * live * 10, moe_rows_max=4)


def _cell(config=CONFIG, mix=MIX):
    return {"seconds": 2.0, "conf": configs.load_config(config),
            "traffic": traffic.load_json("traffic", mix), "peaks": PEAKS}


@pytest.fixture()
def obs(monkeypatch):
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    for rec in [_tick(T0 - 4.0, 1, 3000, 50),            # the check's
                _tick(T0 + 0.1, 6, 36_000, 230),
                _tick(T0 + 0.2, 8, 50_000, 250),
                _record(T0 + 0.4, 0),                    # nothing decoding
                _tick(T0 + 5.0, 2, 9000, 90)]:           # the drain's
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
            "_tick": [("jit__tick(3)", 10 * ms, 12 * ms),
                      ("jit__tick(3)", 40 * ms, 14 * ms)],
            # prompts of 3,000 and 5,120 whole in the window; a third the
            # window's end cuts: left out
            "_prefill_paged": [("jit__prefill_paged(5)", 100 * ms, 200 * ms),
                               ("jit__prefill_paged(7)", 400 * ms,
                                300 * ms),
                               ("jit__prefill_paged(7)", 2900 * ms,
                                300 * ms)]},
        "ops": {
            "fusion.1": [ev("fusion.1", 100, 20.0), ev("fusion.1", 400, 30.0),
                         ev("fusion.1", 2900, 30.0),
                         ev("fusion.1", 10, 1.0)],      # a tick's
            "convolution.2": [ev("convolution.2", 130, 40.0),
                              ev("convolution.2", 450, 50.0)],
            "ragged-dot-none.4": [ev("ragged-dot-none.4", 200, 80.0),
                                  ev("ragged-dot-none.4", 520, 100.0)],
            "gqa_prefill_w0_t3000.6": [ev("gqa_prefill_w0_t3000.6", 290,
                                          4.0)],
            "gqa_prefill_w0_t5120.6": [ev("gqa_prefill_w0_t5120.6", 650,
                                          8.0)],
        }}
    maps = {3000: {"fusion.1": "ssd_scan", "convolution.2": "ssd_scan",
                   "ragged-dot-none.4": "moe"},
            5120: {"fusion.1": "mamba2", "convolution.2": "ssd_scan",
                   "ragged-dot-none.4": "moe"}}
    monkeypatch.setattr(cost, "prefill_scopes",
                        lambda cell, tokens: maps.get(tokens, {}))
    from ray_tpu.ops import dispatch
    monkeypatch.setattr(
        dispatch, "kernel_choices", lambda op=None: [
            {"op": "gqa_prefill", "shape": (1, t, 32, 8, 128, 0),
             "choice": "pallas"} for t in (3000, 5120, 8192, 12288)])
    yield {"phases": [{}], "trace": trace, "requests": [], "cell": _cell()}
    reqtrace._reset_store_for_tests()


def test_the_shares_count_a_prefills_own_operations(obs):
    # 3,000 tokens: fusion.1 20 + convolution.2 40 of 200 ms; 5,120:
    # convolution.2 50 of 300 (its fusion.1 is the mixer's there)
    assert readers.load_reader(NEW[0])(obs) \
        == pytest.approx(100.0 * 110.0 / 500.0)
    assert readers.load_reader(NEW[2])(obs) \
        == pytest.approx(100.0 * 180.0 / 500.0)
    assert cost.prefill_share(obs, "head") is None
    took, whole, tokens, count = cost.prefill_scope_seconds(obs, "ssd_scan")
    assert (took, whole, tokens, count) == (pytest.approx(0.110),
                                            pytest.approx(0.5), 8120, 2)


def test_the_scans_roofline_is_the_forms_work_over_the_scopes_time(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    least = cost.scan_bytes(shape, 8120, 2) / 8.19e11
    assert least > cost.scan_flops(shape, 8120) / 197e12
    assert readers.load_reader(NEW[1])(obs) \
        == pytest.approx(100.0 * least / 0.110)
    assert 0.1 < 100.0 * least / 0.110 < 100.0


def test_the_ticks_roofline_takes_the_windows_records(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    least = cost.tick_bytes(shape, 240, 7, 43_000)       # the two ticks'
    want = 100.0 * least / 8.19e11 / 13e-3
    assert readers.load_reader(NEW[3])(obs) == pytest.approx(want)
    assert 50.0 < want < 100.0


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A run without a trace; the rehearsal's trace; a program without
    the counters or the scopes, a family without the sizes (another
    cell's, the parent's): no number, no error."""
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
    ms = 1e6
    trace = {"window": (0.0, 3e9),
             "programs": {"_tick": [("jit__tick(3)", 10 * ms, 12 * ms)],
                          "_prefill_paged": [("jit__prefill_paged(5)",
                                              100 * ms, 60 * ms)]},
             "ops": {"fusion.1": [("fusion.1", 110 * ms, 5 * ms)]}}
    # a ring without the experts' counters, a prefill no kernel names
    assert read({"phases": [{}], "cell": _cell(), "requests": [],
                 "trace": trace}) is None
    # another family's cell: its shape() has none of the sizes
    other = _cell("jamba2-3b", "docqa-32k")
    store.record_loop(_tick(T0 + 0.2, 4, 4000, 100))
    assert read({"phases": [{}], "cell": other, "requests": [],
                 "trace": trace}) is None
    reqtrace._reset_store_for_tests()
