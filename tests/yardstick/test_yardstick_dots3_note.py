"""What `dots3-note-docnotes-32k` brings to the yardstick: the control of
its `correct` at a size a test run holds (the program as configured keeps
the cell's mean limit; every matrix rounded to 8 bits, dense attention in
place of the selected and a wrong selection all fail it), the operations
and bytes of `dsa_cost` by hand, the family file's arithmetic and
refusals, the cell's sizes as the issue gave them, and the five new
readers on a hand-made trace and loop ring."""
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

from benchmarks.harness import (configs, dsa_cost as cost,  # noqa: E402
                                readers, reference, traffic)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "dots3-note-l5-e32"
CELL = "dots3-note-docnotes-32k"
MIX = "docnotes-32k"
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}
NEW = ["dsa_index_roofline.tput", "dsa_attention_roofline.tput",
       "dsa_share_of_prefill.tput", "dsa_rows_selected_share.tput",
       "dsa_tick_bytes_roofline.tput"]
THERE = ["compiles_in_window.tput", "prefill_device_ms_per_ktok.tput",
         "tick_device_ms_mean.tput", "tick_live_slots_mean.tput",
         "device_idle_share.tput", "chip_empty_share.tput",
         "ttft_collision_share.tput", "client_ttft_p50_ms.tput",
         "expert_rows_max_over_mean.tput"]


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return {r["name"]: r for r in rows}["dots3-note-prev"]


# ----------------------------------------------------- the control of it

def _toy(dtype):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64), dtype=dtype)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper and the
    # logits flat: at 0.11 the layers, the indexer and the router count and
    # the gaps read what they read at the published widths on the chip
    # (bf16 0.013 here against 0.011 to 0.016 there, 8-bit matrices 0.038
    # against 0.037 to 0.040, dense for selected 0.128 against 0.119 to
    # 0.131, the first rows 0.185 against 0.166 to 0.173), so the cell's
    # own limit is what is tested
    keys = iter(jax.random.split(jax.random.PRNGKey(12), 200))
    params = jax.tree.map(
        lambda x: x + (0.11 * jax.random.normal(
            next(keys), x.shape, jnp.float32)).astype(x.dtype), params)
    return conf, cfg, params


def _round_to_8_bits(params):
    from benchmarks.probe_state_precision import round_in_place
    return round_in_place(jax.tree.map(jnp.copy, params), 8, 2)


def _mean_gap(conf, cfg, served, true):
    """The serving check's path and number: 14 tokens prefilled (over the
    toy's window of 9 and top 12), 40 decoded through the cache, the rings
    and the index keys; the program's log-probability of each token it
    chose against the reference's of the same token, the mean gap."""
    step, init_cache, _ = _model_fns(cfg)
    prefill = jax.jit(lambda p, t, c: step(p, t, cfg, c, 0))
    decode = jax.jit(lambda p, t, c, pos: step(p, t, cfg, c, pos))
    tokens = [int(t) for t in TOKENS[:14]]
    logits, cache = prefill(served, jnp.asarray(tokens)[None],
                            init_cache(cfg, 1))
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


def test_the_cells_mean_limit_tells_the_configured_program_from_the_rest():
    limit = traffic.load_json("traffic", MIX)["tolerances"][
        "logprob_mean_abs"]
    conf, cfg, params = _toy(jnp.bfloat16)
    good = _mean_gap(conf, cfg, params, params)
    eight_bits = _mean_gap(conf, cfg, _round_to_8_bits(params), params)
    dense = _mean_gap({**conf, "reference_selection": "dense"}, cfg, params,
                      params)
    first = _mean_gap({**conf, "reference_selection": "first"}, cfg, params,
                      params)
    assert good <= limit < min(eight_bits, dense, first), (
        good, eight_bits, dense, first)
    # float32 on both sides is the same function
    conf, cfg32, params32 = _toy(jnp.float32)
    assert _mean_gap(conf, cfg32, params32, params32) < 1e-4


# ----------------------------------------------- the family file by hand

def test_the_published_keys_and_the_parameters_by_hand():
    conf = configs.load_config(CONFIG)
    published = _catalog()["config"]
    changed = set(conf["reduced"])
    assert changed == {"num_hidden_layers", "layer_types",
                       "n_routed_experts", "max_position_embeddings"}
    for key, value in published.items():
        if key not in changed:
            assert conf[key] == value, key
    assert conf["layer_types"] == published["layer_types"][:5]
    assert conf["source"] == _catalog()["source_url"]
    assert set(conf["reduced_from"]) == changed
    shape = configs.model_shape(conf)
    d = 5120
    full = (d * 1024 + 1024 * 128 * 192 + d * 576 + 512 * 128 * 256
            + d * 128 + 128 * 128 * d)
    index = 1024 * 64 * 128 + d * 128 + d * 64
    sliding = (d * 1024 + 1024 * 64 * 256 + d * 1088 + 1024 * 64 * 320
               + d * 64 + 64 * 128 * d)
    expert = 3 * d * 1536
    assert (full, index, sliding) == (134_676_480, 9_371_648, 90_832_896)
    always = 2 * (full + index) + 3 * sliding + 3 * d * 13824 \
        + 4 * expert + 152064 * d
    assert shape["always_params"] == always
    assert shape["held_params"] == always + 152064 * d + 4 * d * 256 \
        + 4 * 32 * expert
    assert round(shape["held_params"] / 1e9, 2) == 5.45
    # the program holds what the family file reckons, and the norms
    cfg = configs.program_config(conf, 33280)
    params = jax.eval_shape(lambda: configs.init_params(conf, cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(params))
    assert 0 < held - shape["held_params"] < 200_000
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert round(nbytes / 1e9, 2) == 10.91
    # a token's matrix multiplications: one of the 8 chosen falls here
    assert shape["matmul_params"] == always + 4 * (d * 256 + expert)
    assert (shape["full_layers"], shape["sliding_layers"]) == (2, 3)
    assert (shape["row_full"], shape["row_index"], shape["row_ring"],
            shape["ring_rows"]) == (640, 128, 1152, 640)


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    for key, value, words in (
            ("scoring_func", "softmax", "scores other than sigmoid"),
            ("topk_method", "greedy", "noaux_tc"),
            ("rope_scaling", {"type": "yarn"}, "rotary scaling"),
            ("attention_gate_type", "elementwise", "headwise"),
            ("layer_types", ["full_attention"] * 4 + ["linear_attention"],
             "layer kind"),
            ("tie_word_embeddings", True, "tied head"),
            ("num_key_value_heads", 8, "grouped keys and values")):
        with pytest.raises(ValueError, match=words):
            configs.program_config({**conf, key: value}, 1024)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 40_000)
    cfg = configs.program_config(conf, 33280)
    assert cfg.full_layout == (1, 1, 0, 0, 0)
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, 32)
    assert (cfg.ring_rows, cfg.band_block, cfg.window) == (640, 512, 513)


# ------------------------------------------------------ the cost by hand

def test_the_index_score_the_selected_pairs_and_the_tick_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    assert cost.visible_pairs(4000) == 4000 * 4001 / 2
    # 2,048 queries see fewer rows than the indexer keeps; the rest 2,048
    assert cost.selected_pairs(4000, 2048) \
        == 2048 * 2049 / 2 + (4000 - 2048) * 2048
    assert cost.selected_pairs(1000, 2048) == cost.visible_pairs(1000)
    assert cost.index_flops(shape, 4000) == 2 * 64 * 128 * 4000 * 4001 / 2
    assert cost.index_bytes(shape, 4000) == (
        4000 * (64 * 128 + 128) * 2 + 4000 * 64 * 4
        + 4000 * 4001 / 2 * 4)
    assert cost.selected_flops(shape, 4000) \
        == 128 * 2 * (192 + 128) * cost.selected_pairs(4000, 2048)
    assert cost.selected_bytes(shape, 4000) == 4000 * 128 * 2 * 320 * 2
    # the program's calls a layer: blocks of 1,024 queries of the prompt
    # padded to them, a group of 16 heads
    assert [cost.index_calls(shape, t) for t in (200, 1000, 4000, 32768)] \
        == [1, 1, 4, 32]
    assert cost.selected_calls(shape) == 8
    # a tick: 8 slots at 10,000 rows, 40 experts hit
    want = (2 * shape["always_params"] + 4 * 4 * 5120 * 256
            + 40 * 2 * 3 * 5120 * 1536
            + 2 * 2 * (80_000 * 128 + 8 * 2048 * 640)
            + 3 * 2 * 8 * 513 * 1152)
    assert cost.tick_bytes(shape, 40, 80_000, 8 * 2048, 8 * 513) == want
    assert 3.5e9 < want < 5.5e9


def test_the_cell_is_sized_as_the_issue_asked():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == configs.load_config(CONFIG)["reduced"]
    mix = traffic.load_json("traffic", MIX)
    same = traffic.load_json("traffic", "docqa-32k")
    for key in ("loop", "clients", "prompt_tokens", "output_tokens",
                "max_batch", "max_seq_len", "replays",
                "reference_new_tokens"):
        assert mix[key] == same[key], key
    assert (mix["loop"], mix["clients"], mix["max_batch"],
            mix["max_seq_len"], mix["reference_new_tokens"]) \
        == ("closed", 8, 8, 33280, 48)
    tol = mix["tolerances"]
    assert 0 < tol["logprob_mean_abs"] < tol["logprob_abs"] < 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    # `in`, not "the last": a later PR appends its own cell behind this one
    for name in THERE:
        assert CELL in by_name[name]["workloads"]
    tput = {e["name"]: e for e in bench["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in tput["workloads"] and tput["bound"] == 0.1
    # the slab: two full layers of latent rows and index keys, three rings
    shape = configs.model_shape(configs.load_config(CONFIG))
    slab = 8 * 2 * (33280 * (shape["row_full"] + shape["row_index"]) * 2
                    ) + 8 * 3 * shape["ring_rows"] * shape["row_ring"] * 2
    assert round(slab / 1e9, 3) == 0.853


# ------------------------------------------------------------ the readers

def _record(ts, live, **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 8,
            "pending": 0, "admit_ms": 0.0, "admissions": [],
            "dispatch_ms": 1.0, "readback_ms": 9.0, "emit_ms": 0.5,
            "total_ms": 11.0, **more}


def _tick(ts, live, visible):
    return _record(ts, live, moe_experts_hit=30, dsa_rows_visible=visible,
                   dsa_rows_selected=min(visible, live * 2048),
                   ring_rows_read=live * 513, dsa_rows_scored=8 * 33280)


def _cell(config=CONFIG, mix=MIX):
    return {"seconds": 2.0, "conf": configs.load_config(config),
            "traffic": traffic.load_json("traffic", mix), "peaks": PEAKS}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    admitted = {"rid": 1, "prompt_tokens": 4000,
                "dsa_rows_visible": 4000 * 4001 // 2,
                "dsa_rows_selected": int(cost.selected_pairs(4000, 2048))}
    for rec in [_tick(T0 - 4.0, 1, 4010),                # the check's
                dict(_tick(T0 + 0.1, 6, 60_000), admissions=[admitted]),
                _tick(T0 + 0.2, 8, 100_000),
                _record(T0 + 0.3, 0),                    # nothing decoding
                _tick(T0 + 5.0, 1, 300)]:                # the drain's
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
            "_tick": [("jit__tick(3)", 10 * ms, 19 * ms),
                      ("jit__tick(3)", 400 * ms, 21 * ms)],
            "_prefill_paged": [("jit__prefill_paged(5)", 100 * ms, 200 * ms),
                               ("jit__prefill_paged(7)", 500 * ms,
                                600 * ms)]},
        "ops": {
            # a 4,000-token prompt: four calls a full layer, two layers
            "dsa_index_t4000.1": [ev("dsa_index_t4000.1", 100 + i, 0.25)
                                  for i in range(8)],
            "dsa_select_t4000.2": [ev("dsa_select_t4000.2", 110 + i, 0.5)
                                   for i in range(8)],
            # eight groups of heads a layer
            "mla_selected_t4000.3": [ev("mla_selected_t4000.3", 120 + i, 1.5)
                                     for i in range(16)],
            # a 16,384-token prompt of which the window holds ONE layer
            "dsa_index_t16384.4": [ev("dsa_index_t16384.4", 500 + i, 1.0)
                                   for i in range(16)],
            "mla_selected_t16384.5": [ev("mla_selected_t16384.5", 600 + i,
                                         20.0) for i in range(8)],
            "mla_band_w513_t4000.6": [ev("mla_band_w513_t4000.6", 150, 1.0)],
            "fusion.7": [ev("fusion.7", 160, 30)]}}
    yield {"phases": [{}], "trace": trace, "requests": [], "cell": _cell()}
    reqtrace._reset_store_for_tests()


def test_the_kernels_readers_take_the_length_from_the_name(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    # 8 index events of a 4,000-token prompt are 2 layers' worth, 16 of a
    # 16,384-token one (sixteen calls a layer) 1
    need = (2 * cost.index_flops(shape, 4000)
            + cost.index_flops(shape, 16384)) / 197e12
    want = 100.0 * need / (8 * 0.25e-3 + 16 * 1.0e-3)
    assert readers.load_reader(NEW[0])(obs) == pytest.approx(want)
    assert 1.0 < want < 100.0
    need = (2 * cost.selected_flops(shape, 4000)
            + cost.selected_flops(shape, 16384)) / 197e12
    want = 100.0 * need / (16 * 1.5e-3 + 8 * 20e-3)
    assert readers.load_reader(NEW[1])(obs) == pytest.approx(want)
    assert 1.0 < want < 100.0
    assert readers.load_reader(NEW[2])(obs) == pytest.approx(
        100.0 * (8 * 0.25 + 16 * 1.0 + 8 * 0.5 + 16 * 1.5 + 8 * 20.0)
        / 800.0)


def test_the_counters_readers_take_the_windows_records(obs):
    shape = configs.model_shape(obs["cell"]["conf"])
    # the admission of the window and its two decode passes
    selected = cost.selected_pairs(4000, 2048) + 6 * 2048 + 8 * 2048
    visible = 4000 * 4001 / 2 + 60_000 + 100_000
    assert readers.load_reader(NEW[3])(obs) \
        == pytest.approx(100.0 * selected / visible)
    least = (cost.tick_bytes(shape, 30, 60_000, 6 * 2048, 6 * 513)
             + cost.tick_bytes(shape, 30, 100_000, 8 * 2048, 8 * 513)) / 2
    assert readers.load_reader(NEW[4])(obs) \
        == pytest.approx(100.0 * least / 8.19e11 / 20e-3)
    assert readers.load_reader("tick_live_slots_mean.tput")(obs) \
        == pytest.approx(7.0)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A run without a trace; a program without the kernels and a family
    without an indexer (another cell's, the parent's): no number, no
    error."""
    reqtrace._reset_store_for_tests()
    read = readers.load_reader(name)
    assert read({"phases": [], "cell": _cell(), "trace": None,
                 "requests": []}) is None
    trace = {"window": (0.0, 3e9),
             "programs": {"_tick": [("jit__tick(1)", 1e8, 5e6)],
                          "_prefill_paged": [("jit__prefill_paged(1)", 2e8,
                                              5e7)]},
             "ops": {"mla_prefill_t1024": [("mla_prefill_t1024", 2e8, 1e6)]}}
    store = reqtrace.store()
    store.record_loop(_record(T0 + 0.1, 4, live_rows=4000))
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    other = _cell("deepseek-v2-l5-e40", "longdoc")
    assert read({"phases": [{}], "cell": other, "trace": trace,
                 "requests": []}) is None
    reqtrace._reset_store_for_tests()
