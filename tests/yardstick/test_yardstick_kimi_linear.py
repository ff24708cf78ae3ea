"""What `kimi-linear-generate` brings to the yardstick: the control of its
`correct` (the program with its delta-rule state in the next precision
down must fall outside what float32 on both sides allows, at a size a
test run holds), the bytes of a tick by hand at the cell's sizes, its two
per-layer readers on a hand-made loop ring, and the family file's own
arithmetic by hand."""
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

from benchmarks.harness import (configs, kimi_linear_cost,  # noqa: E402
                                readers, reference, traffic)
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "kimi-linear-48b-l8-e64"
CELL = "kimi-linear-generate"
TOL = 2e-4      # test_yardstick_reference.py's: float32 on both sides
TOKENS = np.random.default_rng(1).integers(1, 500, 64).astype(np.int32)
T0 = 2_000_000.0


def _toy(**changes):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 64),
                              dtype=jnp.float32, **changes)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper beside the
    # embedding: make the layers count, as they do at 2,304
    keys = iter(jax.random.split(jax.random.PRNGKey(12), 200))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape,
                                              x.dtype), params)
    return conf, cfg, params


def _through_the_cache(cfg, params):
    """18 tokens prefilled (4.5 chunks of 4: a ragged last chunk), 32
    decoded through the recurrence: the serving check's path."""
    step, init_cache, _ = _model_fns(cfg)
    logits, cache = step(params, TOKENS[None, :18], cfg,
                         init_cache(cfg, 1), 0)
    rows = [logits[0, -1]]
    for pos in range(18, 49):
        logits, cache = step(params, TOKENS[None, pos:pos + 1], cfg, cache,
                             jnp.int32(pos))
        rows.append(logits[0, -1])
    return jax.nn.log_softmax(jnp.stack(rows), -1)


def test_a_bf16_state_would_fail_what_float32_allows():
    conf, cfg, params = _toy()
    want = jax.nn.log_softmax(
        reference.logits(conf, params, TOKENS[:49])[17:], -1)
    good = float(jnp.max(jnp.abs(_through_the_cache(cfg, params) - want)))
    low = dataclasses.replace(cfg, state_dtype=jnp.bfloat16)
    bad = float(jnp.max(jnp.abs(_through_the_cache(low, params) - want)))
    assert good <= TOL < bad
    assert bad > 10 * good


def test_the_reference_is_given_the_programs_share():
    """A reference of another share (experts 4 to 7 of 16) is another
    function: the program's logits are not its logits."""
    conf, cfg, params = _toy()
    got = _through_the_cache(cfg, params)
    other = {**conf, "expert_parallel_rank": 1}
    want = jax.nn.log_softmax(
        reference.logits(other, params, TOKENS[:49])[17:], -1)
    assert float(jnp.max(jnp.abs(got - want))) > TOL


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    cfg = configs.program_config(conf, 2816)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.pattern) == (
        256, 64, "KKKAKKKA")
    for key, value in [("hidden_act", "gelu"), ("mla_use_nope", False),
                       ("moe_router_activation_func", "softmax"),
                       ("q_lora_rank", 1536), ("num_expert_group", 8),
                       ("moe_layer_freq", 2), ("num_key_value_heads", 8),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("num_hidden_layers", 9)]:
        with pytest.raises(ValueError, match="Kimi-Linear path has no"):
            configs.program_config({**conf, key: value}, 2816)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 2817)


def test_the_published_widths_and_the_parameters_by_hand():
    c = configs.load_config(CONFIG)
    lin = c["linear_attn_config"]
    assert (c["hidden_size"], c["num_attention_heads"], lin["num_heads"],
            lin["head_dim"], lin["short_conv_kernel_size"]) \
        == (2304, 32, 32, 128, 4)
    assert (c["kv_lora_rank"], c["qk_rope_head_dim"],
            c["qk_nope_head_dim"], c["v_head_dim"]) == (512, 64, 128, 128)
    assert (c["num_experts"] * c["expert_parallel_size"],
            c["num_experts_per_token"], c["moe_intermediate_size"],
            c["routed_scaling_factor"], c["intermediate_size"],
            c["vocab_size"], c["first_k_dense_replace"]) \
        == (256, 8, 1024, 2.446, 9216, 163840, 1)
    assert c["reduced"] == ["num_hidden_layers", "linear_attn_config",
                            "num_experts", "model_max_length"]
    per = configs.family(c).config.__globals__["layer_params"](c)
    # q, k, v 2,304 x 4,096 each with the two low-rank ins and beta in one
    # product, the low-rank outs 128 x 4,096, out 4,096 x 2,304
    assert per["K"] == 2304 * (3 * 4096 + 128 + 128 + 32) \
        + 2 * 128 * 4096 + 4096 * 2304 == 39_460_864
    assert per["A"] == 2304 * 6144 + 2304 * 576 + 512 * 8192 \
        + 4096 * 2304 == 29_114_368
    assert per["dense"] == 3 * 2304 * 9216 == 63_700_992
    assert per["expert"] == 3 * 2304 * 1024 == 7_077_888
    # router, 2 of the 8 chosen experts, the shared expert
    assert per["E"] == 2304 * 256 + 2 * 7_077_888 + 7_077_888
    shape = configs.model_shape(c)
    assert shape["matmul_params"] == 6 * per["K"] + 2 * per["A"] \
        + per["dense"] + 7 * per["E"] + 163840 * 2304 == 888_946_688
    assert (shape["expert_layers"], shape["experts_held"]) == (7, 64)
    # what the program holds: every expert held, the embedding too
    cfg = configs.program_config(c, 2816)
    held = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: configs.init_params(c, cfg, 0))))
    kda = per["K"] + 4 * 12288 + 4096 + 32 + 128    # conv, dt, A, norm
    experts = 2304 * 256 + 256 + 65 * 7_077_888
    assert held == 6 * kda + 2 * (per["A"] + 512) + per["dense"] \
        + 7 * experts + 8 * 2 * 2304 + 2304 + 2 * 163840 * 2304 \
        == shape["held_params"] == 4_338_599_872


def test_the_bytes_of_the_share_and_of_a_tick_by_hand():
    shape = configs.model_shape(configs.load_config(CONFIG))
    mix = traffic.load_json("traffic", "generate")
    # 8.68 GB of bf16
    assert kimi_linear_cost.held_bytes(shape) == 8_677_199_744
    # a slot: 6 x (32 x 128 x 128 float32 + 3 x 12,288 bf16) and 2,816
    # rows of 576 bf16 in each of 2 latent layers: 19.5 MB
    assert kimi_linear_cost.slot_bytes(shape, mix["max_seq_len"]) \
        == 6 * (2_097_152 + 73_728) + 2 * 2816 * 1152 == 19_513_344
    # a full tick: every held expert hit, 128 slots live at their last row
    always = 2 * shape["always_params"]
    assert always == 1_580_437_376
    full = kimi_linear_cost.tick_bytes(shape, 7 * 64, 128, 128 * 2816)
    assert full == always + 448 * 14_155_776 \
        + 2 * 128 * 6 * 2_097_152 + 128 * 2816 * 2 * 1152
    assert round(full / 1e9, 1) == 12.0
    # an idle engine's tick still reads what every token reads
    assert kimi_linear_cost.tick_bytes(shape, 0, 0, 0) == always


def test_the_cell_is_sized_as_the_issue_reckoned_it():
    mix = traffic.load_json("traffic", "generate")
    assert (mix["max_batch"], mix["max_seq_len"], mix["max_queue_depth"],
            mix["reference_new_tokens"], mix["loop"]) \
        == (128, 2816, 256, 32, "open")
    assert mix["prompt_tokens"] == {"values": [288, 512, 1024, 2048],
                                    "weights": [0.3, 0.35, 0.25, 0.1]}
    assert mix["output_tokens"] == {"values": [256, 512, 768],
                                    "weights": [0.3, 0.4, 0.3]}
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    # the reference check's prompt ends in a ragged chunk
    conf = configs.load_config(CONFIG)
    assert traffic.prompt_lengths(mix)[0] % conf["kda_chunk_size"] == 32
    # the slab as the program lays it: the latent rows padded to 640
    cfg = configs.program_config(conf, mix["max_seq_len"])
    slab = jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, mix["max_batch"]))
    assert [sorted(e) for e in slab] == [["k"]] * 2 \
        + [["conv", "state"]] * 6
    assert slab[0]["k"].shape == (128, 2816, 640)
    assert slab[2]["state"].shape == (128, 32, 128, 128) \
        and slab[2]["state"].dtype == jnp.float32
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(slab))
    assert total == 128 * (6 * (2_097_152 + 73_728) + 2 * 2816 * 1280) \
        == 2_589_982_720


def test_every_line_of_the_benchmark_file_keeps_to_200_characters():
    """A configuration's `why` is held to the same 200 printable
    characters as a cell's: this PR's first was 205, and the check
    refused the file for it before any run."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    lines = [(e["name"], key, e[key])
             for e in bench["configs"] for key in ("why", "source")]
    lines += [(e["name"], "why", e["why"]) for e in bench["workloads"]]
    lines += [(e["name"], "layer", e["layer"]) for e in bench["per_layer"]]
    lines += [("command", "word", w) for w in bench["command"]]
    for name, key, text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, key)


# ------------------------------------------------------------ the readers

def _record(ts, live, admissions=(), **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live,
            "max_batch": 128, "pending": 0, "admit_ms": 0.0,
            "admissions": list(admissions), "dispatch_ms": 1.0,
            "readback_ms": 20.0, "emit_ms": 0.5, "total_ms": 22.0, **more}


def _admission(prompt_tokens, prefill_ms):
    return {"rid": 0, "prompt_tokens": prompt_tokens,
            "suffix_tokens": prompt_tokens, "reused_tokens": 0,
            "lookup_ms": 0.0, "prefill_ms": prefill_ms, "commit_ms": 0.0,
            "commit_dispatches": 0, "commit_blocks": 0, "splice_ms": 0.5,
            "state_bytes": 13_025_280}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    counted = {"moe_pairs_held": 256, "moe_rows_max": 16}
    for rec in [
            # before the window: the reference check
            _record(T0 - 4.0, 1, [_admission(288, 900.0)], live_rows=300,
                    moe_experts_hit=448, **counted),
            _record(T0 + 0.1, 0, [_admission(288, 28.8)], live_rows=0),
            _record(T0 + 0.2, 1, live_rows=289, moe_experts_hit=400,
                    **counted),
            _record(T0 + 0.3, 3, [_admission(1024, 51.2)], live_rows=1711,
                    moe_experts_hit=420, **counted),
            # an adoption prefills nothing here
            _record(T0 + 0.4, 3, [_admission(512, 0.0)], live_rows=1714),
            _record(T0 + 5.0, 1, [_admission(288, 700.0)], live_rows=300,
                    moe_experts_hit=448, **counted)]:
        store.record_loop(rec)
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    yield {"phases": [{}],
           "trace": {"programs": {"_tick": [("jit__tick", 0, 20e6),
                                            ("jit__tick", 0, 30e6)]}},
           "cell": {"seconds": 2.0, "conf": configs.load_config(CONFIG),
                    "peaks": {"hbm_bytes_per_s": 8.19e11}}}
    reqtrace._reset_store_for_tests()


def test_prefill_ms_per_ktok_reads_the_windows_admissions(obs):
    read = readers.load_reader("prefill_ms_per_ktok.itl")
    # 28.8 ms for 288 tokens, 51.2 for 1,024; the adoption left out
    assert read(obs) == pytest.approx((100.0 + 50.0) / 2)


def test_tick_bytes_roofline_on_a_hand_made_ring(obs):
    read = readers.load_reader("tick_bytes_roofline.itl")
    shape = configs.model_shape(obs["cell"]["conf"])
    # the two decode passes of the window that counted their experts
    # (the pass with an adoption has no tick's counters), a 25 ms tick
    least = (kimi_linear_cost.tick_bytes(shape, 400, 1, 289)
             + kimi_linear_cost.tick_bytes(shape, 420, 3, 1711)) / 2
    assert read(obs) == pytest.approx(100.0 * least / 8.19e11 / 25e-3)
    assert 30.0 < read(obs) < 40.0
    assert read({**obs, "trace": None}) is None


@pytest.mark.parametrize("name", ["tick_bytes_roofline.itl",
                                  "prefill_ms_per_ktok.itl"])
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A program without the ring or the counters: no number, no error."""
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    read = readers.load_reader(name)
    cell = {"seconds": 2.0, "conf": configs.load_config(CONFIG),
            "peaks": {"hbm_bytes_per_s": 8.19e11}}
    trace = {"programs": {"_tick": [("jit__tick", 0, 20e6)]}}
    assert read({"phases": [], "cell": cell, "trace": trace}) is None
    # a ring of the parent's: no live_rows, no moe_experts_hit, an
    # admission that prefilled nothing
    store.record_loop(_record(T0 + 0.1, 1, [_admission(288, 0.0)]))
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 100.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    assert read({"phases": [{}], "cell": cell, "trace": trace}) is None
    reqtrace._reset_store_for_tests()
