"""What `nemotron-3-super-reason` brings to the yardstick: the control of
its `correct` (the program with its recurrence state in the next
precision down must fall outside what float32 on both sides allows, at a
size a test run holds), its two per-layer readers on a hand-made loop
ring, and the family file's own arithmetic by hand."""
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

from benchmarks.harness import configs, readers, reference  # noqa: E402
from ray_tpu.models.generate import _model_fns  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

CONFIG = "nemotron-3-super-l11-e128"
TOL = 2e-4      # test_yardstick_reference.py's: float32 on both sides
TOKENS = np.random.default_rng(1).integers(1, 500, 40).astype(np.int32)
T0 = 2_000_000.0


def _toy(**changes):
    conf = configs.load_config(CONFIG)
    conf = {**conf, **configs.family(conf).toy}
    cfg = dataclasses.replace(configs.program_config(conf, 48),
                              dtype=jnp.float32, **changes)
    params = configs.init_params(conf, cfg, 11)
    # at 64 wide the init's 0.02 leaves every layer a whisper beside the
    # embedding: make the layers count, as they do at 4,096
    keys = iter(jax.random.split(jax.random.PRNGKey(12), 200))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape,
                                              x.dtype), params)
    return conf, cfg, params


def _through_the_cache(cfg, params):
    """18 tokens prefilled (a ragged last chunk), 8 decoded through the
    recurrence: the serving check's path."""
    step, init_cache, _ = _model_fns(cfg)
    logits, cache = step(params, TOKENS[None, :18], cfg,
                         init_cache(cfg, 1), jnp.int32(0))
    rows = [logits[0, -1]]
    for pos in range(18, 25):
        logits, cache = step(params, TOKENS[None, pos:pos + 1], cfg, cache,
                             jnp.int32(pos))
        rows.append(logits[0, -1])
    return jax.nn.log_softmax(jnp.stack(rows), -1)


def test_a_bf16_state_would_fail_what_float32_allows():
    conf, cfg, params = _toy()
    want = jax.nn.log_softmax(
        reference.logits(conf, params, TOKENS[:25])[17:], -1)
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
        reference.logits(other, params, TOKENS[:25])[17:], -1)
    assert float(jnp.max(jnp.abs(got - want))) > TOL


def test_the_family_file_refuses_what_the_program_cannot_honour():
    conf = configs.load_config(CONFIG)
    assert configs.program_config(conf, 1536).n_routed_experts == 512
    for key, value in [("num_nextn_predict_layers", 1), ("n_group", 8),
                       ("mlp_hidden_act", "silu"), ("use_bias", True),
                       ("tie_word_embeddings", True), ("expand", 4),
                       ("num_hidden_layers", 12), ("norm_eps", 1e-6),
                       ("hybrid_override_pattern", "MEMEMEMEM-E")]:
        with pytest.raises(ValueError, match="Nemotron-H path has no"):
            configs.program_config({**conf, key: value}, 1536)
    with pytest.raises(ValueError, match="exceeds the file's"):
        configs.program_config(conf, 1537)


def test_the_published_widths_and_the_parameters_by_hand():
    c = configs.load_config(CONFIG)
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["ssm_state_size"], c["n_groups"], c["conv_kernel"],
            c["chunk_size"]) == (4096, 128, 64, 128, 8, 4, 128)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (32, 2, 128)
    assert (c["n_routed_experts"] * c["expert_parallel_size"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["moe_latent_size"], c["moe_shared_expert_intermediate_size"],
            c["routed_scaling_factor"], c["vocab_size"]) \
        == (512, 22, 2688, 1024, 5376, 5, 131072)
    assert c["hybrid_override_pattern"].count("M") == 5
    per = configs.family(c).config.__globals__["layer_params"](c)
    # in 4,096 x (8,192 + 10,240 + 128), out 8,192 x 4,096
    assert per["M"] == 4096 * 18560 + 8192 * 4096
    assert per["*"] == 2 * 4096 * 4096 + 2 * 4096 * 256
    # router, two latent projections, 5.5 of the 22 chosen experts,
    # the shared expert
    assert per["E"] == 4096 * 512 + 2 * 4096 * 1024 \
        + 5.5 * 2 * 1024 * 2688 + 2 * 4096 * 5376
    shape = configs.model_shape(c)
    assert shape["matmul_params"] == 5 * per["M"] + per["*"] \
        + 5 * per["E"] + 131072 * 4096
    assert (shape["expert_layers"], shape["experts_held"]) == (5, 128)
    # what the program holds: every expert held, the embedding too
    cfg = configs.program_config(c, 1536)
    held = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: configs.init_params(c, cfg, 0))))
    mamba = per["M"] + 5 * 10240 + 3 * 128 + 8192   # conv, dt A D, norm
    experts = 4096 * 512 + 512 + 2 * 4096 * 1024 \
        + 128 * 2 * 1024 * 2688 + 2 * 4096 * 5376
    assert held == 5 * mamba + per["*"] + 5 * experts + 11 * 4096 \
        + 2 * 131072 * 4096 + 4096 == 5_453_470_080


# ------------------------------------------------------------ the readers

def _record(ts, live, admissions=(), **more):
    return {"engine_id": "cb-test", "ts": ts, "live": live,
            "max_batch": 96, "pending": 0, "admit_ms": 0.0,
            "admissions": list(admissions), "dispatch_ms": 1.0,
            "readback_ms": 20.0, "emit_ms": 0.5, "total_ms": 22.0, **more}


def _admission(splice_ms, **more):
    return {"rid": 0, "prompt_tokens": 192, "suffix_tokens": 192,
            "reused_tokens": 0, "lookup_ms": 0.0, "prefill_ms": 15.0,
            "commit_ms": 0.0, "commit_dispatches": 0, "commit_blocks": 0,
            "splice_ms": splice_ms, **more}


@pytest.fixture()
def obs():
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    state = {"state_bytes": 21_278_720}
    for rec in [
            # before the window: the reference check's admission
            _record(T0 - 4.0, 1, [_admission(90.0, **state)],
                    moe_pairs_held=640, moe_rows_max=64),
            # 5 expert layers x 128 held = 640 groups
            _record(T0 + 0.1, 0, [_admission(0.4, **state)]),
            _record(T0 + 0.2, 1, moe_pairs_held=1280, moe_rows_max=6),
            _record(T0 + 0.3, 2, [_admission(0.8, **state)],
                    moe_pairs_held=2560, moe_rows_max=16),
            # an admission of a family without state is not this metric's
            _record(T0 + 0.4, 2, [_admission(50.0)]),
            _record(T0 + 5.0, 1, [_admission(70.0, **state)],
                    moe_pairs_held=640, moe_rows_max=64)]:
        store.record_loop(rec)
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 900.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    yield {"phases": [{}], "cell": {
        "seconds": 2.0, "conf": configs.load_config(CONFIG)}}
    reqtrace._reset_store_for_tests()


def test_splice_ms_mean_reads_the_admissions_that_carried_state(obs):
    read = readers.load_reader("splice_ms_mean.itl")
    assert read(obs) == pytest.approx(0.6)        # (0.4 + 0.8) / 2


def test_expert_rows_max_over_mean_on_a_hand_made_ring(obs):
    read = readers.load_reader("expert_rows_max_over_mean.itl")
    # 6 rows against a mean of 2, 16 against a mean of 4
    assert read(obs) == pytest.approx((3.0 + 4.0) / 2)


@pytest.mark.parametrize("name", ["splice_ms_mean.itl",
                                  "expert_rows_max_over_mean.itl"])
def test_the_new_readers_return_none_where_there_is_nothing(name):
    """A program without the counters, as the parent of PR 27 is, or a
    cell whose family keeps no state: no number, and no error."""
    reqtrace._reset_store_for_tests()
    store = reqtrace.store()
    read = readers.load_reader(name)
    cell = {"seconds": 2.0, "conf": configs.load_config("gpt2-124m")}
    assert read({"phases": [], "cell": cell}) is None
    store.record_loop(_record(T0 + 0.1, 1, [_admission(0.3)]))
    store.record({"kind": "trace", "request_id": "r0", "ts": T0,
                  "total_ms": 100.0, "outcome": "ok", "attempts": 1,
                  "replayed": False, "preempts": 0, "phases": [],
                  "phase_ms": {}})
    assert read({"phases": [{}], "cell": cell}) is None
    reqtrace._reset_store_for_tests()
