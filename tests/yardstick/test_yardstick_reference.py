"""The plain float32 references agree with the program at a tiny size on
the CPU: the program's training forward and its cached (prefill, then
decode) path against one full forward pass of the reference, on seeded
random weights in float32, so the tolerance can be tight."""
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

from benchmarks.harness import configs, reference  # noqa: E402
from ray_tpu.models import gpt2, llama  # noqa: E402

# float32 on both sides, different summation orders: a few 1e-5 at most
TOL = 2e-4

GPT2_CONF = {"family": "gpt2", "n_layer": 2, "n_embd": 64, "n_head": 4,
             "vocab_size": 250, "n_positions": 48,
             "layer_norm_epsilon": 1e-5}
LLAMA_CONF = {"family": "llama", "hidden_size": 64,
              "intermediate_size": 160, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 250,
              "rope_theta": 1e6, "rms_norm_eps": 1e-6}


def _gpt2():
    cfg = gpt2.GPT2Config(vocab_size=250, max_seq_len=48, num_layers=2,
                          num_heads=4, d_model=64, dtype=jnp.float32)
    params = gpt2.gpt2_init(cfg, jax.random.PRNGKey(3))
    # biases and norm offsets are zero at init: make them count
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 200))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                               x.dtype), params)
    return cfg, params


def _llama():
    cfg = llama.LlamaConfig(vocab_size=250, max_seq_len=48, num_layers=2,
                            num_heads=4, num_kv_heads=2, d_model=64,
                            d_ff=160, rope_theta=1e6, dtype=jnp.float32)
    params = llama.llama_init(cfg, jax.random.PRNGKey(5))
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 200))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                               x.dtype), params)
    return cfg, params


TOKENS = np.random.default_rng(0).integers(1, 250, 40).astype(np.int32)


def test_gpt2_forward_agrees():
    cfg, params = _gpt2()
    got = gpt2.gpt2_forward(params, TOKENS[None], cfg)[0, :, :250]
    want = reference.logits(GPT2_CONF, params, TOKENS)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_gpt2_loss_agrees():
    cfg, params = _gpt2()
    toks, tgts = TOKENS[None, :-1], TOKENS[None, 1:]
    got = float(gpt2.gpt2_loss(params, toks, tgts, cfg))
    want = reference.mean_loss(GPT2_CONF, params, toks, tgts)
    assert got == pytest.approx(want, abs=TOL)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_the_family_tables_training_loss_agrees(family):
    """What the training cell runs for a family (`configs.train_program`)
    against the reference's loss: the cell's code names no model."""
    (cfg, params), conf = ((_gpt2(), GPT2_CONF) if family == "gpt2"
                           else (_llama(), LLAMA_CONF))
    loss_fn, specs = configs.train_program(conf, cfg, remat=False)
    batch = {"tokens": np.stack([TOKENS[:-1], TOKENS[:0:-1]]),
             "targets": np.stack([TOKENS[1:], TOKENS[-2::-1]])}
    got = float(jax.jit(loss_fn)(params, batch))
    want = reference.mean_loss(conf, params, batch["tokens"],
                               batch["targets"])
    assert got == pytest.approx(want, abs=TOL)
    assert jax.tree.structure(specs, is_leaf=lambda x: x is None
                              or not isinstance(x, (dict, list))) \
        .num_leaves > 0
    assert set(configs.family(conf).train_kernels) <= {
        "flash_attention", "linear_cross_entropy"}


@pytest.mark.parametrize("name,seq,params,flops", [
    # 12 x 12 x 768^2 + 50,257 x 768; 6 N + 12 x 12 x 768 x 1,024 / 2
    ("gpt2-124m", 1024, 123_532_032, 797_815_296.0),
    # 8 x (2 x 4,096^2 + 2 x 4,096 x 1,024 + 3 x 4,096 x 14,336)
    # + 32,768 x 4,096; 6 N + 12 x 8 x 4,096 x 4,096 / 2
    ("mistral-7b-v0.3-l8", 4096, 1_879_048_192,
     6.0 * 1_879_048_192 + 805_306_368.0)])
def test_train_flops_per_token_by_hand(name, seq, params, flops):
    conf = configs.load_config(name)
    shape = configs.model_shape(conf)
    assert shape["matmul_params"] == params
    assert shape["heads"] * shape["head_dim"] == shape["d_model"]
    assert configs.train_flops_per_token(conf, seq) == flops


def test_llama_forward_agrees():
    cfg, params = _llama()
    got = llama.llama_forward(params, TOKENS[None], cfg)[0, :, :250]
    want = reference.logits(LLAMA_CONF, params, TOKENS)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_prefill_then_decode_through_the_cache_agrees(family):
    """32 prompt tokens prefilled, 8 more decoded one at a time through
    the cache, against ONE full forward pass of the reference."""
    if family == "gpt2":
        (cfg, params), conf = _gpt2(), GPT2_CONF
        cache = gpt2.gpt2_init_kv_cache(cfg, 1)
        step = gpt2.gpt2_forward_cached
    else:
        (cfg, params), conf = _llama(), LLAMA_CONF
        cache = llama.init_kv_cache(cfg, 1)
        step = llama.llama_forward_cached
    logits, cache = step(params, TOKENS[None, :32], cfg, cache,
                         jnp.int32(0))
    rows = [logits[0, -1]]
    for pos in range(32, 39):
        logits, cache = step(params, TOKENS[None, pos:pos + 1], cfg,
                             cache, jnp.int32(pos))
        rows.append(logits[0, -1])
    want = reference.logits(conf, params, TOKENS[:39])[31:]
    np.testing.assert_allclose(jnp.stack(rows)[:, :250], want, atol=TOL,
                               rtol=0)


def test_score_emitted_reads_the_right_positions():
    cfg, params = _llama()
    prompt, emitted = list(TOKENS[:30]), list(TOKENS[30:34])
    out = reference.score_emitted(LLAMA_CONF, params, prompt, emitted)
    lg = reference.logits(LLAMA_CONF, params, TOKENS[:33])
    lp = jax.nn.log_softmax(lg, -1)
    for j, tok in enumerate(emitted):
        assert out[j]["logprob"] == pytest.approx(float(lp[29 + j, tok]),
                                                  abs=1e-6)
        assert out[j]["margin"] >= 0.0


def test_a_lower_precision_would_fail_the_tolerance():
    """The tolerance is tight enough to tell bfloat16 from float32."""
    cfg, params = _llama()
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    cfg16 = llama.LlamaConfig(**{**cfg.__dict__, "dtype": jnp.bfloat16})
    got = llama.llama_forward(low, TOKENS[None], cfg16)[0, :, :250]
    want = reference.logits(LLAMA_CONF, params, TOKENS)
    assert float(jnp.max(jnp.abs(got - want))) > TOL
