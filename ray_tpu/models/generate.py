"""Autoregressive generation over KV caches — the inference half of the
serving story (BASELINE config "Llama JAX replica, batched inference";
the reference serves torch models, generation itself lives outside its
tree, so this is native framework capability like models/llama.py).

TPU-first shape discipline: prefill is ONE jitted call over the padded
prompt, the decode loop is ONE jitted lax.scan over steps with the
cache donated — no per-token dispatch, no dynamic shapes. For token
streaming (Serve), `stream_generate` trades the scan for a jitted
single-step called from Python so each token can be yielded as it
lands.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from .llama import LlamaConfig, init_kv_cache, llama_forward_cached


def _model_fns(config):
    """(forward_cached, init_cache, ragged_decode) for the config's
    model family — generation and the continuous-batching engine are
    model-agnostic over this cache protocol. A `forward_cached` may
    carry, as its attribute `with_counters`, a form of itself that hands
    back a third value, a dict of small counters of the run (the engine's
    admission record), as `ragged_decode` may hand one back itself."""
    if isinstance(config, LlamaConfig):
        from .llama import llama_decode

        return llama_forward_cached, init_kv_cache, llama_decode
    from .gpt2 import (GPT2Config, gpt2_decode, gpt2_forward_cached,
                       gpt2_init_kv_cache)

    if isinstance(config, GPT2Config):
        return gpt2_forward_cached, gpt2_init_kv_cache, gpt2_decode
    from .nemotron_h import (NemotronHConfig, nemotron_h_decode,
                             nemotron_h_forward_cached,
                             nemotron_h_init_cache)

    if isinstance(config, NemotronHConfig):
        # a cache with state beside keys and values: module docstring of
        # models/nemotron_h.py, and the engine's splice
        return (nemotron_h_forward_cached, nemotron_h_init_cache,
                nemotron_h_decode)
    from .kimi_linear import (KimiLinearConfig, kimi_linear_decode,
                              kimi_linear_forward_cached,
                              kimi_linear_init_cache)

    if isinstance(config, KimiLinearConfig):
        # state beside ONE latent row a token: module docstring of
        # models/kimi_linear.py, and the engine's third kind of entry
        return (kimi_linear_forward_cached, kimi_linear_init_cache,
                kimi_linear_decode)
    from .deepseek_v2 import (DeepseekV2Config, deepseek_v2_decode,
                              deepseek_v2_forward_cached,
                              deepseek_v2_init_cache)

    if isinstance(config, DeepseekV2Config):
        # ONE latent row a token and nothing else: module docstring of
        # models/deepseek_v2.py
        return (deepseek_v2_forward_cached, deepseek_v2_init_cache,
                deepseek_v2_decode)
    from .smallthinker import (SmallThinkerConfig, smallthinker_decode,
                               smallthinker_forward_cached,
                               smallthinker_init_cache)

    if isinstance(config, SmallThinkerConfig):
        # keys and values at TWO row counts, the shorter a ring: module
        # docstring of models/smallthinker.py, and the engine's fourth
        # kind of entry
        return (smallthinker_forward_cached, smallthinker_init_cache,
                smallthinker_decode)
    from .jamba import (JambaConfig, jamba_decode, jamba_forward_cached,
                        jamba_init_cache)

    if isinstance(config, JambaConfig):
        # Mamba-1 state a RUN of layers beside ONE key-value head's rows:
        # module docstring of models/jamba.py
        return jamba_forward_cached, jamba_init_cache, jamba_decode
    raise TypeError(f"no generation support for {type(config).__name__}")


def lora_targets(config):
    """The LoRA-target leaves of a model family as
    ``((leaf_name, in_dim, out_dim), ...)`` — each names an entry of
    every block's ``["attn"]`` sub-tree. This table is the ONE place
    the serving stack (serve/lora.py AdapterPool, the engine's
    mixed-tenant decode, the per-tenant online trainer) learns which
    projections an adapter applies to, so the pool layout, the decode
    gather, and the prefill merge can never disagree."""
    if isinstance(config, LlamaConfig):
        kv_dim = config.num_kv_heads * config.head_dim
        return (("wq", config.d_model, config.d_model),
                ("wv", config.d_model, kv_dim))
    from .gpt2 import GPT2Config

    if isinstance(config, GPT2Config):
        return (("qkv", config.d_model, 3 * config.d_model),)
    raise TypeError(f"no LoRA support for {type(config).__name__}")


def merge_lora_params(params, config, lora):
    """Base params with ONE adapter's low-rank deltas folded into the
    target leaves: ``W + scale * (A_l @ B_l)`` per block. `lora` is the
    single-adapter slice ``{"scale": f32 scalar, "targets": {name:
    {"a": [L, in, r], "b": [L, r, out]}}}`` (serve/lora.py
    ``adapter_slice``). Called INSIDE the jitted prefill, so the merged
    leaves never persist — prefill is per-request single-tenant, only
    the decode tick needs the scatter-gathered per-slot form."""
    lora_targets(config)  # validates the family
    blocks = []
    for li, p in enumerate(params["blocks"]):
        attn = dict(p["attn"])
        for name, ab in lora["targets"].items():
            w = attn[name]
            delta = jnp.dot(ab["a"][li], ab["b"][li],
                            preferred_element_type=jnp.float32)
            attn[name] = w + (delta * lora["scale"]).astype(w.dtype)
        p2 = dict(p)
        p2["attn"] = attn
        blocks.append(p2)
    out = dict(params)
    out["blocks"] = blocks
    return out


def _sample_fn(vocab_size: int, temperature: float, top_k: int):
    def sample(key: jax.Array, logits: jax.Array) -> jax.Array:
        # padded vocab rows must never be sampled
        logits = logits[..., :vocab_size]
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k > 0 and top_k < vocab_size:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, -1e30, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(
            jnp.int32)

    return sample


@functools.partial(jax.jit, static_argnums=(2,))
def _prefill(params, prompt, config, cache):
    fwd = _model_fns(config)[0]
    logits, cache = fwd(params, prompt, config, cache, 0)
    return logits[:, -1], cache


def _decode_many(params, config, cache, first_token, start_pos, steps,
                 key, temperature, top_k):
    sample = _sample_fn(config.vocab_size, temperature, top_k)

    fwd = _model_fns(config)[0]

    def step(carry, _):
        cache, tok, pos, key = carry
        logits, cache = fwd(params, tok[:, None], config, cache, pos)
        key, sub = jax.random.split(key)
        nxt = sample(sub, logits[:, -1])
        return (cache, nxt, pos + 1, key), nxt

    (_, _, _, _), toks = jax.lax.scan(
        step, (cache, first_token, start_pos, key), None, length=steps)
    return jnp.moveaxis(toks, 0, 1)  # [B, steps]


_decode_many_jit = jax.jit(
    _decode_many, static_argnums=(1, 5, 7, 8), donate_argnums=(2,))


def generate(params: Any, config: LlamaConfig, prompt: jax.Array, *,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: int = 0, key: Optional[jax.Array] = None,
             eos_token: Optional[int] = None) -> jax.Array:
    """Batched generation: prompt [B, T0] int32 -> [B, max_new_tokens]
    int32. Greedy at temperature 0, else top-k/temperature sampling.
    With eos_token, tokens after a sequence's first EOS are replaced by
    EOS (compute still runs the full static length — TPU shapes)."""
    b, t0 = prompt.shape
    if t0 + max_new_tokens > config.max_seq_len:
        raise ValueError(
            f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({config.max_seq_len})")
    key = key if key is not None else jax.random.PRNGKey(0)
    cache = _model_fns(config)[1](config, b)
    last_logits, cache = _prefill(params, prompt, config, cache)
    key, k0 = jax.random.split(key)
    first = _sample_fn(config.vocab_size, temperature, top_k)(
        k0, last_logits)
    if max_new_tokens == 1:
        toks = first[:, None]
    else:
        rest = _decode_many_jit(params, config, cache, first,
                                jnp.int32(t0), max_new_tokens - 1, key,
                                temperature, top_k)
        toks = jnp.concatenate([first[:, None], rest], axis=1)
    if eos_token is not None:
        hit = jnp.cumsum(
            (toks == eos_token).astype(jnp.int32), axis=1) > 0
        done_before = jnp.concatenate(
            [jnp.zeros((b, 1), bool), hit[:, :-1]], axis=1)
        toks = jnp.where(done_before, eos_token, toks)
    return toks


def stream_generate(params: Any, config: LlamaConfig, prompt: jax.Array,
                    *, max_new_tokens: int, temperature: float = 0.0,
                    top_k: int = 0, key: Optional[jax.Array] = None,
                    eos_token: Optional[int] = None
                    ) -> Iterator[jax.Array]:
    """Yield one [B] int32 token batch per decode step — the producer
    Serve's streaming path consumes for token-by-token LLM responses.
    Uses a jitted single step per token (streaming is latency-bound at
    the consumer; per-step dispatch is irrelevant next to the yield)."""
    b, t0 = prompt.shape
    if t0 + max_new_tokens > config.max_seq_len:
        raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
    key = key if key is not None else jax.random.PRNGKey(0)
    sample = _sample_fn(config.vocab_size, temperature, top_k)
    cache = _model_fns(config)[1](config, b)
    last_logits, cache = _prefill(params, prompt, config, cache)
    key, sub = jax.random.split(key)
    tok = sample(sub, last_logits)
    pos = t0
    done = jnp.zeros((b,), bool)
    for _ in range(max_new_tokens):
        out = tok
        if eos_token is not None:
            out = jnp.where(done, eos_token, tok)
            done = done | (tok == eos_token)
        yield out
        if eos_token is not None and bool(done.all()):
            return
        cache, tok, key = _stream_step(params, cache, config, tok,
                                       jnp.int32(pos), temperature,
                                       top_k, key)
        pos += 1


@functools.partial(jax.jit, static_argnums=(2, 5, 6),
                   donate_argnums=(1,))
def _stream_step(params, cache, config, tok, pos, temperature, top_k,
                 key):
    # module-level so the compiled step is shared across every
    # stream_generate call with the same (config, sampling) — a serving
    # replica must not recompile per request
    fwd = _model_fns(config)[0]
    logits, cache = fwd(params, tok[:, None], config, cache, pos)
    key, sub = jax.random.split(key)
    nxt = _sample_fn(config.vocab_size, temperature, top_k)(
        sub, logits[:, -1])
    return cache, nxt, key
