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

from .family import family_of


def _model_fns(config):
    """(forward_cached, init_cache, decode) of the config's family: the
    three of its record (`models/family.py`) the older callers unpack."""
    f = family_of(config)
    return f.forward_cached, f.init_cache, f.decode


def lora_targets(config):
    """The LoRA-target leaves of a model family as
    ``((leaf_name, in_dim, out_dim), ...)`` — each names an entry of
    every block's ``["attn"]`` sub-tree, from the family's record. The
    ONE place the serving stack (serve/lora.py AdapterPool, the engine's
    mixed-tenant decode, the per-tenant online trainer) learns which
    projections an adapter applies to, so the pool layout, the decode
    gather, and the prefill merge can never disagree."""
    targets = family_of(config, "LoRA").lora_targets
    if targets is None:
        raise TypeError(f"no LoRA support for {type(config).__name__}")
    return targets(config)


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
    fwd = family_of(config).forward_cached
    logits, cache = fwd(params, prompt, config, cache, 0)
    return logits[:, -1], cache


def _decode_many(params, config, cache, first_token, start_pos, steps,
                 key, temperature, top_k):
    sample = _sample_fn(config.vocab_size, temperature, top_k)

    fwd = family_of(config).forward_cached

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


def generate(params: Any, config: Any, prompt: jax.Array, *,
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
    cache = family_of(config).init_cache(config, b)
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


def stream_generate(params: Any, config: Any, prompt: jax.Array,
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
    cache = family_of(config).init_cache(config, b)
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
    fwd = family_of(config).forward_cached
    logits, cache = fwd(params, tok[:, None], config, cache, pos)
    key, sub = jax.random.split(key)
    nxt = _sample_fn(config.vocab_size, temperature, top_k)(
        sub, logits[:, -1])
    return cache, nxt, key
