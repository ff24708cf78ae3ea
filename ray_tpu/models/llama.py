"""Llama-family decoder in pure functional JAX — RMSNorm, RoPE, SwiGLU,
grouped-query attention — with a megatron-style PartitionSpec tree.

The reference tree carries no model code (its Train/RLlib wrap torch
models, SURVEY.md §2.4); this is native framework capability following the
same idioms as models/gpt2.py: pytree params + jit-able forward, bf16
params/activations with fp32 norm stats, flash attention (Pallas on TPU),
static shapes, one spec tree serving dp/fsdp/tp by changing only the mesh.

GQA + tp note: num_kv_heads must divide by the tp degree in use (as in
every tp Llama deployment). The training block repeats kv heads to query
heads for the flash kernel; the cache paths contract per kv group
(`cache_attention`), because a repeat of the whole KV slab IS an HBM
copy: XLA cannot fuse it into the reduction that reads it.

A run of tokens attends in one of THREE forms, chosen from what
`llama_forward_cached` and `llama_decode` are given, shapes and a
concrete position alone. A run of more than `_PROMPT_BLOCK` tokens from
a concrete position 0 (`_is_prompt`) attends over its OWN keys and
values through the prompt form (`ops/swa.prompt_attention`: no [H, T, S]
scores, nothing read of the slab's empty rows). A run of at most
`ops/swa.DECODE_ROWS` rows is a tick's (one token a slot, or the
speculative verify's k + 1) and takes the decode form
(`ops/swa.decode_attention`, through `cache_attention`): each slot's
rows up to its own position, block by block, and not the
`max_batch x max_seq_len` rows of the slab. Every other run (a suffix
on top of a cached prefix, a prompt of at most one block) attends over
the whole slab, masked (`slab_attention`). The cache comes back the
same whichever ran, so the forms differ in their reduction shapes
alone: a prompt replayed through a cached prefix meets another program,
and a near-tie of two logits may fall the other way.
`ops/dispatch.kernel_choices("gqa_prefill")` and `("gqa_decode")` list
the shapes that took the prompt and the decode form."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import flash_attention
from ..ops.layers import mm, rms_norm
from ..ops.rope import apply_rope, rope_table
from ..ops.swa import cache_attention, prompt_attention
from .family import Family

Params = Dict[str, Any]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 4
    d_model: int = 768
    d_ff: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    vocab_pad_multiple: int = 128

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @staticmethod
    def tiny() -> "LlamaConfig":  # tests / dry runs
        return LlamaConfig(vocab_size=512, max_seq_len=128, num_layers=2,
                           num_heads=4, num_kv_heads=2, d_model=128,
                           d_ff=256)

    @staticmethod
    def small() -> "LlamaConfig":  # ~125M-class
        return LlamaConfig()

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=32000, max_seq_len=4096,
                           num_layers=32, num_heads=32, num_kv_heads=32,
                           d_model=4096, d_ff=11008)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, max_seq_len=8192,
                           num_layers=32, num_heads=32, num_kv_heads=8,
                           d_model=4096, d_ff=14336, rope_theta=500000.0)


def llama_init(config: LlamaConfig, key: jax.Array) -> Params:
    c = config
    if c.num_heads % c.num_kv_heads:
        raise ValueError("num_heads must be a multiple of num_kv_heads")
    k_iter = iter(jax.random.split(key, 2 + 7 * c.num_layers))

    def norm(k, *shape, scale=0.02):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * scale).astype(c.dtype)

    kv_dim = c.num_kv_heads * c.head_dim
    params: Params = {
        "tok_emb": norm(next(k_iter), c.padded_vocab, c.d_model),
        "norm_f": {"scale": jnp.ones(c.d_model, c.dtype)},
        "lm_head": norm(next(k_iter), c.d_model, c.padded_vocab),
        "blocks": [],
    }
    for _ in range(c.num_layers):
        params["blocks"].append({
            "attn_norm": {"scale": jnp.ones(c.d_model, c.dtype)},
            "attn": {
                "wq": norm(next(k_iter), c.d_model, c.d_model),
                "wk": norm(next(k_iter), c.d_model, kv_dim),
                "wv": norm(next(k_iter), c.d_model, kv_dim),
                "wo": norm(next(k_iter), c.d_model, c.d_model),
            },
            "ffn_norm": {"scale": jnp.ones(c.d_model, c.dtype)},
            "mlp": {
                "w_gate": norm(next(k_iter), c.d_model, c.d_ff),
                "w_up": norm(next(k_iter), c.d_model, c.d_ff),
                "w_down": norm(next(k_iter), c.d_ff, c.d_model),
            },
        })
    return params


def _qkv(h: jax.Array, p: Params, c: LlamaConfig):
    b, t, _ = h.shape
    q = mm(h, p["attn"]["wq"]).reshape(b, t, c.num_heads, c.head_dim)
    k = mm(h, p["attn"]["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    v = mm(h, p["attn"]["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    return q, k, v


def _repeat_kv(k: jax.Array, v: jax.Array, c: LlamaConfig):
    # llama_block's alone (the flash kernel wants equal head counts):
    # on a KV slab this is an HBM copy, see cache_attention
    if c.num_kv_heads != c.num_heads:  # GQA: broadcast kv to query heads
        rep = c.num_heads // c.num_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


# the prompt form's block: the kernel's default and the one measured
# (PERF.md, PR 37 and PR 38). A run of at most one block has no blocks to
# skip, and the tiny models' prompts keep the slab form's numbers.
_PROMPT_BLOCK = 512


def _is_prompt(pos: Any, t: int) -> bool:
    """Whether `t` tokens at `pos` are a prompt the prompt form takes:
    longer than one block, from a position 0 that is known while tracing
    (a traced `pos`, as a decode scan carries, is not)."""
    if t <= _PROMPT_BLOCK:
        return False
    try:
        return int(pos) == 0
    except TypeError:       # a tracer
        return False


def _mlp_res(x: jax.Array, p: Params) -> jax.Array:
    h = rms_norm(x, p["ffn_norm"]["scale"])
    gate = jax.nn.silu(mm(h, p["mlp"]["w_gate"]).astype(jnp.float32))
    up = mm(h, p["mlp"]["w_up"]).astype(jnp.float32)
    return x + mm((gate * up).astype(x.dtype), p["mlp"]["w_down"])


def llama_block(x: jax.Array, p: Params, cos: jax.Array, sin: jax.Array,
                config: LlamaConfig) -> jax.Array:
    c = config
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"]["scale"])
    q, k, v = _qkv(h, p, c)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k, v = _repeat_kv(k, v, c)
    a = flash_attention(q, k, v, True).reshape(b, t, c.d_model)
    x = x + mm(a, p["attn"]["wo"])
    return _mlp_res(x, p)


def llama_block_cached(x: jax.Array, p: Params, cos: jax.Array,
                       sin: jax.Array, config: LlamaConfig,
                       cache: Params, pos: jax.Array):
    """KV-cache path (prefill AND decode — tokens land at position `pos`
    and attend over everything written so far). Static shapes: the
    cache is the full [B, S, n_kv, hd] window and masking does the
    truncation, the standard fixed-shape TPU decode layout. Heads are
    contracted per kv group against the cache as it lies
    (`cache_attention`); `_repeat_kv` remains for `llama_block` alone,
    whose flash kernel wants equal head counts. A prompt from position
    0 (`_is_prompt`) attends over its own rows alone, as the cache holds
    them: what `cache_attention` would see of the slab, without the
    rows past the run.
    Returns (x, new_cache_for_this_block)."""
    c = config
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"]["scale"])
    q, k, v = _qkv(h, p, c)
    positions = jnp.broadcast_to(pos + jnp.arange(t)[None, :], (b, t))
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    k = k.astype(cache["k"].dtype)
    v = v.astype(cache["v"].dtype)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    if _is_prompt(pos, t):
        a, _ = prompt_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                None, _PROMPT_BLOCK)
        a = a.reshape(b, t, c.num_heads * c.head_dim)
    else:
        # a suffix or a short prompt over the whole slab, a step of
        # `generate()` through the decode form: `cache_attention`
        a = cache_attention(q, ck, cv, positions)
    x = x + mm(a, p["attn"]["wo"])
    return _mlp_res(x, p), {"k": ck, "v": cv}


def llama_forward(params: Params, tokens: jax.Array,
                  config: LlamaConfig) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, padded_vocab] fp32."""
    c = config
    cos, sin = rope_table(c.head_dim, c.max_seq_len, c.rope_theta)
    x = params["tok_emb"][tokens]
    for p in params["blocks"]:
        x = llama_block(x, p, cos, sin, c)
    x = rms_norm(x, params["norm_f"]["scale"])
    return jnp.dot(x, params["lm_head"],
                   preferred_element_type=jnp.float32)


def llama_block_decode(x: jax.Array, p: Params, cos: jax.Array,
                       sin: jax.Array, config: LlamaConfig,
                       cache: Params, pos_vec: jax.Array,
                       lora: Optional[Dict[str, Any]] = None):
    """Ragged-batch decode with PER-SLOT positions (continuous
    batching: every batch slot is a different sequence at its own
    depth). x [B, t, D]; pos_vec [B] int32 is each slot's BASE
    position — slot b's token j lands at pos_vec[b] + j. t == 1 is the
    classic one-token tick; t == k+1 is the speculative VERIFY pass
    (models/engine.py), which scores a slot's k drafted tokens in one
    forward. Each new K/V row is scattered at its own position and
    attention is masked per (slot, query position), so query j sees
    exactly the rows a sequential j-step decode would — the
    bit-identity the speculation oracle rests on. Rows past a query's
    position stay invisible, which is also why rejected draft rows
    need no rollback: they are overwritten before any later query can
    see them. Heads are contracted per kv group (`cache_attention`),
    never against a repeated slab; `_repeat_kv` remains for
    `llama_block` alone, whose flash kernel wants equal head counts.

    `lora` (optional, serve/lora.py mixed-tenant decode): this layer's
    per-slot adapter selections — ``{"wq": (a [B,D,r], b [B,r,D]),
    "wv": (a, b), "scale": [B]}`` — added to the base projections as
    ``base @ x + scatter-gathered (B·A) @ x``. Slots on the null
    adapter (all-zero A/B, scale 0) add an exact-zero delta, keeping
    the base-only math bit-identical to the lora=None path."""
    c = config
    b, t = x.shape[0], x.shape[1]
    h = rms_norm(x, p["attn_norm"]["scale"])
    if lora is None:
        q, k, v = _qkv(h, p, c)
    else:
        from ..ops.layers import lora_delta

        q = mm(h, p["attn"]["wq"]) + lora_delta(
            h, *lora["wq"], lora["scale"])
        k = mm(h, p["attn"]["wk"])
        v = mm(h, p["attn"]["wv"]) + lora_delta(
            h, *lora["wv"], lora["scale"])
        q = q.reshape(b, t, c.num_heads, c.head_dim)
        k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
        v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
    positions = pos_vec[:, None] + jnp.arange(t)[None, :]   # [B, t]
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    rows = jnp.arange(b)
    ck = cache["k"].at[rows[:, None], positions].set(
        k.astype(cache["k"].dtype))
    cv = cache["v"].at[rows[:, None], positions].set(
        v.astype(cache["v"].dtype))
    a = cache_attention(q, ck, cv, positions)
    x = x + mm(a, p["attn"]["wo"])
    return _mlp_res(x, p), {"k": ck, "v": cv}


def llama_decode(params: Params, tokens: jax.Array, config: LlamaConfig,
                 cache: list, pos_vec: jax.Array,
                 lora: Optional[Dict[str, Any]] = None):
    """One decode step for a ragged batch: tokens [B] at per-slot
    positions pos_vec [B]. Returns (logits [B, padded_vocab] fp32,
    new_cache). tokens [B, q] is the speculative VERIFY form: slot b's
    q tokens land at positions pos_vec[b]..pos_vec[b]+q-1 and the
    logits come back [B, q, padded_vocab] — position j's row is what a
    sequential decode would have produced after feeding tokens[:, :j+1]
    (models/engine.py accepts the longest agreeing draft prefix off
    it).

    `lora` (optional): the adapter-pool stacks + per-slot indices —
    ``{"idx": [B] int32, "scale": [P] f32, "wq": (a [P,L,D,r],
    b [P,L,r,D]), "wv": (...)}`` (serve/lora.py layout). Each slot's
    adapter is gathered out of the pool once, then every layer adds its
    per-slot low-rank delta to the wq/wv projections."""
    c = config
    cos, sin = rope_table(c.head_dim, c.max_seq_len, c.rope_theta)
    ragged = tokens.ndim == 1
    x = params["tok_emb"][tokens[:, None] if ragged else tokens]
    sel = None
    if lora is not None:
        idx = lora["idx"]
        sel = {t: (lora[t][0][idx], lora[t][1][idx])
               for t in ("wq", "wv")}
        scale = lora["scale"][idx]
    new_cache = []
    for li, (p, blk_cache) in enumerate(zip(params["blocks"], cache)):
        lora_l = None if sel is None else {
            "wq": (sel["wq"][0][:, li], sel["wq"][1][:, li]),
            "wv": (sel["wv"][0][:, li], sel["wv"][1][:, li]),
            "scale": scale}
        x, nc = llama_block_decode(x, p, cos, sin, c, blk_cache,
                                   pos_vec, lora_l)
        new_cache.append(nc)
    x = rms_norm(x, params["norm_f"]["scale"])
    if ragged:
        x = x[:, 0]
    return jnp.dot(x, params["lm_head"],
                   preferred_element_type=jnp.float32), new_cache


def init_kv_cache(config: LlamaConfig, batch_size: int,
                  max_len: int = 0, dtype: Any = None) -> list:
    """Per-layer K/V buffers [B, S, n_kv_heads, head_dim]."""
    c = config
    s = max_len or c.max_seq_len
    dt = dtype or c.dtype
    return [{"k": jnp.zeros((batch_size, s, c.num_kv_heads, c.head_dim),
                            dt),
             "v": jnp.zeros((batch_size, s, c.num_kv_heads, c.head_dim),
                            dt)}
            for _ in range(c.num_layers)]


def llama_forward_cached(params: Params, tokens: jax.Array,
                         config: LlamaConfig, cache: list,
                         pos: jax.Array):
    """Append `tokens` [B, T] at position `pos` (scalar int32); returns
    (logits [B, T, padded_vocab] fp32, new_cache). pos=0 with the whole
    prompt is prefill (through the prompt form where `_is_prompt` says
    so: module docstring); T=1 afterwards is autoregressive decode."""
    c = config
    cos, sin = rope_table(c.head_dim, c.max_seq_len, c.rope_theta)
    x = params["tok_emb"][tokens]
    new_cache = []
    for p, blk_cache in zip(params["blocks"], cache):
        x, nc = llama_block_cached(x, p, cos, sin, c, blk_cache, pos)
        new_cache.append(nc)
    x = rms_norm(x, params["norm_f"]["scale"])
    return jnp.dot(x, params["lm_head"],
                   preferred_element_type=jnp.float32), new_cache


def llama_loss(params: Params, tokens: jax.Array, targets: jax.Array,
               config: LlamaConfig, remat: bool = False) -> jax.Array:
    fwd = llama_forward
    if remat:
        fwd = jax.checkpoint(llama_forward, static_argnums=(2,))
    logits = fwd(params, tokens, config)
    if config.padded_vocab != config.vocab_size:
        neg = jnp.full((config.padded_vocab - config.vocab_size,), -1e30,
                       dtype=logits.dtype)
        logits = logits.at[..., config.vocab_size:].set(neg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def llama_partition_specs(config: LlamaConfig) -> Params:
    """Megatron layout: q/k/v and gate/up column-parallel on tp, wo/down
    row-parallel, embeddings 2D-sharded. Collapses to replicated when the
    mesh has tp=fsdp=1."""
    block = {
        "attn_norm": {"scale": P()},
        "attn": {"wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
                 "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp")},
        "ffn_norm": {"scale": P()},
        "mlp": {"w_gate": P("fsdp", "tp"), "w_up": P("fsdp", "tp"),
                "w_down": P("tp", "fsdp")},
    }
    return {
        "tok_emb": P("tp", "fsdp"),
        "norm_f": {"scale": P()},
        "lm_head": P("fsdp", "tp"),
        "blocks": [block for _ in range(config.num_layers)],
    }


def llama_lora_targets(config: LlamaConfig):
    kv_dim = config.num_kv_heads * config.head_dim
    return (("wq", config.d_model, config.d_model),
            ("wv", config.d_model, kv_dim))


FAMILY = Family(
    config_type=LlamaConfig, init=llama_init, forward=llama_forward,
    loss=llama_loss, partition_specs=llama_partition_specs,
    init_cache=init_kv_cache, forward_cached=llama_forward_cached,
    decode=llama_decode, lora_targets=llama_lora_targets, decode_walks=True)
