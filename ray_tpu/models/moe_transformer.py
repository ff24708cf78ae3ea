"""Mixture-of-experts decoder (Mixtral-style): Llama blocks with the dense
SwiGLU MLP replaced by top-k routed experts (ops.moe.moe_ffn — GShard
dispatch/combine einsums, expert-parallel all_to_all under shard_map) plus
the Switch load-balancing auxiliary loss.

Partition layout: experts shard on the `ep` mesh axis (first dim of
w_in/w_out), with fsdp/tp inside each expert — the EP design the reference
cannot express natively (SURVEY.md §2.3 row 'Parallelism strategies')."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.layers import mm, rms_norm
from ..ops.moe import load_balancing_loss, moe_ffn
from ..ops.rope import rope_table
from .llama import LlamaConfig

Params = Dict[str, Any]


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 4
    d_model: int = 768
    d_ff: int = 2048
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
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

    def as_llama(self) -> LlamaConfig:
        """Attention-side view of this config (reuses llama_block)."""
        return LlamaConfig(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, d_model=self.d_model,
            d_ff=self.d_ff, rope_theta=self.rope_theta, dtype=self.dtype,
            vocab_pad_multiple=self.vocab_pad_multiple)

    @staticmethod
    def tiny() -> "MoEConfig":
        return MoEConfig(vocab_size=512, max_seq_len=128, num_layers=2,
                         num_heads=4, num_kv_heads=2, d_model=128,
                         d_ff=256, num_experts=4, top_k=2)

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        return MoEConfig(vocab_size=32000, max_seq_len=4096, num_layers=32,
                         num_heads=32, num_kv_heads=8, d_model=4096,
                         d_ff=14336, num_experts=8, top_k=2,
                         rope_theta=1e6)


def moe_init(config: MoEConfig, key: jax.Array) -> Params:
    c = config
    if c.num_heads % c.num_kv_heads:
        raise ValueError("num_heads must be a multiple of num_kv_heads")
    k_iter = iter(jax.random.split(key, 2 + 8 * c.num_layers))

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
            "moe": {
                "gate_w": norm(next(k_iter), c.d_model, c.num_experts),
                "w_in": norm(next(k_iter), c.num_experts, c.d_model,
                             c.d_ff),
                "w_out": norm(next(k_iter), c.num_experts, c.d_ff,
                              c.d_model),
            },
        })
    return params


def _moe_block(x: jax.Array, p: Params, cos, sin,
               config: MoEConfig) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, router_logits [T_total, E]) for the aux loss."""
    from ..ops.attention import flash_attention
    from ..ops.rope import apply_rope

    c = config
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"]["scale"])
    q = mm(h, p["attn"]["wq"]).reshape(b, t, c.num_heads, c.head_dim)
    k = mm(h, p["attn"]["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    v = mm(h, p["attn"]["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if c.num_kv_heads != c.num_heads:
        rep = c.num_heads // c.num_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    a = flash_attention(q, k, v, True).reshape(b, t, c.d_model)
    x = x + mm(a, p["attn"]["wo"])

    h = rms_norm(x, p["ffn_norm"]["scale"])
    y, logits = moe_ffn(
        h, p["moe"]["gate_w"], p["moe"]["w_in"], p["moe"]["w_out"],
        top_k=c.top_k, capacity_factor=c.capacity_factor,
        activation=jax.nn.silu, return_router_logits=True)
    return x + y, logits


def moe_forward(params: Params, tokens: jax.Array, config: MoEConfig,
                return_router_logits: bool = False):
    c = config
    cos, sin = rope_table(c.head_dim, c.max_seq_len, c.rope_theta)
    x = params["tok_emb"][tokens]
    all_logits = []
    for p in params["blocks"]:
        x, logits = _moe_block(x, p, cos, sin, c)
        all_logits.append(logits)
    x = rms_norm(x, params["norm_f"]["scale"])
    out = jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)
    if return_router_logits:
        return out, all_logits
    return out


def moe_loss(params: Params, tokens: jax.Array, targets: jax.Array,
             config: MoEConfig, remat: bool = False) -> jax.Array:
    """Cross-entropy + Switch load-balancing aux loss."""
    def body(params, tokens):
        return moe_forward(params, tokens, config,
                           return_router_logits=True)

    fwd = jax.checkpoint(body) if remat else body
    logits, router_logits = fwd(params, tokens)
    if config.padded_vocab != config.vocab_size:
        neg = jnp.full((config.padded_vocab - config.vocab_size,), -1e30,
                       dtype=logits.dtype)
        logits = logits.at[..., config.vocab_size:].set(neg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = -jnp.mean(ll)
    aux = sum(load_balancing_loss(lg, config.top_k)
              for lg in router_logits) / len(router_logits)
    return ce + config.aux_loss_coeff * aux


def moe_partition_specs(config: MoEConfig) -> Params:
    """Experts on `ep`, megatron tp/fsdp inside each expert."""
    block = {
        "attn_norm": {"scale": P()},
        "attn": {"wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
                 "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp")},
        "ffn_norm": {"scale": P()},
        "moe": {"gate_w": P(),
                "w_in": P("ep", "fsdp", "tp"),
                "w_out": P("ep", "tp", "fsdp")},
    }
    return {
        "tok_emb": P("tp", "fsdp"),
        "norm_f": {"scale": P()},
        "lm_head": P("fsdp", "tp"),
        "blocks": [block for _ in range(config.num_layers)],
    }
