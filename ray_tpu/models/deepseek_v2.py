"""The DeepSeek-V2 family (`model_type` deepseek_v2; DeepSeek-V2 236B-A21B)
in pure functional JAX: every layer is latent attention and a feed-forward
part, each behind an RMSNorm with a residual, `x <- x + mla(norm(x)); x <-
x + ffn(norm(x))`; a final RMSNorm and an untied head.

  MLA  (`ops/mla.py`) a low-rank query, `q = W_qb RMSNorm(W_qa h)`, as
       heads of `[d_n | d_r]`; `[c | k_r] = W_kva h`, `c <- RMSNorm(c)`;
       keys' first part and values per head from c through `W_kvb`. The
       d_r numbers of every head's query and the ONE key part all heads
       share take rotary positions under YaRN (`ops/rope.yarn_table`, the
       split-half convention on the columns as the weights give them),
       and the softmax scale is `(d_n + d_r)^-1/2 yarn_mscale^2`. A token
       leaves ONE cache row, `[c | rope(k_r) | 0]`: the ROTATED key part.
       A prompt runs the blocked prompt form (`prompt_attention`), a
       decode tick the absorbed form over the cache.
  FFN  a dense SwiGLU in the first `first_dense` layers, else the expert
       layer (`ops/grouped_moe.py`): softmax scores over ALL
       `n_routed_experts`, which stand in `n_group` groups; the best
       `topk_group` groups by their largest score compete and the top k
       scores among them are chosen, weighing as they are (not
       renormalised) times `routed_scaling_factor`. THIS share of the
       deployment holds `experts_held` SwiGLU experts from `first_expert`
       on (gate and up packed in `w1` [held, D, 2 I]) and computes their
       part of the sum and nothing for the others; the `n_shared_experts`
       shared experts are one SwiGLU of their summed width, added on
       every share. The router's matrix is float32.

The residual stream is float32 (the weights and every product's inputs
are `dtype`, bf16 as served; products accumulate in float32 and go back
into the stream unrounded), and the router reads the normed stream as it
is: with weights of 16 x score that are not renormalised, one expert
chosen otherwise moves a token's logits by 0.2 to 0.4, and a stream
rounded to bf16 at every layer chose otherwise in a third of the
benchmark's checks (PERF.md section 6, PR 33).

Over a prompt the feed-forward part runs in blocks of `ffn_block` tokens
(`lax.map`): `held_experts` sizes its buffers for every token-expert pair,
held here or not, 2.1 GB for 8,192 tokens at the published widths.

The cache (`init_cache`) is one {"k": [B, S, row]} per layer and nothing
else: sequence entries of one array, no values, no state (the engine's
third kind of entry, alone: `models/engine.py` says what it refuses such
a family). `forward_cached` prefills a run of tokens FROM POSITION 0 or
appends one token at any position; it hands back the logits of the last
position only; `forward_counted` adds the counters of the run. `decode`
runs one step for every slot at its own position and reports what the
expert layers' grouped products saw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_moe import (held_counts, held_experts,
                               softmax_group_limited_route)
from ..ops.layers import mm, rms_norm
from ..ops.mla import (absorbed_attention, latent_row, prompt_attention,
                       row_width)
from ..ops.rope import apply_rope, yarn_mscale, yarn_table
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    max_seq_len: int = 8448
    num_layers: int = 5
    d_model: int = 5120
    norm_eps: float = 1e-6
    first_dense: int = 1             # leading layers with a dense ffn
    d_ff: int = 12288
    # latent attention
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    attn_block: int = 512            # of the prompt form
    # rotary positions under YaRN (factor 1: plain rotary positions)
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    # the expert layer
    n_routed_experts: int = 160      # the router's width
    experts_held: int = 40           # of them, on this share
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 2
    routed_scaling_factor: float = 16.0
    ffn_block: int = 2048            # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError("first_dense lies outside the layers")
        if not 0 <= self.first_expert \
                <= self.n_routed_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("the router's groups do not divide its width")

    @property
    def latent_row(self) -> int:
        return row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    @staticmethod
    def tiny() -> "DeepseekV2Config":  # tests / dry runs
        return DeepseekV2Config(
            vocab_size=512, max_seq_len=128, num_layers=3, d_model=64,
            d_ff=96, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            attn_block=8, rope_original_max=16, n_routed_experts=16,
            experts_held=4, num_experts_per_tok=3, n_group=4, topk_group=2,
            moe_intermediate_size=32, ffn_block=16)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def deepseek_v2_init(config: DeepseekV2Config, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 2 + 12 * c.num_layers))

    def normal(*shape, scale=0.02, dtype=None):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(dtype or c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    # as models/kimi_linear.py found it had to be: a layer's way back
    # into the residual stream at 0.02 / sqrt(2 L) under an embedding of
    # unit size, so that a token's own embedding decides its experts
    back = 0.02 / math.sqrt(2 * c.num_layers)
    q_dim = c.num_heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
    kvb = c.qk_nope_head_dim + c.v_head_dim
    inter = c.moe_intermediate_size
    shared = inter * c.n_shared_experts
    params: Params = {"tok_emb": normal(c.vocab_size, c.d_model, scale=1.0),
                      "norm_f": ones(c.d_model),
                      "lm_head": normal(c.d_model, c.vocab_size),
                      "blocks": []}
    for i in range(c.num_layers):
        block: Params = {
            "norm1": ones(c.d_model), "norm2": ones(c.d_model),
            "mla": {
                "w_qa": normal(c.d_model, c.q_lora_rank),
                "q_norm": jnp.ones(c.q_lora_rank, c.dtype),
                "w_qb": normal(c.q_lora_rank, q_dim),
                "w_kva": normal(c.d_model,
                                c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": jnp.ones(c.kv_lora_rank, c.dtype),
                "w_kvb": normal(c.kv_lora_rank, c.num_heads * kvb),
                "wo": normal(c.num_heads * c.v_head_dim, c.d_model,
                             scale=back)}}
        if i < c.first_dense:
            block["mlp"] = {"w1": normal(c.d_model, 2 * c.d_ff),
                            "w2": normal(c.d_ff, c.d_model, scale=back)}
        else:
            block["moe"] = {
                "router": normal(c.d_model, c.n_routed_experts, dtype=F32),
                "w1": normal(c.experts_held, c.d_model, 2 * inter),
                "w2": normal(c.experts_held, inter, c.d_model, scale=back),
                "s1": normal(c.d_model, 2 * shared),
                "s2": normal(shared, c.d_model, scale=back)}
        params["blocks"].append(block)
    return params


# ------------------------------------------------------ latent attention

def _rope_table(c: DeepseekV2Config):
    return yarn_table(
        c.qk_rope_head_dim, c.max_seq_len, c.rope_theta, c.rope_factor,
        c.rope_original_max, c.rope_beta_fast, c.rope_beta_slow,
        c.rope_mscale, c.rope_mscale_all_dim)


def _mla_inputs(h: jax.Array, p: Params, c: DeepseekV2Config, rope,
                positions: jax.Array | None):
    """h [B, T, D] at `positions` [B, T] (None: 0 .. T-1), `rope` the
    (cos, sin) of `_rope_table` -> (q_n
    [B,T,H,d_n], the rotated q_r [B,T,H,d_r], the normed latent
    [B,T,rank], the rotated shared key part [B,T,d_r], W_kvb as [rank, H,
    d_n + d_v])."""
    b, t, _ = h.shape
    q = mm(rms_norm(mm(h, p["w_qa"]), p["q_norm"], c.norm_eps),
            p["w_qb"]).reshape(
        b, t, c.num_heads, c.qk_nope_head_dim + c.qk_rope_head_dim)
    lat, k_r = jnp.split(mm(h, p["w_kva"]), [c.kv_lora_rank], -1)
    cos, sin = rope
    q_r = apply_rope(q[..., c.qk_nope_head_dim:], cos, sin, positions)
    k_r = apply_rope(k_r[:, :, None, :], cos, sin, positions)[:, :, 0]
    w_kvb = p["w_kvb"].reshape(c.kv_lora_rank, c.num_heads,
                               c.qk_nope_head_dim + c.v_head_dim)
    return (q[..., :c.qk_nope_head_dim], q_r,
            rms_norm(lat, p["kv_norm"], c.norm_eps), k_r, w_kvb)


def _mla_out(a: jax.Array, p: Params) -> jax.Array:
    """Back into the residual stream, float32."""
    return jnp.dot(a.reshape(a.shape[:2] + (-1,)), p["wo"],
                   preferred_element_type=F32)


def _normed(x: jax.Array, scale: jax.Array, c: DeepseekV2Config
            ) -> Tuple[jax.Array, jax.Array]:
    """RMSNorm of the float32 stream: as it is (the router reads it), and
    in the weights' type (every other product does)."""
    h = rms_norm(x, scale, c.norm_eps)
    return h, h.astype(c.dtype)


def _mla_prefill(h: jax.Array, p: Params, c: DeepseekV2Config, rope,
                 cache: Params | None):
    """A run of tokens from position 0: the prompt form over the run
    alone; its rows land in [0, T) of the cache, if there is one.
    Returns (the output, the cache, the blocks of scores computed)."""
    with jax.named_scope("mla_prefill"):
        q_n, q_r, lat, k_r, w_kvb = _mla_inputs(h, p, c, rope, None)
        a, blocks = prompt_attention(q_n, q_r, lat, k_r, w_kvb,
                                     c.softmax_scale, c.attn_block)
        if cache is not None:
            rows = latent_row(lat, k_r, c.latent_row, cache["k"].dtype)
            cache = {"k": jax.lax.dynamic_update_slice(
                cache["k"], rows, (0, 0, 0))}
        return _mla_out(a, p), cache, blocks


def _mla_decode(h: jax.Array, p: Params, c: DeepseekV2Config, rope,
                cache: Params, positions: jax.Array):
    """One token a row at `positions` [B, 1]: its row is written where it
    belongs and the absorbed form walks each slot's rows up to it."""
    with jax.named_scope("mla_absorbed"):
        q_n, q_r, lat, k_r, w_kvb = _mla_inputs(h, p, c, rope, positions)
        rows = latent_row(lat, k_r, c.latent_row, cache["k"].dtype)
        slab = cache["k"].at[
            jnp.arange(h.shape[0])[:, None], positions].set(rows)
        a = absorbed_attention(q_n, q_r, slab, positions, w_kvb,
                               c.softmax_scale)
        return _mla_out(a, p), {"k": slab}


# ------------------------------------------------- the feed-forward parts

def _shared_mlp(h: jax.Array, w1: jax.Array, w2: jax.Array) -> jax.Array:
    """A SwiGLU of h (in the weights' type), float32 out."""
    mid = _swiglu(jnp.dot(h, w1, preferred_element_type=F32))
    return jnp.dot(mid.astype(h.dtype), w2, preferred_element_type=F32)


def expert_layer(h32: jax.Array, valid: jax.Array, p: Params,
                 c: DeepseekV2Config) -> Tuple[jax.Array, jax.Array]:
    """h32 [T, D] float32, valid [T] bool (a padded row routes nowhere) ->
    (the layer's output on this share [T, D] float32, the rows each held
    expert got [held] int32). The router reads h32 itself; the experts
    read it in the weights' type. The weights are the chosen scores as
    they are, whether the expert is held here or not."""
    h = h32.astype(c.dtype)
    chosen, weights = softmax_group_limited_route(
        h32, p["router"], c.num_experts_per_tok, c.n_group, c.topk_group,
        c.routed_scaling_factor)
    chosen = jnp.where(valid[:, None], chosen, c.n_routed_experts)
    routed, counts = held_experts(h, chosen, weights, p["w1"], p["w2"],
                                  c.first_expert, _swiglu)
    return routed + _shared_mlp(h, p["s1"], p["s2"]), counts["sizes"]


def _ffn(x: jax.Array, p: Params, c: DeepseekV2Config):
    """x <- x + ffn(norm2(x)), in blocks of `ffn_block` tokens; the rows
    each held expert got over all of them, or None for a dense part."""
    lead, d = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, d)
    n = flat.shape[0]
    block = min(c.ffn_block, n)
    pad = -n % block
    valid = jnp.arange(n + pad) < n
    flat = jnp.pad(flat, ((0, pad), (0, 0)))

    def one(args):
        xb, ok = args
        h32, h = _normed(xb, p["norm2"]["scale"], c)
        if "mlp" in p:
            with jax.named_scope("dense_mlp"):
                return xb + _shared_mlp(h, p["mlp"]["w1"],
                                        p["mlp"]["w2"]), None
        with jax.named_scope("moe"):
            y, sizes = expert_layer(h32, ok, p["moe"], c)
            return xb + y, sizes

    if n + pad == block:
        out, sizes = one((flat, valid))
    else:
        out, sizes = jax.lax.map(
            one, (flat.reshape(-1, block, d), valid.reshape(-1, block)))
        out = out.reshape(-1, d)
        sizes = None if sizes is None else sizes.sum(0)
    return out[:n].reshape(lead + (d,)), sizes


def _head(x: jax.Array, params: Params, c: DeepseekV2Config) -> jax.Array:
    with jax.named_scope("head"):
        _, h = _normed(x, params["norm_f"]["scale"], c)
        return jnp.dot(h, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------- the model

def _prefill(params: Params, tokens: jax.Array, c: DeepseekV2Config,
             cache: list | None):
    """tokens [B, T] from position 0 -> (the stream [B, T, D], the new
    cache, the run's counters)."""
    x = params["tok_emb"][tokens].astype(F32)
    new_cache = list(cache) if cache is not None else None
    sizes, blocks, rope = [], 0, _rope_table(c)
    for i, p in enumerate(params["blocks"]):
        _, h = _normed(x, p["norm1"]["scale"], c)
        y, entry, n = _mla_prefill(h, p["mla"], c, rope,
                                   cache[i] if cache is not None else None)
        if new_cache is not None:
            new_cache[i] = entry
        blocks += n
        x, rows = _ffn(x + y, p, c)
        sizes += [] if rows is None else [rows]
    return x, new_cache, dict(held_counts(sizes),
                              attn_blocks=jnp.int32(blocks))


def deepseek_v2_forward(params: Params, tokens: jax.Array,
                        config: DeepseekV2Config) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache."""
    x, _, _ = _prefill(params, tokens, config, None)
    return _head(x, params, config)


def deepseek_v2_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                     config: DeepseekV2Config, remat: bool = False
                     ) -> jax.Array:
    fwd = deepseek_v2_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def deepseek_v2_init_cache(config: DeepseekV2Config, batch_size: int,
                           max_len: int = 0, dtype: Any = None) -> list:
    """One {"k": [B, S, row]} a layer: keys and values are both made from
    the one latent row, and no layer owns anything else."""
    c = config
    rows = (batch_size, max_len or c.max_seq_len, c.latent_row)
    return [{"k": jnp.zeros(rows, dtype or c.dtype)}
            for _ in range(c.num_layers)]


def deepseek_v2_forward_counted(params: Params, tokens: jax.Array,
                                config: DeepseekV2Config, cache: list,
                                pos: Any):
    """tokens [B, T] on top of what the cache holds. T > 1 is a prefill
    FROM POSITION 0 (`pos` must be a concrete 0: the prompt form reads
    the run alone, never the cache's earlier rows); T == 1 appends one
    token at scalar position `pos`. Returns (logits [B, 1, vocab] float32
    of the LAST position, the new cache, the counters of the run: the
    expert layers' as `decode` gives them, and `attn_blocks`, the blocks
    of scores the prompt form computed, 0 for one token)."""
    c = config
    b, t = tokens.shape
    if t > 1:
        try:
            start = int(pos)
        except TypeError:
            start = -1
        if start != 0:
            raise ValueError(
                "a run of tokens is a prefill from position 0: the prompt "
                "form attends over the run alone (pos must be a concrete "
                "0)")
        x, new_cache, counts = _prefill(params, tokens, c, cache)
        return _head(x[:, -1:], params, c), new_cache, counts
    logits, new_cache, counts = deepseek_v2_decode(
        params, tokens[:, 0], c, cache,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    return logits[:, None], new_cache, dict(counts,
                                            attn_blocks=jnp.int32(0))


def deepseek_v2_forward_cached(params: Params, tokens: jax.Array,
                               config: DeepseekV2Config, cache: list,
                               pos: Any):
    """`deepseek_v2_forward_counted` less its counters: the cache
    protocol's (logits, cache). The engine's prefill takes the counted
    form (`FAMILY.forward_counted`) and puts what it hands back into the
    admission's record."""
    return deepseek_v2_forward_counted(params, tokens, config, cache,
                                       pos)[:2]


def deepseek_v2_decode(params: Params, tokens: jax.Array,
                       config: DeepseekV2Config, cache: list,
                       pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache, the
    expert layers' counts for the engine's loop record: `held_counts`).
    There is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("this family's decode has no verify form: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]].astype(F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    sizes, rope = [], _rope_table(c)
    for i, p in enumerate(params["blocks"]):
        _, h = _normed(x, p["norm1"]["scale"], c)
        y, new_cache[i] = _mla_decode(h, p["mla"], c, rope, cache[i],
                                      positions)
        x, rows = _ffn(x + y, p, c)
        sizes += [] if rows is None else [rows]
    return _head(x[:, 0], params, c), new_cache, held_counts(sizes)


def deepseek_v2_partition_specs(config: DeepseekV2Config) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    mla = {"mla": {
        "w_qa": P("fsdp", None), "q_norm": P(), "w_qb": P(None, "tp"),
        "w_kva": P("fsdp", None), "kv_norm": P(), "w_kvb": P(None, "tp"),
        "wo": P("tp", "fsdp")}}
    dense = {"mlp": {"w1": P("fsdp", "tp"), "w2": P("tp", "fsdp")}}
    sparse = {"moe": {
        "router": P(),
        "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None),
        "s1": P("fsdp", "tp"), "s2": P("tp", "fsdp")}}
    blocks = [{"norm1": norm, "norm2": norm, **mla,
               **(dense if i < config.first_dense else sparse)}
              for i in range(config.num_layers)]
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"), "blocks": blocks}


FAMILY = Family(
    config_type=DeepseekV2Config, init=deepseek_v2_init,
    forward=deepseek_v2_forward, loss=deepseek_v2_loss,
    partition_specs=deepseek_v2_partition_specs,
    init_cache=deepseek_v2_init_cache,
    forward_cached=deepseek_v2_forward_cached, decode=deepseek_v2_decode,
    forward_counted=deepseek_v2_forward_counted, decode_walks=True)
