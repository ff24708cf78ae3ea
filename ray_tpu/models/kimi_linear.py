"""The Kimi-Linear family (`model_type` kimi_linear; Moonshot AI's
Kimi-Linear-48B-A3B) in pure functional JAX: every layer is a mixer and a
feed-forward part, each behind an RMSNorm with a residual, `x <- x +
mixer(norm(x)); x <- x + ffn(norm(x))`. `pattern` says which mixer, a
character a layer:

  K  Kimi Delta Attention (`ops/kda.py`): q, k, v through a causal
     depthwise convolution of `conv_kernel` and SiLU, q and k
     L2-normalised per head; a log-decay per CHANNEL `g = -exp(A_log)
     softplus(low-rank(h) + dt_bias)`, `beta = sigmoid(h W_b)`; the gated
     delta rule on a float32 state [H, d_k, d_v]; a per-head RMSNorm of
     the output under a low-rank sigmoid gate; out-projection. It owns
     state with NO sequence axis: the recurrence state and the last K-1
     inputs of the convolution.
  A  multi-head latent attention with no positional embedding
     (`ops/mla.py`): ONE cache row a token, `[c | k_r]` padded to whole
     lane tiles, expanded over a prompt and absorbed over the cache.

The feed-forward part of layer i (1-based) is a dense SwiGLU for `i <=
first_dense` and else the expert layer (`ops/grouped_moe.py`): a sigmoid
router over ALL `n_routed_experts`, the chosen experts' scores normalised
and scaled; THIS share of the deployment holds `experts_held` SwiGLU
experts at full hidden width, from `first_expert` on (gate and up packed
in `w1` [held, D, 2 I]), and computes their part of the sum and nothing
for the others; the shared expert is added on every share.

The cache (`init_cache`) is a list of entries ordered by kind: the latent
layers' {"k": [B, S, row]} FIRST (a sequence entry of one array: the
engine's third kind, `models/engine.py`), then the KDA layers' {"state"
[B, H, d_k, d_v] float32, "conv" [B, K-1, 3 H d]}. `forward_cached`
prefills a run of tokens FROM POSITION 0 (the chunked scan from the
carried state, the expanded attention over the run alone) or appends one
token at any position (the recurrence, the absorbed attention over the
cache); it hands back the logits of the last position only. `decode` runs
one step for every slot at its own position, the KDA layers' state step
for the slots the caller's `live` vector holds live alone
(`Family.state_walks`), and reports what the expert layers' grouped
products saw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_moe import held_experts, sigmoid_topk_route
from ..ops.kda import kda_scan, kda_step, l2_normalize
from ..ops.layers import mm, rms_norm
from ..ops.mamba2 import causal_conv
from ..ops.mla import (absorbed_attention, expanded_attention, latent_row,
                       row_width)
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32
# a layer's ops carry their kind in their names, in HLO and in a trace
_SCOPE = {"K": "kda", "A": "mla"}


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    max_seq_len: int = 2048
    pattern: str = "KKKAKKKA"
    d_model: int = 2304
    norm_eps: float = 1e-5
    first_dense: int = 1             # leading layers with a dense ffn
    d_ff: int = 9216
    # K
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    kda_low_rank: int = 128          # of the decay's and the gate's pairs
    conv_kernel: int = 4
    chunk_size: int = 64
    time_step_min: float = 0.001     # init only, as Mamba-2's
    time_step_max: float = 0.1
    # A
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64       # the shared key part; no rope here
    v_head_dim: int = 128
    # the expert layer
    n_routed_experts: int = 256      # the router's width
    experts_held: int = 64           # of them, on this share
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if set(self.pattern) - set("KA") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: only K and A")
        if not 0 <= self.first_dense <= len(self.pattern):
            raise ValueError("first_dense lies outside the layers")
        if not 0 <= self.first_expert \
                <= self.n_routed_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def kda_dim(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def latent_row(self) -> int:
        return row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @staticmethod
    def tiny() -> "KimiLinearConfig":  # tests / dry runs
        return KimiLinearConfig(
            vocab_size=512, max_seq_len=128, pattern="KKAK", d_model=64,
            d_ff=96, kda_num_heads=4, kda_head_dim=16, kda_low_rank=16,
            chunk_size=8, num_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, experts_held=4, num_experts_per_tok=3,
            moe_intermediate_size=32)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def kimi_linear_init(config: KimiLinearConfig, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 3 + 16 * c.num_layers))

    def normal(*shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    # a layer's way back into the residual stream, as GPT-2 scales it
    # (0.02 / sqrt(2 L)), under an embedding of unit size: a token's own
    # embedding then decides its experts. At 0.02 throughout, the stream
    # is the running average of the context after a few tokens, every
    # slot's the same, and all slots choose the same few experts
    back = 0.02 / math.sqrt(2 * c.num_layers)
    h, dk, lo = c.kda_num_heads, c.kda_head_dim, c.kda_low_rank
    q_dim = c.num_heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
    kvb = c.qk_nope_head_dim + c.v_head_dim
    inter = c.moe_intermediate_size
    shared = inter * c.num_shared_experts
    params: Params = {"tok_emb": normal(c.vocab_size, c.d_model, scale=1.0),
                      "norm_f": ones(c.d_model),
                      "lm_head": normal(c.d_model, c.vocab_size),
                      "blocks": []}
    for i, kind in enumerate(c.pattern):
        block: Params = {"norm1": ones(c.d_model), "norm2": ones(c.d_model)}
        if kind == "K":
            # the decay as Mamba-2 draws it: a rate A uniform in [1, 16] a
            # head, a step log-uniform in [time_step_min, time_step_max] a
            # channel through the inverse of softplus; a normal draw would
            # make the state forget at once or never
            u = jax.random.uniform(next(keys), (c.kda_dim,), F32)
            dt = jnp.exp(u * (math.log(c.time_step_max)
                              - math.log(c.time_step_min))
                         + math.log(c.time_step_min))
            a = jax.random.uniform(next(keys), (h,), F32, 1.0, 16.0)
            block["kda"] = {
                # [q | k | v | decay low | gate low | beta]
                "w_in": normal(c.d_model, 3 * c.kda_dim + 2 * lo + h),
                "conv_w": normal(c.conv_kernel, 3 * c.kda_dim,
                                 scale=1.0 / math.sqrt(c.conv_kernel)),
                "w_decay": normal(lo, c.kda_dim),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(a),
                "w_gate": normal(lo, c.kda_dim),
                "norm": jnp.ones(dk, c.dtype),
                "w_out": normal(c.kda_dim, c.d_model, scale=back),
            }
        else:
            block["mla"] = {
                "wq": normal(c.d_model, q_dim),
                "w_kva": normal(c.d_model,
                                c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": jnp.ones(c.kv_lora_rank, c.dtype),
                "w_kvb": normal(c.kv_lora_rank, c.num_heads * kvb),
                "wo": normal(c.num_heads * c.v_head_dim, c.d_model,
                             scale=back),
            }
        if i < c.first_dense:
            block["mlp"] = {"w1": normal(c.d_model, 2 * c.d_ff),
                            "w2": normal(c.d_ff, c.d_model, scale=back)}
        else:
            block["moe"] = {
                "router": normal(c.d_model, c.n_routed_experts),
                # a small seeded spread, so that the bias chooses
                "router_bias": 0.02 * jax.random.normal(
                    next(keys), (c.n_routed_experts,), F32),
                "w1": normal(c.experts_held, c.d_model, 2 * inter),
                "w2": normal(c.experts_held, inter, c.d_model, scale=back),
                "s1": normal(c.d_model, 2 * shared),
                "s2": normal(shared, c.d_model, scale=back),
            }
        params["blocks"].append(block)
    return params


# ------------------------------------------------------------ the mixers

def _kda(h: jax.Array, p: Params, c: KimiLinearConfig, cache: Params,
         live: Optional[jax.Array] = None) -> Tuple[jax.Array, Params]:
    """h [B, T, D] on top of the state in `cache`: the chunked form for a
    run of tokens, the recurrence itself for one token a row, which with
    `live` [B] visits the live rows' state alone (`ops/kda.kda_step`)."""
    b, t, _ = h.shape
    nh, dk, lo = c.kda_num_heads, c.kda_head_dim, c.kda_low_rank
    qkv, d_lo, g_lo, beta = jnp.split(
        mm(h, p["w_in"]),
        [3 * c.kda_dim, 3 * c.kda_dim + lo, 3 * c.kda_dim + 2 * lo], -1)
    qkv, tail = causal_conv(qkv, cache["conv"], p["conv_w"],
                            jnp.zeros((), c.dtype))
    q, k, v = (x.reshape(b, t, nh, dk) for x in jnp.split(qkv, 3, -1))
    q, k = l2_normalize(q) * dk ** -0.5, l2_normalize(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        mm(d_lo, p["w_decay"]).astype(F32)
        + p["dt_bias"]).reshape(b, t, nh, dk)
    beta = jax.nn.sigmoid(beta.astype(F32))
    if t == 1:
        o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            cache["state"], live)
        o = o[:, None]
    else:
        o, state = kda_scan(q, k, v, g, beta, cache["state"], c.chunk_size)
    gate = jax.nn.sigmoid(mm(g_lo, p["w_gate"]).astype(F32))
    o = rms_norm(o, p["norm"], c.norm_eps).reshape(b, t, c.kda_dim) * gate
    return (mm(o.astype(h.dtype), p["w_out"]),
            {"state": state, "conv": tail})


def _mla_inputs(h: jax.Array, p: Params, c: KimiLinearConfig):
    """h [B, T, D] -> (q_n [B,T,H,d_n], q_r [B,T,H,d_r], the normed
    latent c [B,T,rank], the shared key part k_r [B,T,d_r], W_kvb as
    [rank, H, d_n + d_v])."""
    b, t, _ = h.shape
    q = mm(h, p["wq"]).reshape(
        b, t, c.num_heads, c.qk_nope_head_dim + c.qk_rope_head_dim)
    lat, k_r = jnp.split(mm(h, p["w_kva"]), [c.kv_lora_rank], -1)
    w_kvb = p["w_kvb"].reshape(c.kv_lora_rank, c.num_heads,
                               c.qk_nope_head_dim + c.v_head_dim)
    return (q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:],
            rms_norm(lat, p["kv_norm"], c.norm_eps), k_r, w_kvb)


def _mla_out(a: jax.Array, p: Params) -> jax.Array:
    return mm(a.reshape(a.shape[:2] + (-1,)), p["wo"])


def _mla_prefill(h: jax.Array, p: Params, c: KimiLinearConfig,
                 cache: Params | None) -> Tuple[jax.Array, Params | None]:
    """A run of tokens from position 0: the expanded form over the run
    alone; its rows land in [0, T) of the cache, if there is one."""
    q_n, q_r, lat, k_r, w_kvb = _mla_inputs(h, p, c)
    a = expanded_attention(q_n, q_r, lat, k_r, w_kvb)
    if cache is not None:
        rows = latent_row(lat, k_r, c.latent_row, cache["k"].dtype)
        cache = {"k": jax.lax.dynamic_update_slice(cache["k"], rows,
                                                   (0, 0, 0))}
    return _mla_out(a, p), cache


def _mla_decode(h: jax.Array, p: Params, c: KimiLinearConfig,
                cache: Params, positions: jax.Array
                ) -> Tuple[jax.Array, Params]:
    """One token a row at `positions` [B, 1]: its row is written where it
    belongs and the absorbed form walks each slot's rows up to it."""
    q_n, q_r, lat, k_r, w_kvb = _mla_inputs(h, p, c)
    rows = latent_row(lat, k_r, c.latent_row, cache["k"].dtype)
    slab = cache["k"].at[jnp.arange(h.shape[0])[:, None], positions].set(
        rows)
    a = absorbed_attention(q_n, q_r, slab, positions, w_kvb)
    return _mla_out(a, p), {"k": slab}


# ------------------------------------------------- the feed-forward parts

def _dense_mlp(h: jax.Array, p: Params) -> jax.Array:
    mid = _swiglu(jnp.dot(h, p["w1"], preferred_element_type=F32))
    return mm(mid.astype(h.dtype), p["w2"])


def expert_layer(h: jax.Array, p: Params, c: KimiLinearConfig
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h [B, T, D] -> (the layer's output on this share, the grouped
    product's counts with `experts_hit`, the held experts that got a
    row). The weights are normalised over all the chosen experts, held
    here or not."""
    lead = h.shape[:-1]
    flat = h.reshape(-1, h.shape[-1])
    chosen, weights = sigmoid_topk_route(
        flat, p["router"], p["router_bias"], c.num_experts_per_tok,
        c.routed_scaling_factor, c.norm_topk_prob)
    routed, counts = held_experts(flat, chosen, weights, p["w1"], p["w2"],
                                  c.first_expert, _swiglu)
    mid = _swiglu(jnp.dot(flat, p["s1"], preferred_element_type=F32))
    out = routed.astype(h.dtype) + mm(mid.astype(h.dtype), p["s2"])
    local = chosen.reshape(-1) - c.first_expert
    here = (local >= 0) & (local < c.experts_held)
    hit = jnp.zeros((c.experts_held + 1,), bool).at[
        jnp.where(here, local, c.experts_held)].set(True)[:c.experts_held]
    return (out.reshape(lead + (out.shape[-1],)),
            dict(counts, experts_hit=hit.sum().astype(jnp.int32)))


def _ffn(x: jax.Array, p: Params, c: KimiLinearConfig):
    """x <- x + ffn(norm2(x)); the expert layer's counts, or None."""
    h = rms_norm(x, p["norm2"]["scale"], c.norm_eps)
    if "mlp" in p:
        with jax.named_scope("dense_mlp"):
            return x + _dense_mlp(h, p["mlp"]), None
    with jax.named_scope("moe"):
        y, counts = expert_layer(h, p["moe"], c)
        return x + y, counts


def _head(x: jax.Array, params: Params, c: KimiLinearConfig) -> jax.Array:
    with jax.named_scope("head"):
        x = rms_norm(x, params["norm_f"]["scale"], c.norm_eps)
        return jnp.dot(x, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------- the model

def _kda_state(c: KimiLinearConfig, batch: int) -> Params:
    return {"state": jnp.zeros((batch, c.kda_num_heads, c.kda_head_dim,
                                c.kda_head_dim), c.state_dtype),
            "conv": jnp.zeros((batch, c.conv_kernel - 1, 3 * c.kda_dim),
                              c.dtype)}


def kimi_linear_forward(params: Params, tokens: jax.Array,
                        config: KimiLinearConfig) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache: every
    sequence from an empty state."""
    c = config
    x = params["tok_emb"][tokens]
    for kind, p in zip(c.pattern, params["blocks"]):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm1"]["scale"], c.norm_eps)
            if kind == "K":
                y, _ = _kda(h, p["kda"], c, _kda_state(c, tokens.shape[0]))
            else:
                y, _ = _mla_prefill(h, p["mla"], c, None)
            x = x + y
        x, _ = _ffn(x, p, c)
    return _head(x, params, c)


def kimi_linear_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                     config: KimiLinearConfig, remat: bool = False
                     ) -> jax.Array:
    fwd = kimi_linear_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def kimi_linear_init_cache(config: KimiLinearConfig, batch_size: int,
                           max_len: int = 0, dtype: Any = None) -> list:
    """The cache by kind: one {"k": [B, S, row]} per latent layer first
    (ONE array a layer: keys and values are both made from it), then one
    {"state", "conv"} per KDA layer, each in the order of the layers."""
    c = config
    s = max_len or c.max_seq_len
    rows = (batch_size, s, c.latent_row)
    return ([{"k": jnp.zeros(rows, dtype or c.dtype)}
             for _ in range(c.pattern.count("A"))]
            + [_kda_state(c, batch_size)
               for _ in range(c.pattern.count("K"))])


def _cache_slots(c: KimiLinearConfig) -> List[int]:
    """Layer -> its entry in the cache list."""
    n_mla, seen = c.pattern.count("A"), {"A": 0, "K": 0}
    out = []
    for kind in c.pattern:
        out.append(seen[kind] + (n_mla if kind == "K" else 0))
        seen[kind] += 1
    return out


def kimi_linear_forward_cached(params: Params, tokens: jax.Array,
                               config: KimiLinearConfig, cache: list,
                               pos: Any):
    """tokens [B, T] on top of what the cache holds. T > 1 is a prefill
    FROM POSITION 0 (`pos` must be a concrete 0: the expanded attention
    reads the run alone, never the cache's earlier rows); T == 1 appends
    one token at scalar position `pos`. Returns (logits [B, 1, vocab]
    float32 of the LAST position, the new cache)."""
    c = config
    b, t = tokens.shape
    if t > 1:
        try:
            start = int(pos)
        except TypeError:
            start = -1
        if start != 0:
            raise ValueError(
                "a run of tokens is a prefill from position 0: the latent "
                "layers attend over the run alone (pos must be a concrete "
                "0)")
    else:
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b, 1))
    x = params["tok_emb"][tokens]
    new_cache = list(cache)
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm1"]["scale"], c.norm_eps)
            if kind == "K":
                y, new_cache[at] = _kda(h, p["kda"], c, cache[at])
            elif t > 1:
                y, new_cache[at] = _mla_prefill(h, p["mla"], c, cache[at])
            else:
                y, new_cache[at] = _mla_decode(h, p["mla"], c, cache[at],
                                               positions)
            x = x + y
        x, _ = _ffn(x, p, c)
    return _head(x[:, -1:], params, c), new_cache


def kimi_linear_decode(params: Params, tokens: jax.Array,
                       config: KimiLinearConfig, cache: list,
                       pos_vec: jax.Array,
                       live: Optional[jax.Array] = None):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]; `live` [B] (the tick's own liveness vector, 0 for a dead
    slot) lets the KDA layers' state step visit the live slots alone: a
    dead slot's state is then neither read nor written, and its logits
    are nobody's. Returns (logits [B, vocab] float32, the new cache, the
    expert layers' counts for the engine's loop record, summed over the
    layers: `moe_pairs_held`, token-expert pairs that fell on held
    experts; `moe_experts_hit`, held experts that got a row; and
    `moe_rows_max`, the most rows one held expert got). A state cannot be
    un-advanced, so there is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("a recurrent state cannot verify drafted tokens: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]]
    positions = pos_vec[:, None]
    new_cache = list(cache)
    pairs, hit, rows_max = jnp.int32(0), jnp.int32(0), jnp.int32(0)
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm1"]["scale"], c.norm_eps)
            if kind == "K":
                y, new_cache[at] = _kda(h, p["kda"], c, cache[at], live)
            else:
                y, new_cache[at] = _mla_decode(h, p["mla"], c, cache[at],
                                               positions)
            x = x + y
        x, counts = _ffn(x, p, c)
        if counts is not None:
            pairs = pairs + counts["pairs_held"]
            hit = hit + counts["experts_hit"]
            rows_max = jnp.maximum(rows_max, counts["rows_max"])
    return (_head(x[:, 0], params, c), new_cache,
            {"moe_pairs_held": pairs, "moe_rows_max": rows_max,
             "moe_experts_hit": hit})


def kimi_linear_partition_specs(config: KimiLinearConfig) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    mixers = {
        "K": {"kda": {
            "w_in": P("fsdp", None), "conv_w": P(), "w_decay": P(),
            "dt_bias": P(), "A_log": P(), "w_gate": P(), "norm": P(),
            "w_out": P(None, "fsdp")}},
        "A": {"mla": {
            "wq": P("fsdp", "tp"), "w_kva": P("fsdp", None),
            "kv_norm": P(), "w_kvb": P(None, "tp"),
            "wo": P("tp", "fsdp")}},
    }
    dense = {"mlp": {"w1": P("fsdp", "tp"), "w2": P("tp", "fsdp")}}
    sparse = {"moe": {
        "router": P(), "router_bias": P(),
        "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None),
        "s1": P("fsdp", "tp"), "s2": P("tp", "fsdp")}}
    blocks = [{"norm1": norm, "norm2": norm, **mixers[kind],
               **(dense if i < config.first_dense else sparse)}
              for i, kind in enumerate(config.pattern)]
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"), "blocks": blocks}


FAMILY = Family(
    config_type=KimiLinearConfig, init=kimi_linear_init,
    forward=kimi_linear_forward, loss=kimi_linear_loss,
    partition_specs=kimi_linear_partition_specs,
    init_cache=kimi_linear_init_cache,
    forward_cached=kimi_linear_forward_cached, decode=kimi_linear_decode,
    decode_walks=True, state_walks=True)
