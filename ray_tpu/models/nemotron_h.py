"""The Nemotron-H family (`model_type` nemotron_h; NVIDIA Nemotron-3) in
pure functional JAX: a stack whose layers differ in kind. Each layer is
ONE mixer behind a pre-norm with a residual, `x <- x + mixer(norm(x))`,
and `pattern` says which, a character a layer:

  M  a Mamba-2 mixer (`ops/mamba2.py`): in-projection to gate z, the
     convolved run xBC and the step dt; causal depthwise convolution and
     SiLU; the state-space recurrence per head; a grouped gated RMSNorm;
     out-projection. It owns state with NO sequence axis: the float32
     recurrence state [H, P, N] and the last K-1 inputs of the
     convolution.
  *  grouped-query attention with no positional embedding, over the
     cache as it lies (`ops/swa.cache_attention`, at whatever ratio of
     query to key-value heads the configuration has).
  E  a LatentMoE layer (`ops/grouped_moe.py`): a sigmoid router over ALL
     `n_routed_experts`, the chosen experts' scores normalised and
     scaled; the routed experts work in a latent space (down-projection,
     experts, up-projection) and THIS share of the deployment holds
     `experts_held` of them, from `first_expert` on, and computes their
     part of the sum and nothing for the others; one shared expert at
     full width is added on every share. No cache entry at all.

The cache (`init_cache`) is therefore not a list of key-value pairs a
layer: it is a list of entries ordered by kind, the attention layers'
{"k", "v"} [B, S, kv_heads, head_dim] first, then the Mamba layers'
{"ssm" [B, H, P, N] float32, "conv" [B, K-1, C]}. The engine
(`models/engine.py`) splices entries with "k"/"v" by rows and any other
entry whole. `forward_cached` continues from whatever the cache holds
(the chunked scan from a carried state), hands back the logits of the
LAST position only (`num_logits_to_keep` 1: a [T, vocab] float32 block
at the published vocabulary has no room beside the weights), and
`decode` runs one recurrence step for every slot at its own position (for
the slots that are `live`, where the engine's tick says which) and
reports what the expert layers' grouped products saw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_moe import held_experts, sigmoid_topk_route
from ..ops.layers import mm, rms_norm
from ..ops.mamba2 import causal_conv, gated_group_norm, ssd_scan, ssd_step
from ..ops.swa import cache_attention, slab_attention
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32
# a layer's ops carry its kind in their names, in HLO and in a trace
_SCOPE = {"M": "mamba2", "*": "attention", "E": "latent_moe"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    max_seq_len: int = 2048
    pattern: str = "MEMEMEMEM*E"
    d_model: int = 4096
    norm_eps: float = 1e-5
    # M
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 512      # the router's width
    experts_held: int = 128          # of them, on this share
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if set(self.pattern) - set("ME*") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: only M, E and *")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must divide by n_groups")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must divide by num_kv_heads")
        if not 0 <= self.first_expert \
                <= self.n_routed_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def tiny() -> "NemotronHConfig":  # tests / dry runs
        return NemotronHConfig(
            vocab_size=512, max_seq_len=128, pattern="MEM*E", d_model=64,
            mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, chunk_size=4, num_heads=4, num_kv_heads=2,
            head_dim=16, n_routed_experts=16, experts_held=4,
            num_experts_per_tok=3, moe_intermediate_size=32,
            moe_latent_size=32, moe_shared_expert_intermediate_size=64)


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


# ------------------------------------------------------------------ init

def nemotron_h_init(config: NemotronHConfig, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 3 + 8 * c.num_layers))

    def normal(*shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    h = c.mamba_num_heads
    attn_dim = c.num_heads * c.head_dim
    kv_dim = c.num_kv_heads * c.head_dim
    lat, inter = c.moe_latent_size, c.moe_intermediate_size
    shared = c.moe_shared_expert_intermediate_size
    params: Params = {"tok_emb": normal(c.vocab_size, c.d_model),
                      "norm_f": ones(c.d_model),
                      "lm_head": normal(c.d_model, c.vocab_size),
                      "blocks": []}
    for kind in c.pattern:
        block: Params = {"norm": ones(c.d_model)}
        if kind == "M":
            # dt log-uniform in [time_step_min, time_step_max], floored,
            # through the inverse of softplus; A uniform in [1, 16]: a
            # normal draw would make the recurrence forget at once or
            # never
            u = jax.random.uniform(next(keys), (h,), F32)
            dt = jnp.exp(u * (math.log(c.time_step_max)
                              - math.log(c.time_step_min))
                         + math.log(c.time_step_min))
            dt = jnp.maximum(dt, c.time_step_floor)
            a = jax.random.uniform(next(keys), (h,), F32, 1.0, 16.0)
            block["mamba"] = {
                "w_in": normal(c.d_model, c.d_inner + c.conv_dim + h),
                "conv_w": normal(c.conv_kernel, c.conv_dim,
                                 scale=1.0 / math.sqrt(c.conv_kernel)),
                "conv_b": jnp.zeros(c.conv_dim, c.dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(a),
                "D": jnp.ones(h, F32),
                "norm": jnp.ones(c.d_inner, c.dtype),
                "w_out": normal(c.d_inner, c.d_model),
            }
        elif kind == "*":
            block["attn"] = {"wq": normal(c.d_model, attn_dim),
                             "wk": normal(c.d_model, kv_dim),
                             "wv": normal(c.d_model, kv_dim),
                             "wo": normal(attn_dim, c.d_model)}
        else:
            block["moe"] = {
                "router": normal(c.d_model, c.n_routed_experts),
                # a small seeded spread, so that the bias chooses
                "router_bias": 0.02 * jax.random.normal(
                    next(keys), (c.n_routed_experts,), F32),
                "w_down": normal(c.d_model, lat),
                "w_up": normal(lat, c.d_model),
                "w1": normal(c.experts_held, lat, inter),
                "w2": normal(c.experts_held, inter, lat),
                "s1": normal(c.d_model, shared),
                "s2": normal(shared, c.d_model),
            }
        params["blocks"].append(block)
    return params


# ------------------------------------------------------------ the mixers

def _mamba_inputs(h: jax.Array, p: Params, c: NemotronHConfig):
    """h [B, T, D] -> (z [B,T,di], xbc [B,T,C] before the convolution,
    dt [B,T,H] float32 after softplus)."""
    proj = mm(h, p["w_in"])
    z, xbc, dt = jnp.split(proj, [c.d_inner, c.d_inner + c.conv_dim], -1)
    return z, xbc, jax.nn.softplus(dt.astype(F32) + p["dt_bias"])


def _mamba_split(xbc: jax.Array, c: NemotronHConfig):
    gn = c.n_groups * c.ssm_state_size
    x, bm, cm = jnp.split(xbc, [c.d_inner, c.d_inner + gn], -1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (c.mamba_num_heads, c.mamba_head_dim)),
            bm.reshape(lead + (c.n_groups, c.ssm_state_size)),
            cm.reshape(lead + (c.n_groups, c.ssm_state_size)))


def _mamba_out(y: jax.Array, z: jax.Array, p: Params,
               c: NemotronHConfig) -> jax.Array:
    y = y.reshape(z.shape).astype(z.dtype)
    return mm(gated_group_norm(y, z, p["norm"], c.n_groups, c.norm_eps),
               p["w_out"])


def _mamba(h: jax.Array, p: Params, c: NemotronHConfig, cache: Params,
           live: Optional[jax.Array] = None) -> Tuple[jax.Array, Params]:
    """h [B, T, D] on top of the state in `cache`: the chunked scan for a
    run of tokens, the recurrence itself for one token a row (of the rows
    that are `live`, where the caller knows which: `ops/mamba2.ssd_step`)."""
    z, xbc, dt = _mamba_inputs(h, p, c)
    xbc, tail = causal_conv(xbc, cache["conv"], p["conv_w"], p["conv_b"])
    a = -jnp.exp(p["A_log"])
    if h.shape[1] == 1:
        x, bm, cm = _mamba_split(xbc[:, 0], c)
        y, state = ssd_step(x, dt[:, 0], a, bm, cm, p["D"], cache["ssm"],
                            live)
    else:
        x, bm, cm = _mamba_split(xbc, c)
        y, state = ssd_scan(x, dt, a, bm, cm, p["D"], cache["ssm"],
                            c.chunk_size)
    return _mamba_out(y, z, p, c), {"ssm": state, "conv": tail}


def _qkv(h: jax.Array, p: Params, c: NemotronHConfig):
    b, t, _ = h.shape
    return (mm(h, p["wq"]).reshape(b, t, c.num_heads, c.head_dim),
            mm(h, p["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim),
            mm(h, p["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim))


def _attention(h: jax.Array, p: Params, c: NemotronHConfig, cache: Params,
               positions: jax.Array) -> Tuple[jax.Array, Params]:
    """New rows land at `positions` [B, t] of the cache, queries see the
    rows at or before their own. No rotary embedding in this family."""
    q, k, v = _qkv(h, p, c)
    rows = jnp.arange(h.shape[0])[:, None]
    ck = cache["k"].at[rows, positions].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[rows, positions].set(v.astype(cache["v"].dtype))
    a = cache_attention(q, ck, cv, positions)
    return mm(a, p["wo"]), {"k": ck, "v": cv}


def _attention_prefill(h: jax.Array, p: Params, c: NemotronHConfig,
                       cache: Params, pos: jax.Array
                       ) -> Tuple[jax.Array, Params]:
    """As `_attention` for a run of tokens from one scalar position on:
    the rows are written as one slice."""
    b, t, _ = h.shape
    q, k, v = _qkv(h, p, c)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
    positions = jnp.broadcast_to(pos + jnp.arange(t)[None, :], (b, t))
    a = cache_attention(q, ck, cv, positions)
    return mm(a, p["wo"]), {"k": ck, "v": cv}


def _attention_uncached(h: jax.Array, p: Params, c: NemotronHConfig
                        ) -> jax.Array:
    b, t, _ = h.shape
    q, k, v = _qkv(h, p, c)
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    # no cache and no tick: the run over itself, which a loss differentiates
    return mm(slab_attention(q, k, v, positions), p["wo"])


def latent_moe(h: jax.Array, p: Params, c: NemotronHConfig
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h [B, T, D] -> (the layer's output on this share, the grouped
    product's counts). The weights are normalised over all the chosen
    experts, held here or not."""
    lead = h.shape[:-1]
    flat = h.reshape(-1, h.shape[-1])
    chosen, weights = sigmoid_topk_route(
        flat, p["router"], p["router_bias"], c.num_experts_per_tok,
        c.routed_scaling_factor, c.norm_topk_prob)
    routed, counts = held_experts(mm(flat, p["w_down"]), chosen, weights,
                                  p["w1"], p["w2"], c.first_expert, _relu2)
    out = mm(routed.astype(h.dtype), p["w_up"])
    mid = _relu2(jnp.dot(flat, p["s1"], preferred_element_type=F32))
    out = out + mm(mid.astype(h.dtype), p["s2"])
    return out.reshape(lead + (out.shape[-1],)), counts


def _head(x: jax.Array, params: Params, c: NemotronHConfig) -> jax.Array:
    with jax.named_scope("head"):
        # the epsilon is the config's, never rms_norm's default
        x = rms_norm(x, params["norm_f"]["scale"], c.norm_eps)
        return jnp.dot(x, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------- the model

def nemotron_h_forward(params: Params, tokens: jax.Array,
                       config: NemotronHConfig) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache: every
    sequence from an empty state."""
    c = config
    b = tokens.shape[0]
    x = params["tok_emb"][tokens]
    for kind, p in zip(c.pattern, params["blocks"]):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm"]["scale"], c.norm_eps)
            if kind == "M":
                y, _ = _mamba(h, p["mamba"], c, _mamba_state(c, b))
            elif kind == "*":
                y = _attention_uncached(h, p["attn"], c)
            else:
                y, _ = latent_moe(h, p["moe"], c)
            x = x + y
    return _head(x, params, c)


def nemotron_h_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                    config: NemotronHConfig, remat: bool = False
                    ) -> jax.Array:
    fwd = nemotron_h_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _mamba_state(c: NemotronHConfig, batch: int) -> Params:
    return {"ssm": jnp.zeros((batch, c.mamba_num_heads, c.mamba_head_dim,
                              c.ssm_state_size), c.state_dtype),
            "conv": jnp.zeros((batch, c.conv_kernel - 1, c.conv_dim),
                              c.dtype)}


def nemotron_h_init_cache(config: NemotronHConfig, batch_size: int,
                          max_len: int = 0, dtype: Any = None) -> list:
    """The cache by kind: one {"k", "v"} [B, S, kv_heads, head_dim] per
    attention layer first, then one {"ssm", "conv"} per Mamba layer, each
    in the order of the layers. Expert layers keep nothing."""
    c = config
    s = max_len or c.max_seq_len
    dt = dtype or c.dtype
    kv = (batch_size, s, c.num_kv_heads, c.head_dim)
    return ([{"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
             for _ in range(c.pattern.count("*"))]
            + [_mamba_state(c, batch_size)
               for _ in range(c.pattern.count("M"))])


def _cache_slots(c: NemotronHConfig) -> List[int]:
    """Layer -> its entry in the cache list (-1: none)."""
    n_attn, seen = c.pattern.count("*"), {"*": 0, "M": 0}
    out = []
    for kind in c.pattern:
        if kind == "E":
            out.append(-1)
            continue
        out.append(seen[kind] + (n_attn if kind == "M" else 0))
        seen[kind] += 1
    return out


def nemotron_h_forward_cached(params: Params, tokens: jax.Array,
                              config: NemotronHConfig, cache: list,
                              pos: jax.Array):
    """Append tokens [B, T] at scalar position `pos` on top of what the
    cache holds. Returns (logits [B, 1, vocab] float32 of the LAST
    position, the new cache). T > 1 runs the chunked scan (a prefill),
    T == 1 the recurrence."""
    c = config
    x = params["tok_emb"][tokens]
    new_cache = list(cache)
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm"]["scale"], c.norm_eps)
            if kind == "M":
                y, new_cache[at] = _mamba(h, p["mamba"], c, cache[at])
            elif kind == "*":
                y, new_cache[at] = _attention_prefill(h, p["attn"], c,
                                                      cache[at], pos)
            else:
                y, _ = latent_moe(h, p["moe"], c)
            x = x + y
    return _head(x[:, -1:], params, c), new_cache


def nemotron_h_decode(params: Params, tokens: jax.Array,
                      config: NemotronHConfig, cache: list,
                      pos_vec: jax.Array,
                      live: Optional[jax.Array] = None):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]; `live` [B] (0: a slot nobody decodes for, whose state the
    Mamba layers then leave as it lies; None: every slot is stepped).
    Returns (logits [B, vocab] float32, the new cache, the
    expert layers' counts for the engine's loop record: token-expert
    pairs that fell on held experts, summed over the layers, and the most
    rows one held expert got). A state cannot be un-advanced, so there is
    no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("a recurrent state cannot verify drafted tokens: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]]
    positions = pos_vec[:, None]
    new_cache = list(cache)
    pairs = jnp.int32(0)
    rows_max = jnp.int32(0)
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        with jax.named_scope(_SCOPE[kind]):
            h = rms_norm(x, p["norm"]["scale"], c.norm_eps)
            if kind == "M":
                y, new_cache[at] = _mamba(h, p["mamba"], c, cache[at],
                                          live)
            elif kind == "*":
                y, new_cache[at] = _attention(h, p["attn"], c, cache[at],
                                              positions)
            else:
                y, counts = latent_moe(h, p["moe"], c)
                pairs = pairs + counts["pairs_held"]
                rows_max = jnp.maximum(rows_max, counts["rows_max"])
            x = x + y
    return (_head(x[:, 0], params, c), new_cache,
            {"moe_pairs_held": pairs, "moe_rows_max": rows_max})


def nemotron_h_partition_specs(config: NemotronHConfig) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    kinds = {
        "M": {"norm": norm, "mamba": {
            "w_in": P("fsdp", None), "conv_w": P(), "conv_b": P(),
            "dt_bias": P(), "A_log": P(), "D": P(), "norm": P(),
            "w_out": P(None, "fsdp")}},
        "*": {"norm": norm, "attn": {
            "wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
            "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp")}},
        "E": {"norm": norm, "moe": {
            "router": P(), "router_bias": P(),
            "w_down": P("fsdp", None), "w_up": P(None, "fsdp"),
            "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None),
            "s1": P("fsdp", "tp"), "s2": P("tp", "fsdp")}},
    }
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"),
            "blocks": [kinds[kind] for kind in config.pattern]}


FAMILY = Family(
    config_type=NemotronHConfig, init=nemotron_h_init,
    forward=nemotron_h_forward, loss=nemotron_h_loss,
    partition_specs=nemotron_h_partition_specs,
    init_cache=nemotron_h_init_cache, forward_cached=nemotron_h_forward_cached,
    decode=nemotron_h_decode, decode_walks=True, state_walks=True)
