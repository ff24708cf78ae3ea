"""The Granite hybrid family (`model_type` granitemoehybrid; IBM Granite
4.0-H) in pure functional JAX: a stack of layers of TWO residual
sublayers each, a mixer and a feed-forward, each behind an RMSNorm, with
four fixed multipliers in the stream, in the scores and in the logits:

    x      = embedding_multiplier * E[t]
    x      = x + residual_multiplier * mixer(norm1(x))
    x      = x + residual_multiplier * (moe(norm2(x)) + shared(norm2(x)))
    logits = norm_f(x) E^T / logits_scaling          (the head is tied)

`pattern` says which mixer a layer has, a character a layer:

  M  a Mamba-2 mixer (`ops/mamba2.py`): in-projection to the gate z, the
     convolved run xBC and the step dt; causal depthwise convolution and
     SiLU; the state-space recurrence per head, B and C in
     `mamba_n_groups` groups (ONE as published: all 128 heads read the
     same B and C); the gated RMSNorm over each group's channels;
     out-projection. It owns state with NO sequence axis: the float32
     recurrence state [H, P, N] and the last K-1 inputs of the
     convolution.
  *  grouped-query attention with NO positional embedding and a score
     scale that is `attention_multiplier`, not `head_dim ** -0.5`: the
     queries are multiplied by `attention_multiplier * head_dim ** 0.5`
     in float32 before they are rounded, and the ops scale by
     `head_dim ** -0.5` as they do for every family
     (`ops/swa.prompt_attention` over a run from position 0,
     `cache_attention` over the cache as it lies for a suffix and a
     tick).

EVERY layer's feed-forward is an expert layer AND a shared MLP
(`ops/grouped_moe.py`): the router's logits over ALL `num_experts`, the
`num_experts_per_tok` largest chosen and weighed by their softmax over
the chosen alone; gated SwiGLU experts at full hidden width (gate and up
packed in one `w1` [held, D, 2 I], the gate half first), of which THIS
share of the deployment holds `experts_held`, from `first_expert` on,
and computes their part of the sum and nothing for the others; one
shared SwiGLU at `shared_intermediate_size` on every share.

The residual stream is float32 (weights and every product's inputs are
`dtype`, bf16 as served; products accumulate in float32 and go back into
the stream unrounded).

The cache (`init_cache`) is a list of entries ordered by kind: the
attention layers' {"k", "v"} [B, S, kv_heads, head_dim] first, then the
Mamba layers' {"ssm" [B, H, P, N] float32, "conv" [B, K-1, C]}. The
engine (`models/engine.py`) splices entries with "k"/"v" by rows and any
other entry whole.

A prompt is walked in blocks of `prefill_token_block` tokens: a layer
walks the whole blocks as one loop (`lax.scan`) and the rest, if the
prompt is no whole number of blocks, as a last block of ITS OWN length,
with the recurrence state, the convolution's tail and the expert
layers' counts carried from block to block (a [T, 16,768] in-projection
and a [T x 10, 4,096] float32 expert buffer at 12,288 tokens have no
room beside the weights). Nothing is padded but the scan's ragged last
chunk, which `ops/mamba2.ssd_scan` pads with `dt = 0` AFTER the
softplus (it neither decays nor feeds the state), and the tail is taken
from the block's real inputs before that: the state handed to the first
tick is the one after the last REAL token. The attention layer takes the
whole run at once (its queries, keys and values are 0.15 GB at 12,288
tokens) and writes its rows as one slice. `forward_cached` continues
from whatever the cache holds and hands back the logits of the LAST
position only; `decode` runs one recurrence step for the slots that are
`live` and reports what the expert layers' grouped products saw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_moe import held_counts, held_experts, softmax_topk_route
from ..ops.layers import rms_norm
from ..ops.mamba2 import causal_conv, gated_group_norm, ssd_scan, ssd_step
from ..ops.swa import cache_attention, prompt_attention
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 12800
    pattern: str = "MMMMM*MMMM"
    d_model: int = 4096
    norm_eps: float = 1e-5
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    # M
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    time_step_min: float = 0.001     # the init's alone
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    # the feed-forward
    num_experts: int = 72            # the router's width
    experts_held: int = 36           # of them, on this share
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 10
    intermediate_size: int = 768     # ONE expert's
    shared_intermediate_size: int = 1536
    prefill_token_block: int = 2048  # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if set(self.pattern) - set("M*") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: only M and *")
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError("mamba_num_heads must divide by mamba_n_groups")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must divide by num_kv_heads")
        if not 0 <= self.first_expert \
                <= self.num_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def query_scale(self) -> float:
        """What the queries are multiplied by so that an op that scales by
        `head_dim ** -0.5` scores at `attention_multiplier`."""
        return self.attention_multiplier * math.sqrt(self.head_dim)

    @staticmethod
    def tiny() -> "GraniteHybridConfig":  # tests / dry runs
        return GraniteHybridConfig(
            vocab_size=512, max_seq_len=128, pattern="MM*M", d_model=64,
            attention_multiplier=1.0 / 16, mamba_num_heads=8,
            mamba_head_dim=16, mamba_d_state=16, mamba_chunk_size=4,
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
            experts_held=4, num_experts_per_tok=3, intermediate_size=32,
            shared_intermediate_size=64, prefill_token_block=8)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def granite_hybrid_init(config: GraniteHybridConfig, key: jax.Array
                        ) -> Params:
    """Mamba-2's own init for what is its own (dt log-uniform in
    [time_step_min, time_step_max] through the inverse softplus, A
    uniform in [1, 16], D ones, the convolution normal at 1/sqrt(K) with
    a seeded bias so that it counts); every other matrix normal(0, 0.02).
    The embedding is the HEAD too, read through both ends' multipliers:
    at 0.02 a token's stream would start at 12 x 0.02 against 0.4 a
    sublayer adds, so the token's own row would lead its logits by thirty
    times their spread (every token predicts itself), and that spread
    would be 64 x 0.02 / 16 = 0.08. So the embedding is normal(0, 0.02 /
    embedding_multiplier), the stream starts at 0.02 as the other
    families' does, and the final norm's scale is embedding_multiplier x
    logits_scaling, which gives the logits the spread of 64 x 0.02 = 1.3
    an untied 0.02 head would. The query and key projections are normal
    at 0.02 / sqrt(query_scale): under `attention_multiplier` = 1 /
    head_dim the scores of 0.02 projections would spread by 0.15 (all
    keys alike, the layer a running mean), so they get the spread they
    have at 0.02 under `head_dim ** -0.5`, 1.6. The multipliers
    themselves stay whole in the program: a wrong one moves every
    logit."""
    c = config
    keys = iter(jax.random.split(key, 2 + 12 * c.num_layers))

    def normal(*shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(c.dtype)

    def ones(n, value=1.0):
        return {"scale": jnp.full(n, value, c.dtype)}

    h, d = c.mamba_num_heads, c.d_model
    attn_dim, kv_dim = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    params: Params = {
        "tok_emb": normal(c.vocab_size, d,
                          scale=0.02 / c.embedding_multiplier),
        "norm_f": ones(d, c.embedding_multiplier * c.logits_scaling),
        "blocks": []}
    for kind in c.pattern:
        block: Params = {
            "norm1": ones(d), "norm2": ones(d),
            "router": normal(d, c.num_experts),
            "moe": {"w1": normal(c.experts_held, d,
                                 2 * c.intermediate_size),
                    "w2": normal(c.experts_held, c.intermediate_size, d)},
            "shared": {"w1": normal(d, 2 * c.shared_intermediate_size),
                       "w2": normal(c.shared_intermediate_size, d)}}
        if kind == "M":
            u = jax.random.uniform(next(keys), (h,), F32)
            dt = jnp.exp(u * (math.log(c.time_step_max)
                              - math.log(c.time_step_min))
                         + math.log(c.time_step_min))
            dt = jnp.maximum(dt, c.time_step_floor)
            a = jax.random.uniform(next(keys), (h,), F32, 1.0, 16.0)
            block["mamba"] = {
                "w_in": normal(d, c.d_inner + c.conv_dim + h),
                "conv_w": normal(c.mamba_d_conv, c.conv_dim,
                                 scale=1.0 / math.sqrt(c.mamba_d_conv)),
                "conv_b": normal(c.conv_dim, scale=0.1),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(a),
                "D": jnp.ones(h, F32),
                "norm": jnp.ones(c.d_inner, c.dtype),
                "w_out": normal(c.d_inner, d),
            }
        else:
            qk = 0.02 / math.sqrt(c.query_scale)
            block["attn"] = {"wq": normal(d, attn_dim, scale=qk),
                             "wk": normal(d, kv_dim, scale=qk),
                             "wv": normal(d, kv_dim),
                             "wo": normal(attn_dim, d)}
        params["blocks"].append(block)
    return params


# ------------------------------------------------------------ sublayers

def _norm(x: jax.Array, scale: jax.Array, c: GraniteHybridConfig
          ) -> jax.Array:
    """The float32 stream through an RMSNorm, in the weights' type."""
    return rms_norm(x, scale, c.norm_eps).astype(c.dtype)


def _dot(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=F32)


def _mamba(x: jax.Array, p: Params, c: GraniteHybridConfig, state: Params,
           live: Optional[jax.Array] = None) -> Tuple[jax.Array, Params]:
    """x <- x + residual_multiplier * mamba(norm1(x)) for x [B, T, D] on
    top of `state`: the chunked scan for a run of tokens, the recurrence
    itself for one token a row (of the rows that are `live`, where the
    caller knows which: `ops/mamba2.ssd_step`)."""
    m = p["mamba"]
    with jax.named_scope("mamba2"):
        h = _norm(x, p["norm1"]["scale"], c)
        z, xbc, dt = jnp.split(_dot(h, m["w_in"]),
                               [c.d_inner, c.d_inner + c.conv_dim], -1)
        dt = jax.nn.softplus(dt + m["dt_bias"])
        xbc, tail = causal_conv(xbc.astype(c.dtype), state["conv"],
                                m["conv_w"], m["conv_b"])
        gn = c.mamba_n_groups * c.mamba_d_state
        xs, bm, cm = jnp.split(xbc, [c.d_inner, c.d_inner + gn], -1)
        lead = xbc.shape[:-1]
        xs = xs.reshape(lead + (c.mamba_num_heads, c.mamba_head_dim))
        bm = bm.reshape(lead + (c.mamba_n_groups, c.mamba_d_state))
        cm = cm.reshape(lead + (c.mamba_n_groups, c.mamba_d_state))
        a = -jnp.exp(m["A_log"])
        if x.shape[1] == 1:
            y, ssm = ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                              m["D"], state["ssm"], live)
        else:
            # around the scan alone: whatever implements it is found by
            # this name
            with jax.named_scope("ssd_scan"):
                y, ssm = ssd_scan(xs, dt, a, bm, cm, m["D"], state["ssm"],
                                  c.mamba_chunk_size)
        # float32 through the gate and the norm, rounded once
        y = gated_group_norm(y.reshape(z.shape), z, m["norm"],
                             c.mamba_n_groups, c.norm_eps).astype(c.dtype)
        x = x + c.residual_multiplier * _dot(y, m["w_out"])
    return x, {"ssm": ssm, "conv": tail}


def _qkv(x: jax.Array, p: Params, c: GraniteHybridConfig):
    b, t, _ = x.shape
    h = _norm(x, p["norm1"]["scale"], c)
    a = p["attn"]
    # the score scale is the queries': folded in before they are rounded
    q = (_dot(h, a["wq"]) * c.query_scale).astype(c.dtype)
    return (q.reshape(b, t, c.num_heads, c.head_dim),
            _dot(h, a["wk"]).astype(c.dtype).reshape(
                b, t, c.num_kv_heads, c.head_dim),
            _dot(h, a["wv"]).astype(c.dtype).reshape(
                b, t, c.num_kv_heads, c.head_dim))


def _attn_out(x: jax.Array, a: jax.Array, p: Params,
              c: GraniteHybridConfig) -> jax.Array:
    return x + c.residual_multiplier * _dot(
        a.reshape(a.shape[:2] + (-1,)), p["attn"]["wo"])


def _from_zero(pos: Any) -> bool:
    """Whether `pos` is a 0 known while tracing: a run of tokens from it
    attends over itself alone."""
    try:
        return int(pos) == 0
    except TypeError:       # a tracer
        return False


def _attn_run(x: jax.Array, p: Params, c: GraniteHybridConfig,
              cache: Optional[Params], pos: Any
              ) -> Tuple[jax.Array, Optional[Params]]:
    """The attention mixer over a run of tokens x [B, T, D] at scalar
    position `pos`: from a concrete 0 the prompt form over the run
    alone, else (a suffix) over the cache as it lies. The rows land in
    the cache, if there is one."""
    with jax.named_scope("attention"):
        b, t, _ = x.shape
        q, k, v = _qkv(x, p, c)
        if cache is not None:
            cache = {n: jax.lax.dynamic_update_slice(
                cache[n], new.astype(cache[n].dtype), (0, pos, 0, 0))
                for n, new in (("k", k), ("v", v))}
        if cache is None or _from_zero(pos):
            a, _ = prompt_attention(q, k, v)
        else:
            positions = jnp.broadcast_to(pos + jnp.arange(t)[None, :],
                                         (b, t))
            a = cache_attention(q, cache["k"], cache["v"], positions)
        return _attn_out(x, a, p, c), cache


def _attn_tick(x: jax.Array, p: Params, c: GraniteHybridConfig,
               cache: Params, positions: jax.Array
               ) -> Tuple[jax.Array, Params]:
    """One token a row at `positions` [B, 1]."""
    with jax.named_scope("attention"):
        q, k, v = _qkv(x, p, c)
        at = (jnp.arange(x.shape[0])[:, None], positions)
        ck = cache["k"].at[at].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[at].set(v.astype(cache["v"].dtype))
        a = cache_attention(q, ck, cv, positions)
        return _attn_out(x, a, p, c), {"k": ck, "v": cv}


def _ffn(x: jax.Array, p: Params, c: GraniteHybridConfig
         ) -> Tuple[jax.Array, jax.Array]:
    """x <- x + residual_multiplier * (moe(norm2(x)) + shared(norm2(x)))
    for x [B, T, D] -> (x, the rows each held expert got [held])."""
    h = _norm(x, p["norm2"]["scale"], c)
    flat = h.reshape(-1, h.shape[-1])
    with jax.named_scope("moe"):
        chosen, weights = softmax_topk_route(flat, p["router"],
                                             c.num_experts_per_tok)
        routed, counts = held_experts(flat, chosen, weights, p["moe"]["w1"],
                                      p["moe"]["w2"], c.first_expert,
                                      _swiglu)
    with jax.named_scope("shared_mlp"):
        mid = _swiglu(_dot(flat, p["shared"]["w1"])).astype(c.dtype)
        out = routed + _dot(mid, p["shared"]["w2"])
    return (x + c.residual_multiplier * out.reshape(x.shape),
            counts["sizes"])


def _in_blocks(fn: Callable, carry: Any, x: jax.Array, block: int):
    """`fn(carry, x [B, n, D]) -> (carry, x)` over a prompt x [B, T, D] in
    blocks of `block` tokens: the whole blocks as one loop, the rest as a
    last block of its own length. Returns (carry, x)."""
    b, t, d = x.shape
    whole, rest = divmod(t, block)
    if whole + bool(rest) <= 1:
        return fn(carry, x)
    xb = jnp.moveaxis(x[:, :whole * block].reshape(b, whole, block, d), 1, 0)
    carry, yb = jax.lax.scan(fn, carry, xb)
    y = jnp.moveaxis(yb, 0, 1).reshape(b, whole * block, d)
    if rest:
        carry, last = fn(carry, x[:, whole * block:])
        y = jnp.concatenate([y, last], axis=1)
    return carry, y


def _head(x: jax.Array, params: Params, c: GraniteHybridConfig
          ) -> jax.Array:
    """The final norm, then the embedding as the head, over
    `logits_scaling`."""
    with jax.named_scope("head"):
        h = _norm(x, params["norm_f"]["scale"], c)
        return jax.lax.dot_general(
            h, params["tok_emb"], (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=F32) / c.logits_scaling


# ------------------------------------------------------------- the model

def _mamba_state(c: GraniteHybridConfig, batch: int) -> Params:
    return {"ssm": jnp.zeros((batch, c.mamba_num_heads, c.mamba_head_dim,
                              c.mamba_d_state), c.state_dtype),
            "conv": jnp.zeros((batch, c.mamba_d_conv - 1, c.conv_dim),
                              c.dtype)}


def granite_hybrid_init_cache(config: GraniteHybridConfig, batch_size: int,
                              max_len: int = 0, dtype: Any = None) -> list:
    """The cache by kind: one {"k", "v"} [B, S, kv_heads, head_dim] per
    attention layer first, then one {"ssm", "conv"} per Mamba layer, each
    in the order of the layers."""
    c = config
    kv = (batch_size, max_len or c.max_seq_len, c.num_kv_heads, c.head_dim)
    dt = dtype or c.dtype
    return ([{"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
             for _ in range(c.pattern.count("*"))]
            + [_mamba_state(c, batch_size)
               for _ in range(c.pattern.count("M"))])


def _cache_slots(c: GraniteHybridConfig) -> List[int]:
    """Layer -> its entry in the cache list."""
    seen = {"*": 0, "M": c.pattern.count("*")}
    out = []
    for kind in c.pattern:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def _walk(params: Params, tokens: jax.Array, c: GraniteHybridConfig,
          cache: Optional[list], pos: Any):
    """A run of tokens [B, T] at scalar position `pos` through every
    layer, on top of what the cache holds (None: an empty sequence and
    nothing kept) -> (the stream [B, T, D] float32, the new cache or
    None, the rows each held expert got, a [held] a layer)."""
    x = c.embedding_multiplier * params["tok_emb"][tokens].astype(F32)
    b, block = tokens.shape[0], c.prefill_token_block
    new_cache = list(cache) if cache is not None else None
    sizes = []
    none = jnp.zeros((c.experts_held,), jnp.int32)
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        if kind == "M":
            def layer(carry, xb, p=p):
                state, rows = carry
                xb, state = _mamba(xb, p, c, state)
                xb, got = _ffn(xb, p, c)
                return (state, rows + got), xb

            state = cache[at] if cache is not None else _mamba_state(c, b)
            (entry, rows), x = _in_blocks(layer, (state, none), x, block)
        else:
            def layer(rows, xb, p=p):
                xb, got = _ffn(xb, p, c)
                return rows + got, xb

            x, entry = _attn_run(x, p, c,
                                 cache[at] if cache is not None else None,
                                 pos)
            rows, x = _in_blocks(layer, none, x, block)
        if new_cache is not None:
            new_cache[at] = entry
        sizes.append(rows)
    return x, new_cache, sizes


def granite_hybrid_forward(params: Params, tokens: jax.Array,
                           config: GraniteHybridConfig) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache: every
    sequence from an empty state."""
    x, _, _ = _walk(params, tokens, config, None, 0)
    return _head(x, params, config)


def granite_hybrid_loss(params: Params, tokens: jax.Array,
                        targets: jax.Array, config: GraniteHybridConfig,
                        remat: bool = False) -> jax.Array:
    fwd = granite_hybrid_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def granite_hybrid_forward_counted(params: Params, tokens: jax.Array,
                                   config: GraniteHybridConfig, cache: list,
                                   pos: Any):
    """tokens [B, T] at scalar position `pos` on top of what the cache
    holds: the state continues from the cache's, the attention sees the
    cache's rows below `pos` (a prompt from a concrete 0 sees itself
    alone). T == 1 is one step of `decode`. Returns (logits [B, 1, vocab]
    float32 of the LAST position, the new cache, the expert layers'
    counters: `ops/grouped_moe.held_counts`)."""
    c = config
    b, t = tokens.shape
    if t == 1:
        logits, new_cache, counts = granite_hybrid_decode(
            params, tokens[:, 0], c, cache,
            jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
        return logits[:, None], new_cache, counts
    x, new_cache, sizes = _walk(params, tokens, c, cache, pos)
    return _head(x[:, -1:], params, c), new_cache, held_counts(sizes)


def granite_hybrid_forward_cached(params: Params, tokens: jax.Array,
                                  config: GraniteHybridConfig, cache: list,
                                  pos: Any):
    """`granite_hybrid_forward_counted` less its counters: the cache
    protocol's (logits, cache). The engine's prefill takes the counted
    form (`FAMILY.forward_counted`)."""
    return granite_hybrid_forward_counted(params, tokens, config, cache,
                                          pos)[:2]


def granite_hybrid_decode(params: Params, tokens: jax.Array,
                          config: GraniteHybridConfig, cache: list,
                          pos_vec: jax.Array,
                          live: Optional[jax.Array] = None):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]; `live` [B] (0: a slot nobody decodes for, whose state the
    Mamba layers then leave as it lies; None: every slot is stepped).
    Returns (logits [B, vocab] float32, the new cache, the expert layers'
    counts for the engine's loop record: `ops/grouped_moe.held_counts`).
    A state cannot be un-advanced, so there is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("a recurrent state cannot verify drafted tokens: "
                         "tokens must be [B]")
    x = c.embedding_multiplier * params["tok_emb"][tokens[:, None]].astype(
        F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    sizes = []
    for kind, p, at in zip(c.pattern, params["blocks"], _cache_slots(c)):
        if kind == "M":
            x, new_cache[at] = _mamba(x, p, c, cache[at], live)
        else:
            x, new_cache[at] = _attn_tick(x, p, c, cache[at], positions)
        x, got = _ffn(x, p, c)
        sizes.append(got)
    return _head(x[:, 0], params, c), new_cache, held_counts(sizes)


def granite_hybrid_partition_specs(config: GraniteHybridConfig) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    ffn = {"norm1": norm, "norm2": norm, "router": P(),
           "moe": {"w1": P("ep", None, "tp"), "w2": P("ep", "tp", None)},
           "shared": {"w1": P("fsdp", "tp"), "w2": P("tp", "fsdp")}}
    kinds = {
        "M": dict(ffn, mamba={
            "w_in": P("fsdp", None), "conv_w": P(), "conv_b": P(),
            "dt_bias": P(), "A_log": P(), "D": P(), "norm": P(),
            "w_out": P(None, "fsdp")}),
        "*": dict(ffn, attn={
            "wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
            "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp")}),
    }
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "blocks": [kinds[kind] for kind in config.pattern]}


FAMILY = Family(
    config_type=GraniteHybridConfig, init=granite_hybrid_init,
    forward=granite_hybrid_forward, loss=granite_hybrid_loss,
    partition_specs=granite_hybrid_partition_specs,
    init_cache=granite_hybrid_init_cache,
    forward_cached=granite_hybrid_forward_cached,
    decode=granite_hybrid_decode,
    forward_counted=granite_hybrid_forward_counted,
    decode_walks=True, state_walks=True)
