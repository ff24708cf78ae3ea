"""The SmallThinker family (SmallThinker-21BA3B / 4BA0.6B) in pure
functional JAX: every layer is grouped-query attention and an expert
layer, each behind an RMSNorm with a residual, and the layer's ROUTER
reads the layer's input, ahead of the attention: `r = x W_r; x <- x +
attn(norm1(x)); x <- x + experts(norm2(x); r)`; a final RMSNorm and an
untied head.

  attention  `num_heads` query heads of `head_dim` over `num_kv_heads`
             key-value heads (28 over 4 of 128: not the hidden size).
             Two kinds of layer, told apart by DATA on the config, not
             by a period wired in: `window_layout[i]` says whether layer
             i sees only the last `window` positions (query i sees keys
             `i - window < j <= i`), `rope_layout[i]` whether its
             queries and keys are rotated at their absolute positions
             (`ops/rope.py`, split-half). As published a global layer
             has no positions at all and a window layer both. A prompt
             runs the blocked prompt form (`ops/swa.prompt_attention`:
             the band's kernel), a decode tick `llama.py`'s per-group
             contraction over the cache as it lies.
  experts    `softmax_topk_route` (`ops/grouped_moe.py`) over the
             layer's INPUT, the float32 residual stream before any norm:
             the k largest logits, weighed by their softmax over the
             chosen. Every expert is held here (`held_experts` with
             `first=0`): ReGLU experts, gate and up packed in `w1` [E, D,
             2 I], `relu(gate) * up` into `w2` [E, I, D]. No shared
             expert. The router's product stands at the top of the layer,
             so the choice and the sort of the grouped product do not
             wait for the attention.

The residual stream is float32 (weights and every product's inputs are
`dtype`, bf16 as served; products accumulate in float32 and go back into
the stream unrounded), as `models/deepseek_v2.py` found a router over a
rounded stream needs. Over a prompt the expert layer runs in blocks of
`ffn_block` tokens (`lax.map`), as that family's does.

The cache (`init_cache`) shows the engine's FOUR kinds of slab entry
(`models/engine.py`, the cache protocol): keys and values with a sequence
axis `[B, S, G, d]` (a global layer here, every layer of `llama.py`); ONE
latent row a token (`kimi_linear.py`, `deepseek_v2.py`: none here); a
slot's state with no sequence axis (`nemotron_h.py`: none here); and the
RING: keys and values `[B, rows, G, d]` whose `rows` is SHORTER than
`max_seq_len` (a window layer: `rows = min(window, max_seq_len)`). The
token at position p lies in row `p mod rows`, its key stored already
rotated, so the order of the rows never matters to a softmax. The engine
learns the ring from the slab's shapes alone. A prefill hands a window
layer's entry back as the ring would lie after the prompt (the last
`min(T, rows)` positions, each at `p mod rows`); a tick scatters the new
key and value at `pos mod rows` and masks rows `>= min(pos + 1, rows)`.

`forward_cached` prefills a run of tokens FROM POSITION 0 or appends one
token at any position; it hands back the logits of the last position
only; `forward_counted` adds the counters of the run. `decode` runs one
step for every slot at its own position and reports what the expert
layers' grouped products saw.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_moe import held_counts, held_experts, softmax_topk_route
from ..ops.layers import mm, rms_norm
from ..ops.rope import apply_rope, rope_table
from ..ops.swa import (cache_attention, prompt_attention, ring_rows,
                       visited_blocks)
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    max_seq_len: int = 16384
    num_layers: int = 8
    d_model: int = 2560
    norm_eps: float = 1e-6
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    # one entry a layer: 1 where the layer sees `window` positions, 1
    # where its queries and keys are rotated
    window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 2
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 2
    window: int = 4096
    rope_theta: float = 1500000.0
    attn_block: int = 512            # of the prompt form
    num_experts: int = 64
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    ffn_block: int = 2048            # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        for name in ("window_layout", "rope_layout"):
            if len(getattr(self, name)) != self.num_layers:
                raise ValueError(f"{name} has not one entry a layer")

    def rows(self, layer: int) -> int:
        """The sequence rows layer `layer` keeps a slot."""
        if self.window_layout[layer]:
            return min(self.window, self.max_seq_len)
        return self.max_seq_len

    @staticmethod
    def tiny() -> "SmallThinkerConfig":  # tests / dry runs
        return SmallThinkerConfig(
            vocab_size=512, max_seq_len=128, num_layers=5, d_model=64,
            num_heads=6, num_kv_heads=2, head_dim=16,
            window_layout=(0, 1, 1, 1, 0), rope_layout=(0, 1, 1, 1, 0),
            window=8, attn_block=8, num_experts=8, num_experts_per_tok=3,
            moe_intermediate_size=32, ffn_block=16)


def _reglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> relu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.relu(gate) * up


# ------------------------------------------------------------------ init

def smallthinker_init(config: SmallThinkerConfig, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 2 + 7 * c.num_layers))

    def normal(*shape, scale=0.02, dtype=None):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(dtype or c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    # as models/kimi_linear.py found it had to be: a layer's way back
    # into the residual stream at 0.02 / sqrt(2 L) under an embedding of
    # unit size, so that a token's own embedding decides its experts
    back = 0.02 / math.sqrt(2 * c.num_layers)
    q_dim, kv_dim = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    inter = c.moe_intermediate_size
    params: Params = {"tok_emb": normal(c.vocab_size, c.d_model, scale=1.0),
                      "norm_f": ones(c.d_model),
                      "lm_head": normal(c.d_model, c.vocab_size),
                      "blocks": []}
    for _ in range(c.num_layers):
        params["blocks"].append({
            "norm1": ones(c.d_model), "norm2": ones(c.d_model),
            "attn": {"wq": normal(c.d_model, q_dim),
                     "wk": normal(c.d_model, kv_dim),
                     "wv": normal(c.d_model, kv_dim),
                     "wo": normal(q_dim, c.d_model, scale=back)},
            "moe": {"router": normal(c.d_model, c.num_experts, dtype=F32),
                    "w1": normal(c.num_experts, c.d_model, 2 * inter),
                    "w2": normal(c.num_experts, inter, c.d_model,
                                 scale=back)}})
    return params


# -------------------------------------------------------------- a layer

def _route(x: jax.Array, p: Params, c: SmallThinkerConfig):
    """The layer's router over its INPUT x [.., D] (the float32 stream,
    before the attention's norm) -> (experts [.., k], weights [.., k])."""
    with jax.named_scope("router"):
        chosen, weights = softmax_topk_route(
            x.reshape(-1, x.shape[-1]), p["moe"]["router"],
            c.num_experts_per_tok)
        lead = x.shape[:-1] + (c.num_experts_per_tok,)
        return chosen.reshape(lead), weights.reshape(lead)


def _qkv(x: jax.Array, p: Params, c: SmallThinkerConfig, layer: int, rope,
         positions: Optional[jax.Array]):
    """x [B, T, D] float32 at `positions` [B, T] (None: 0 .. T-1) -> q [B,
    T, H, d], k, v [B, T, G, d] in the weights' type, q and k rotated
    where the layer has rotary positions."""
    b, t, _ = x.shape
    h = rms_norm(x, p["norm1"]["scale"], c.norm_eps).astype(c.dtype)
    q = mm(h, p["attn"]["wq"]).reshape(b, t, c.num_heads, c.head_dim)
    k = mm(h, p["attn"]["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    v = mm(h, p["attn"]["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    if c.rope_layout[layer]:
        q = apply_rope(q, *rope, positions)
        k = apply_rope(k, *rope, positions)
    return q, k, v


def _attn_out(a: jax.Array, p: Params) -> jax.Array:
    """[B, T, H d] back into the residual stream, float32."""
    return jnp.dot(a, p["attn"]["wo"], preferred_element_type=F32)


def _scope(c: SmallThinkerConfig, layer: int) -> str:
    return "swa" if c.window_layout[layer] else "global_attn"


def _attn_prefill(x: jax.Array, p: Params, c: SmallThinkerConfig,
                  layer: int, rope, cache: Params | None):
    """A run of tokens from position 0: the prompt form over the run
    alone; its rows land in the cache, if there is one, as the entry
    would hold them after the run. Returns (the output, the cache, the
    blocks of scores one head's walk visited)."""
    with jax.named_scope(_scope(c, layer)):
        q, k, v = _qkv(x, p, c, layer, rope, None)
        window = c.window if c.window_layout[layer] else None
        a, blocks = prompt_attention(q, k, v, window, c.attn_block)
        if cache is not None:
            rows = cache["k"].shape[1]
            cache = {n: jax.lax.dynamic_update_slice(
                cache[n], ring_rows(new, rows).astype(cache[n].dtype),
                (0, 0, 0, 0)) for n, new in (("k", k), ("v", v))}
        return _attn_out(a.reshape(a.shape[:2] + (-1,)), p), cache, blocks


def _attn_decode(x: jax.Array, p: Params, c: SmallThinkerConfig,
                 layer: int, rope, cache: Params, positions: jax.Array):
    """One token a row at `positions` [B, 1]: its key and value are
    written at `pos mod rows` (a global entry's rows are the window: `pos`
    itself) and the per-group contraction reads the entry as it lies,
    rows `<= min(pos, rows - 1)`: all a ring holds once it has wrapped."""
    with jax.named_scope(_scope(c, layer)):
        q, k, v = _qkv(x, p, c, layer, rope, positions)
        rows = cache["k"].shape[1]
        at = (jnp.arange(x.shape[0])[:, None], positions % rows)
        ck = cache["k"].at[at].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[at].set(v.astype(cache["v"].dtype))
        a = cache_attention(q, ck, cv, jnp.minimum(positions, rows - 1))
        return _attn_out(a, p), {"k": ck, "v": cv}


def expert_layer(h: jax.Array, chosen: jax.Array, weights: jax.Array,
                 valid: jax.Array, p: Params, c: SmallThinkerConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """h [T, D] in the weights' type with its experts `chosen` [T, k] and
    their `weights`, valid [T] bool (a padded row routes nowhere) -> (the
    layer's output [T, D] float32, the rows each expert got [E] int32)."""
    chosen = jnp.where(valid[:, None], chosen, c.num_experts)
    out, counts = held_experts(h, chosen, weights, p["w1"], p["w2"], 0,
                               _reglu)
    return out, counts["sizes"]


def _ffn(x: jax.Array, chosen: jax.Array, weights: jax.Array, p: Params,
         c: SmallThinkerConfig):
    """x <- x + experts(norm2(x)) under the choice made at the top of
    the layer, in blocks of `ffn_block` tokens; the rows each expert got
    over all of them."""
    lead, d = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, d)
    n = flat.shape[0]
    block = min(c.ffn_block, n)
    pad = -n % block
    rows = lambda a: jnp.pad(a.reshape(n, -1), ((0, pad), (0, 0)))
    valid = jnp.arange(n + pad) < n

    def one(args):
        xb, eb, wb, ok = args
        with jax.named_scope("moe"):
            h = rms_norm(xb, p["norm2"]["scale"], c.norm_eps).astype(c.dtype)
            y, sizes = expert_layer(h, eb, wb, ok, p["moe"], c)
            return xb + y, sizes

    blocks = (rows(flat), rows(chosen), rows(weights), valid)
    if n + pad == block:
        out, sizes = one(blocks)
    else:
        out, sizes = jax.lax.map(one, tuple(
            a.reshape((-1, block) + a.shape[1:]) for a in blocks))
        out, sizes = out.reshape(-1, d), sizes.sum(0)
    return out[:n].reshape(lead + (d,)), sizes


def _head(x: jax.Array, params: Params, c: SmallThinkerConfig) -> jax.Array:
    with jax.named_scope("head"):
        h = rms_norm(x, params["norm_f"]["scale"], c.norm_eps)
        return jnp.dot(h.astype(c.dtype), params["lm_head"],
                       preferred_element_type=F32)


def _rope_table(c: SmallThinkerConfig):
    return rope_table(c.head_dim, c.max_seq_len, c.rope_theta)


# ------------------------------------------------------------- the model

def _prefill(params: Params, tokens: jax.Array, c: SmallThinkerConfig,
             cache: list | None):
    """tokens [B, T] from position 0 -> (the stream [B, T, D], the new
    cache, the run's counters)."""
    x = params["tok_emb"][tokens].astype(F32)
    new_cache = list(cache) if cache is not None else None
    sizes, blocks, rope = [], 0, _rope_table(c)
    for i, p in enumerate(params["blocks"]):
        chosen, weights = _route(x, p, c)
        y, entry, n = _attn_prefill(x, p, c, i, rope,
                                    cache[i] if cache is not None else None)
        if new_cache is not None:
            new_cache[i] = entry
        blocks += n
        x, rows = _ffn(x + y, chosen, weights, p, c)
        sizes.append(rows)
    causal = c.num_layers * visited_blocks(tokens.shape[1], c.attn_block,
                                           None)[1]
    return x, new_cache, dict(held_counts(sizes),
                              attn_blocks=jnp.int32(blocks),
                              attn_blocks_causal=jnp.int32(causal))


def smallthinker_forward(params: Params, tokens: jax.Array,
                         config: SmallThinkerConfig) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache."""
    x, _, _ = _prefill(params, tokens, config, None)
    return _head(x, params, config)


def smallthinker_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                      config: SmallThinkerConfig, remat: bool = False
                      ) -> jax.Array:
    fwd = smallthinker_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def smallthinker_init_cache(config: SmallThinkerConfig, batch_size: int,
                            max_len: int = 0, dtype: Any = None) -> list:
    """Keys and values [B, rows, G, d] a layer: `max_seq_len` rows for a
    global layer, `min(window, max_seq_len)` for a window layer, which is
    then a ring (module docstring)."""
    c = config
    if max_len:
        c = dataclasses.replace(c, max_seq_len=max_len)
    out = []
    for i in range(c.num_layers):
        shape = (batch_size, c.rows(i), c.num_kv_heads, c.head_dim)
        out.append({"k": jnp.zeros(shape, dtype or c.dtype),
                    "v": jnp.zeros(shape, dtype or c.dtype)})
    return out


def smallthinker_forward_counted(params: Params, tokens: jax.Array,
                                 config: SmallThinkerConfig, cache: list,
                                 pos: Any):
    """tokens [B, T] on top of what the cache holds. T > 1 is a prefill
    FROM POSITION 0 (`pos` must be a concrete 0: the prompt form reads
    the run alone, and a ring keeps no earlier rows to resume from); T ==
    1 appends one token at scalar position `pos`. Returns (logits [B, 1,
    vocab] float32 of the LAST position, the new cache, the counters of
    the run: the expert layers' as `decode` gives them, `attn_blocks`,
    the blocks of scores the prompt form visited over all layers, and
    `attn_blocks_causal`, what a causal walk with no window would have
    visited; both 0 for one token)."""
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
    logits, new_cache, counts = smallthinker_decode(
        params, tokens[:, 0], c, cache,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    zero = jnp.int32(0)
    return logits[:, None], new_cache, dict(
        counts, attn_blocks=zero, attn_blocks_causal=zero)


def smallthinker_forward_cached(params: Params, tokens: jax.Array,
                                config: SmallThinkerConfig, cache: list,
                                pos: Any):
    """`smallthinker_forward_counted` less its counters: the cache
    protocol's (logits, cache). The engine's prefill takes the counted
    form (`FAMILY.forward_counted`)."""
    return smallthinker_forward_counted(params, tokens, config, cache,
                                        pos)[:2]


def smallthinker_decode(params: Params, tokens: jax.Array,
                        config: SmallThinkerConfig, cache: list,
                        pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache, the
    expert layers' counts for the engine's loop record:
    `ops/grouped_moe.held_counts`). There is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("this family's decode has no verify form: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]].astype(F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    sizes, rope = [], _rope_table(c)
    for i, p in enumerate(params["blocks"]):
        chosen, weights = _route(x, p, c)
        y, new_cache[i] = _attn_decode(x, p, c, i, rope, cache[i],
                                       positions)
        x, rows = _ffn(x + y, chosen, weights, p, c)
        sizes.append(rows)
    return _head(x[:, 0], params, c), new_cache, held_counts(sizes)


def smallthinker_partition_specs(config: SmallThinkerConfig) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    block = {"norm1": norm, "norm2": norm,
             "attn": {"wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
                      "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp")},
             "moe": {"router": P(),
                     "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None)}}
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"),
            "blocks": [block for _ in range(config.num_layers)]}


FAMILY = Family(
    config_type=SmallThinkerConfig, init=smallthinker_init,
    forward=smallthinker_forward, loss=smallthinker_loss,
    partition_specs=smallthinker_partition_specs,
    init_cache=smallthinker_init_cache,
    forward_cached=smallthinker_forward_cached, decode=smallthinker_decode,
    forward_counted=smallthinker_forward_counted, decode_walks=True)
