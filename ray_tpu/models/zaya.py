"""The ZAYA1 family (`model_type` zaya; Zyphra ZAYA1-8B) in pure
functional JAX: every layer is an attention sublayer and an expert
sublayer, each behind an RMSNorm, and each sublayer's sum is
RESIDUAL-SCALED: `h <- (s * h + s0) + (o * out + o0)`, four learned
vectors a sublayer; a final RMSNorm, and the head is the embedding
(tied).

  attention  compressed convolutional attention (`ops/cca.py`,
             arXiv:2510.04476): queries and keys made in a latent (`H d`
             and `G d` wide), mixed by two causal convolutions over the
             sequence, corrected by a query-key mean, L2-normalised under
             a learned temperature, rotated over `rotary_dim` channels of
             a head, and attended `H` over `G` heads; a head's values are
             half this token's and half the LAST token's. The cache
             holds the keys and values in pairs, `[B, S, G, d]` a layer,
             and BESIDE them a state with no sequence axis: the tails of
             the two convolutions and the last token's half of the
             values, 5.4 KB a slot and layer as published.
  experts    `mlp_top1_route` (`ops/grouped_moe.py`, arXiv:2511.17127):
             `r_l = x W_d + gamma_l r_{l-1}`, the router's own state
             carried from layer to layer beside the residual stream (0
             ahead of the first layer), an RMSNorm and a three-matrix
             GELU MLP to `num_experts + 1` scores, softmax, ONE choice by
             `argmax(p + b)`. The last choice is no expert: the token
             skips the sublayer's experts (`out = 0`, exactly; the
             residual scaling still applies). Every expert is held here
             (`held_experts` with `first=0`, which sorts the skip's pairs
             last and drops them as it drops pairs of experts held
             elsewhere): SwiGLU experts, gate and up packed in `w1` [E,
             D, 2 I], `p_e * (silu(gate) * up)` into `w2` [E, I, D].

The residual stream, the router (its weights too) and every sum are
float32; the other weights, the cache and the state are `dtype` (bf16 as
served), products accumulate in float32.

The cache (`init_cache`): first one {"k", "v"} [B, S, G, d] a layer, then
one {"conv0", "conv1", "v2"} a layer (`ops/cca.cca_state`), which the
engine splices whole (`models/family.py`: kind "state"). `forward_cached`
continues from whatever the cache holds (a run from a concrete position 0
attends over itself alone, a later block over the cache) and hands back
the logits of the LAST position only: the head has 262,272 rows as
published. `forward_counted` and `decode` add what the expert layers saw:
`ops/grouped_moe.held_counts` and `moe_pairs_skipped`, the token-layer
pairs routed to no expert.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.cca import cca_prompt, cca_state, cca_tick
from ..ops.grouped_moe import held_counts, held_experts, mlp_top1_route
from ..ops.layers import rms_norm
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32
# the share of an expert's matrices that is its own at the seeded init,
# the rest being one matrix the layer's experts share (`zaya_init`)
EXPERT_OWN = 0.1
# the balancing bias of the choice that is no expert, beside the +- 0.005
# of every choice: a token in a hundred skips, not one in seventeen
SKIP_BIAS = -0.03


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    max_seq_len: int = 2816
    num_layers: int = 16
    d_model: int = 2048
    norm_eps: float = 1e-5
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    rotary_dim: int = 64             # head_dim x partial_rotary_factor
    rope_theta: float = 5000000.0
    cca_time0: int = 2
    cca_time1: int = 2
    num_experts: int = 16
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    ffn_block: int = 2048            # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("rotary_dim is an even share of head_dim")
        if min(self.cca_time0, self.cca_time1) < 2:
            raise ValueError("a convolution of one tap carries no tail")

    @property
    def geometry(self) -> Dict[str, Any]:
        """What `ops/cca.py` asks of a config."""
        return {"heads": self.num_heads, "kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim, "rotary": self.rotary_dim,
                "theta": self.rope_theta}

    @staticmethod
    def tiny() -> "ZayaConfig":  # tests / dry runs
        return ZayaConfig(
            vocab_size=512, max_seq_len=128, num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=8,
            num_experts=4, moe_intermediate_size=32, router_hidden_size=16,
            ffn_block=16)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def zaya_init(config: ZayaConfig, key: jax.Array) -> Params:
    """Every matrix normal(0, fan_in^-1/2) (0.022 from the hidden size:
    the 0.02 of the other families), the embedding normal(0, 0.02): it is
    the head, and at 0.02 the logits have a spread of about 1. What a
    trained model has learned away from a neutral value is DRAWN away
    from it, so that a program that leaves a term out disagrees with the
    reference: the residual scales 1 +- 0.1 and their biases +- 0.02,
    `gamma` in [0.3, 0.7], the convolutions' biases +- 0.1.

    Five choices stand in for what training does, which no seed can.
    Two make the sixteen experts about equally likely, as a balanced
    router's are (the fullest expert 2 to 3 times the mean over a
    prompt; whole layers on ONE expert without them). The attention's
    way back at half and the experts' at three times the fan-in's
    scale: the stream is made of what is a token's own and not of the
    mean of every token's values, which would decide every token's
    expert alike. And the router's second and third matrices with every
    column centred at norm 1 (the third at 2), under a balancing bias of
    +- 0.005 (a seventeenth is 0.06, and two best choices lie some 0.03
    apart): GELU's mean is then no constant offset of an expert's score.
    Three keep the function one that bf16 can be HELD to. ONE choice
    among random experts is settled the other way by bf16's rounding for
    one token in fifty a layer; a token so turned meets another expert's
    whole output and is turned again in the layers after; with sixteen
    unrelated experts the bf16 program and the float32 reference shared
    nothing by the sixteenth layer (a MEAN gap of 2.45 on the chip,
    which no limit tells 8 bits from; PERF.md section 6, PR 56). So an
    expert is `sqrt(1 - a^2)` of ONE matrix the layer's experts share and
    `a = EXPERT_OWN` of its own, experts as an upcycled model has them,
    alike in most and different in part: a choice settled the other way
    moves a token by a seventh of an expert's output, a WRONG expert
    still by thirty times bf16's rounding, and every expert's own 25 MB
    are streamed all the same. The choice of NO expert, which no such
    likeness softens, is made rare by its bias (`SKIP_BIAS`: a token in
    a hundred, not one in seventeen). And `tau` in [1.1, 1.5]: scores are
    cosines times sqrt(128) tau, and at 3 to 5 the softmax turned a
    rounding of a key into a fifth of a weight."""
    c = config
    keys = iter(jax.random.split(key, 1 + 32 * c.num_layers))

    def normal(*shape, scale, dtype=None):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(dtype or c.dtype)

    def matrix(*shape, fan_in, dtype=None):
        return normal(*shape, scale=fan_in ** -0.5, dtype=dtype)

    def uniform(*shape, lo, hi, dtype=None):
        return jax.random.uniform(next(keys), shape, F32, lo, hi).astype(
            dtype or c.dtype)

    def centred(*shape, norm):
        w = jax.random.normal(next(keys), shape, F32)
        w = w - w.mean(0, keepdims=True)
        return w * (norm * jax.lax.rsqrt((w * w).sum(0, keepdims=True)))

    def experts(n, *shape, fan_in):
        shared = jax.random.normal(next(keys), (1,) + shape, F32)
        own = jax.random.normal(next(keys), (n,) + shape, F32)
        return (((1.0 - EXPERT_OWN ** 2) ** 0.5 * shared + EXPERT_OWN * own)
                * fan_in ** -0.5).astype(c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    def merge():
        return {"stream_scale": 1.0 + normal(c.d_model, scale=0.1),
                "stream_bias": normal(c.d_model, scale=0.02),
                "out_scale": 1.0 + normal(c.d_model, scale=0.1),
                "out_bias": normal(c.d_model, scale=0.02)}

    d, hd = c.d_model, c.head_dim
    lat = (c.num_heads + c.num_kv_heads) * hd
    kv = c.num_kv_heads * hd
    r, e, inter = c.router_hidden_size, c.num_experts, \
        c.moe_intermediate_size
    params: Params = {"tok_emb": normal(c.vocab_size, d, scale=0.02),
                      "norm_f": ones(d), "blocks": []}
    for _ in range(c.num_layers):
        params["blocks"].append({
            "norm1": ones(d), "norm2": ones(d),
            "res1": merge(), "res2": merge(),
            "attn": {
                # [q~ | k~ | v1 | v2]
                "w_in": matrix(d, lat + kv, fan_in=d),
                "conv0_w": matrix(c.cca_time0, lat, fan_in=c.cca_time0),
                "conv0_b": normal(lat, scale=0.1),
                # a head's taps side by side: [heads, time1 d, d]
                "conv1_w": matrix(c.num_heads + c.num_kv_heads,
                                  c.cca_time1 * hd, hd,
                                  fan_in=c.cca_time1 * hd),
                "conv1_b": normal(lat, scale=0.1),
                "tau": uniform(c.num_kv_heads, lo=1.1, hi=1.5),
                "wo": matrix(c.num_heads * hd, d,
                             fan_in=4 * c.num_heads * hd)},
            "router": {
                "w_down": matrix(d, r, fan_in=d, dtype=F32),
                "gamma": uniform(lo=0.3, hi=0.7, dtype=F32),
                "norm": jnp.ones(r, F32),
                "w1": matrix(r, r, fan_in=r, dtype=F32),
                "w2": centred(r, r, norm=1.0),
                "w3": centred(r, e + 1, norm=2.0),
                "bias": normal(e + 1, scale=0.005, dtype=F32).at[-1].add(
                    SKIP_BIAS)},
            "moe": {"w1": experts(e, d, 2 * inter, fan_in=d),
                    "w2": experts(e, inter, d, fan_in=inter / 9.0)}})
    return params


# ------------------------------------------------------------ sublayers

def _merge(h: jax.Array, out: jax.Array, s: Params) -> jax.Array:
    """The residual-scaled sum, float32."""
    f = lambda name: s[name].astype(F32)
    return (h * f("stream_scale") + f("stream_bias")
            + out * f("out_scale") + f("out_bias"))


def _attention(h: jax.Array, p: Params, c: ZayaConfig, state: Params,
               cache: Optional[Params], pos: Any, tick: bool):
    """h <- merge(h, cca(norm1(h))): h [B, T, D] float32 at scalar `pos`,
    or (`tick`) one token a slot at positions `pos` [B, 1]."""
    with jax.named_scope("cca"):
        x = rms_norm(h, p["norm1"]["scale"], c.norm_eps).astype(c.dtype)
        form = cca_tick if tick else cca_prompt
        out, state, cache = form(x, p["attn"], state, cache, pos,
                                 **c.geometry)
        return _merge(h, out, p["res1"]), state, cache


def _experts(h: jax.Array, carried: jax.Array, p: Params, c: ZayaConfig):
    """h <- merge(h, experts(norm2(h))) under the router's ONE choice, in
    blocks of `ffn_block` tokens: h [.., D] float32, `carried` [.., R] the
    last layer's router state. Returns (h, this layer's router state, the
    rows each expert got [E], the tokens that took no expert)."""
    lead, d = h.shape[:-1], h.shape[-1]
    flat = h.reshape(-1, d)
    n = flat.shape[0]
    block = min(c.ffn_block, n)
    pad = -n % block
    rows = lambda a: jnp.pad(a.reshape(n, -1), ((0, pad), (0, 0)))
    valid = jnp.arange(n + pad) < n
    rt, none = p["router"], c.num_experts

    def one(args):
        hb, rb, ok = args
        with jax.named_scope("router"):
            x = rms_norm(hb, p["norm2"]["scale"], c.norm_eps)
            chosen, weights, rb = mlp_top1_route(
                x, rb, rt["w_down"], rt["gamma"], rt["norm"],
                (rt["w1"], rt["w2"], rt["w3"]), rt["bias"], c.norm_eps)
            # a padded row routes nowhere, and is no skip
            skipped = jnp.sum((chosen[:, 0] == none) & ok, dtype=jnp.int32)
            chosen = jnp.where(ok[:, None], chosen, none)
        with jax.named_scope("moe"):
            out, counts = held_experts(
                x.astype(c.dtype), chosen, weights, p["moe"]["w1"],
                p["moe"]["w2"], 0, _swiglu)
            return (_merge(hb, out, p["res2"]), rb, counts["sizes"],
                    skipped)

    blocks = (rows(flat), rows(carried), valid)
    if n + pad == block:
        out, state, sizes, skipped = one(blocks)
    else:
        out, state, sizes, skipped = jax.lax.map(one, tuple(
            a.reshape((-1, block) + a.shape[1:]) for a in blocks))
        out, state = out.reshape(-1, d), state.reshape(n + pad, -1)
        sizes, skipped = sizes.sum(0), skipped.sum()
    return (out[:n].reshape(lead + (d,)),
            state[:n].reshape(lead + (-1,)), sizes, skipped)


def _head(x: jax.Array, params: Params, c: ZayaConfig) -> jax.Array:
    """The final norm, then the embedding as the head."""
    with jax.named_scope("head"):
        h = rms_norm(x, params["norm_f"]["scale"], c.norm_eps).astype(
            c.dtype)
        return jax.lax.dot_general(
            h, params["tok_emb"], (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=F32)


def _counts(sizes: list, skipped: list) -> Dict[str, jax.Array]:
    return dict(held_counts(sizes),
                moe_pairs_skipped=jnp.sum(jnp.stack(skipped)))


# ------------------------------------------------------------- the model

def _state(c: ZayaConfig, batch: int) -> Params:
    return cca_state(batch, c.num_heads, c.num_kv_heads, c.head_dim,
                     c.cca_time0, c.cca_time1, c.dtype)


def zaya_init_cache(config: ZayaConfig, batch_size: int, max_len: int = 0,
                    dtype: Any = None) -> list:
    """The pairs first: one {"k", "v"} [B, S, G, d] a layer; then one
    {"conv0", "conv1", "v2"} a layer, a slot's state with no sequence
    axis (module docstring)."""
    c = config
    kv = (batch_size, max_len or c.max_seq_len, c.num_kv_heads, c.head_dim)
    dt = dtype or c.dtype
    return ([{"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
             for _ in range(c.num_layers)]
            + [_state(c, batch_size) for _ in range(c.num_layers)])


def _walk(params: Params, tokens: jax.Array, c: ZayaConfig,
          cache: Optional[list], pos: Any, tick: bool = False):
    """tokens [B, T] through every layer, at scalar position `pos` on top
    of what the cache holds (None: an empty sequence and nothing kept),
    or (`tick`) one token a slot at positions `pos` [B, 1] -> (the stream
    [B, T, D] float32, the new cache or None, the run's counters)."""
    h = params["tok_emb"][tokens].astype(F32)
    b, n = tokens.shape[0], c.num_layers
    carried = jnp.zeros(h.shape[:-1] + (c.router_hidden_size,), F32)
    new_cache = list(cache) if cache is not None else None
    sizes, skipped = [], []
    for i, p in enumerate(params["blocks"]):
        state = cache[n + i] if cache is not None else _state(c, b)
        h, state, entry = _attention(
            h, p, c, state, cache[i] if cache is not None else None, pos,
            tick)
        if new_cache is not None:
            new_cache[i], new_cache[n + i] = entry, state
        h, carried, rows, none = _experts(h, carried, p, c)
        sizes.append(rows)
        skipped.append(none)
    return h, new_cache, _counts(sizes, skipped)


def zaya_forward(params: Params, tokens: jax.Array, config: ZayaConfig
                 ) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache: every
    sequence from an empty state."""
    x, _, _ = _walk(params, tokens, config, None, 0)
    return _head(x, params, config)


def zaya_loss(params: Params, tokens: jax.Array, targets: jax.Array,
              config: ZayaConfig, remat: bool = False) -> jax.Array:
    fwd = zaya_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def zaya_forward_counted(params: Params, tokens: jax.Array,
                         config: ZayaConfig, cache: list, pos: Any):
    """tokens [B, T] at scalar position `pos` on top of what the cache
    holds: the convolutions continue from the cache's tails, the
    attention sees the cache's rows below `pos` (a run from a concrete 0
    sees itself alone). T == 1 is one step of `decode`. Returns (logits
    [B, 1, vocab] float32 of the LAST position, the new cache, the expert
    layers' counters: `ops/grouped_moe.held_counts` and
    `moe_pairs_skipped`)."""
    c = config
    b, t = tokens.shape
    if t == 1:
        logits, new_cache, counts = zaya_decode(
            params, tokens[:, 0], c, cache,
            jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
        return logits[:, None], new_cache, counts
    x, new_cache, counts = _walk(params, tokens, c, cache, pos)
    return _head(x[:, -1:], params, c), new_cache, counts


def zaya_forward_cached(params: Params, tokens: jax.Array,
                        config: ZayaConfig, cache: list, pos: Any):
    """`zaya_forward_counted` less its counters: the cache protocol's
    (logits, cache). The engine's prefill takes the counted form
    (`FAMILY.forward_counted`)."""
    return zaya_forward_counted(params, tokens, config, cache, pos)[:2]


def zaya_decode(params: Params, tokens: jax.Array, config: ZayaConfig,
                cache: list, pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache, the
    expert layers' counts for the engine's loop record). A token's query
    is made from tails the step has advanced, which cannot be
    un-advanced: there is no [B, k+1] verify form."""
    if tokens.ndim != 1:
        raise ValueError("a carried convolution cannot verify drafted "
                         "tokens: tokens must be [B]")
    x, new_cache, counts = _walk(params, tokens[:, None], config, cache,
                                 pos_vec[:, None], tick=True)
    return _head(x[:, 0], params, config), new_cache, counts


def zaya_partition_specs(config: ZayaConfig) -> Params:
    """Experts on `ep`; the attention's latent on `tp`; the rest as the
    Llama path lays a block out."""
    norm = {"scale": P()}
    merge = {"stream_scale": P(), "stream_bias": P(), "out_scale": P(),
             "out_bias": P()}
    block = {"norm1": norm, "norm2": norm, "res1": merge, "res2": merge,
             "attn": {"w_in": P("fsdp", None), "conv0_w": P(),
                      "conv0_b": P(), "conv1_w": P(), "conv1_b": P(),
                      "tau": P(), "wo": P("tp", "fsdp")},
             "router": {"w_down": P(), "gamma": P(), "norm": P(),
                        "w1": P(), "w2": P(), "w3": P(), "bias": P()},
             "moe": {"w1": P("ep", None, "tp"), "w2": P("ep", "tp", None)}}
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "blocks": [block for _ in range(config.num_layers)]}


FAMILY = Family(
    config_type=ZayaConfig, init=zaya_init, forward=zaya_forward,
    loss=zaya_loss, partition_specs=zaya_partition_specs,
    init_cache=zaya_init_cache, forward_cached=zaya_forward_cached,
    decode=zaya_decode, forward_counted=zaya_forward_counted,
    decode_walks=True)
