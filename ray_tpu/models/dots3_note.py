"""The dots3-note family (`model_type` dots3_note; dots3-note-prev
288B-A17B) in pure functional JAX, its language model alone (token ids in,
logits out): every layer is latent attention and a feed-forward part,
each behind an RMSNorm with a residual, `x <- x + attn(norm(x)); x <- x +
ffn(norm(x))`; a final RMSNorm and an untied head. Two kinds of layer,
told apart by DATA on the config (`full_layout[i]`), not by a period
wired in.

  latent attention (`ops/mla.py`), at the layer kind's own sizes
       (`_Geometry`): `c_q = s_q RMSNorm(W_qa h)`, `q = W_qb c_q` as heads
       of `[d_n | d_r]`; `[c | k_r] = W_kva h`, `c <- s_kv RMSNorm(c)`;
       `s = (hidden / rank)^1/2` on each normed latent (`lora_rescale`);
       keys' first part and values per head from c through `W_kvb`;
       rotary positions on the d_r numbers of every head's query and on
       the ONE key part all heads share; softmax scale `(d_n + d_r)^-1/2`.
       A HEADWISE GATE: `g = sigmoid(W_g h)`, one number a head, scales
       each head's output before `W_o`.
  FULL layer: 128 heads of [128 | 64], query rank 1,024, latent 512.
       An INDEXER (`ops/dsa.py`): `q_I = W_Iq c_q` as `index_heads` heads
       of `index_dim`, `k_I = LayerNorm(W_Ik h)`, ONE a token, rotary on
       the first d_r numbers of both, `w = W_Iw h * heads^-1/2 dim^-1/2`;
       `I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`; the query
       attends the `index_topk` rows `s <= t` of largest I alone (all of
       them while `t + 1 <= index_topk`).
  SLIDING layer: 64 heads of [192 | 64], ranks 1,024 and 1,024, no
       indexer: query t sees `t - window < s <= t`.
  FFN  a dense SwiGLU in the first `first_dense` layers, else the expert
       layer (`ops/grouped_moe.py`): float32 sigmoid scores over ALL
       `n_routed_experts`, the k largest of `score + bias` chosen, their
       scores normalised over the k, times `routed_scaling_factor`. THIS
       share of the deployment holds `experts_held` SwiGLU experts from
       `first_expert` on and computes their part of the sum and nothing
       for the others; the shared expert is added on every share. The
       pairs that fell here are compacted before the grouped product
       (`_held_part`): the shared op's buffer holds every pair.

The residual stream is float32, as `models/deepseek_v2.py` found a router
over a rounded stream needs.

The cache (`init_cache`) is a cache that is latent AND ring
(`models/family.py`), of entries of three widths under two row counts: a
full layer leaves TWO entries of `max_seq_len` rows, its latent rows `[c |
rope(k_r) | 0]` (640 wide) and, an entry of its own, its index keys (128
wide: a tick scores `S x 256 B` a slot and then reads `index_topk` latent
rows, never all of them); a sliding layer leaves one RING of `ring_rows`
rows (the window up to whole tiles of 128: 640) of its own latent row
(1,152 wide), the token at position p in row `p mod ring_rows`, a row
counting while its token is one of the last `window`.

A prompt (`forward_cached` from position 0, ONE program) goes through a
full layer so that nothing of the size [heads, T, T] or [heads, T, .] for
all heads is ever held beside the weights: the selection a block of
`index_block` queries at a time (`dsa.block_selection`: a mask [T, T] of
int8, packed), then `head_group` heads at a time: their queries from c_q, their
keys and values expanded from the latents, the blocked prompt form under
the mask (`dsa.selected_prompt_attention`), gated, through their rows of
W_o into the stream. A sliding layer likewise, through the band
(`mla.band_prompt_attention`). A tick scores the slab's index keys, takes
`lax.top_k`, gathers those latent rows and runs the absorbed form over
them; the rings go through the absorbed form under `mla.ring_visible`.
`forward_counted` and `decode` count what the indexer and the rings saw.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import dsa
from ..ops.grouped_moe import held_counts, held_experts, sigmoid_topk_route
from ..ops.layers import layer_norm, mm, rms_norm
from ..ops.mla import (LANES, absorbed_attention, band_prompt_attention,
                       latent_row, ring_visible, row_width)
from ..ops.rope import apply_rope, rope_table
from ..ops.swa import ring_rows
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


class _Geometry(NamedTuple):
    """A layer kind's latent attention."""
    heads: int
    q_rank: int
    kv_rank: int
    d_n: int
    d_r: int
    d_v: int
    theta: float

    @property
    def row(self) -> int:
        return row_width(self.kv_rank, self.d_r)

    @property
    def scale(self) -> float:
        return (self.d_n + self.d_r) ** -0.5


@dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    max_seq_len: int = 33280
    num_layers: int = 5
    d_model: int = 5120
    norm_eps: float = 1e-5
    # one entry a layer: 1 a full layer (with the indexer), 0 a sliding one
    full_layout: Tuple[int, ...] = (1, 1, 0, 0, 0)
    first_dense: int = 1             # leading layers with a dense ffn
    d_ff: int = 13824
    lora_rescale: bool = True        # (hidden / rank)^1/2 on the latents
    # latent attention of a full layer
    num_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # its indexer
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    # latent attention of a sliding layer
    swa_num_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    window: int = 513                # a query sees itself and window - 1
    # the expert layer
    n_routed_experts: int = 256      # the router's width
    experts_held: int = 32           # of them, on this share
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # how a prompt goes through (none of them changes a number beyond
    # rounding)
    attn_block: int = 512            # of the selected prompt form
    index_block: int = 1024          # queries the indexer scores a pass
    head_group: int = 16             # heads expanded at a time
    ffn_block: int = 2048            # tokens of a prompt a pass
    row_tile: int = LANES            # the band's block and the ring: whole
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.full_layout) != self.num_layers:
            raise ValueError("full_layout has not one entry a layer")
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError("first_dense lies outside the layers")
        if not 0 <= self.first_expert \
                <= self.n_routed_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")
        if self.index_block % self.attn_block:
            raise ValueError("index_block is not whole attn_blocks")
        for heads in (self.num_heads, self.swa_num_heads):
            if heads % min(self.head_group, heads):
                raise ValueError("head_group does not divide the heads")

    def geometry(self, layer: int) -> _Geometry:
        if self.full_layout[layer]:
            return _Geometry(self.num_heads, self.q_lora_rank,
                             self.kv_lora_rank, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim,
                             self.rope_theta)
        return _Geometry(self.swa_num_heads, self.swa_q_lora_rank,
                         self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                         self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                         self.swa_rope_theta)

    @property
    def band_block(self) -> int:
        """The band's block: window - 1 up to whole tiles of rows."""
        return -(-(self.window - 1) // self.row_tile) * self.row_tile

    @property
    def ring_rows(self) -> int:
        """The rows a sliding layer keeps a slot: the window up to whole
        tiles, or every row where the slab is no longer than that."""
        return min(-(-self.window // self.row_tile) * self.row_tile,
                   self.max_seq_len)

    @staticmethod
    def tiny() -> "Dots3NoteConfig":  # tests / dry runs
        return Dots3NoteConfig(
            vocab_size=512, max_seq_len=128, num_layers=5, d_model=64,
            d_ff=96, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_heads=8, index_dim=16, index_topk=12, swa_num_heads=2,
            swa_q_lora_rank=24, swa_kv_lora_rank=40,
            swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
            swa_v_head_dim=16, window=9, n_routed_experts=16,
            experts_held=4, num_experts_per_tok=3,
            moe_intermediate_size=32, attn_block=8, index_block=16,
            head_group=2, ffn_block=16, row_tile=4)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def dots3_note_init(config: Dots3NoteConfig, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 2 + 18 * c.num_layers))

    def normal(*shape, scale=0.02, dtype=None):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(dtype or c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    # a layer's way back into the residual stream at 0.02 / sqrt(2 L)
    # under an embedding of unit size, as models/deepseek_v2.py has it
    back = 0.02 / math.sqrt(2 * c.num_layers)
    inter = c.moe_intermediate_size
    shared = inter * c.n_shared_experts
    params: Params = {"tok_emb": normal(c.vocab_size, c.d_model, scale=1.0),
                      "norm_f": ones(c.d_model),
                      "lm_head": normal(c.d_model, c.vocab_size),
                      "blocks": []}
    for i in range(c.num_layers):
        g = c.geometry(i)
        block: Params = {
            "norm1": ones(c.d_model), "norm2": ones(c.d_model),
            "attn": {
                "w_qa": normal(c.d_model, g.q_rank),
                "q_norm": jnp.ones(g.q_rank, c.dtype),
                "w_qb": normal(g.q_rank, g.heads * (g.d_n + g.d_r)),
                "w_kva": normal(c.d_model, g.kv_rank + g.d_r),
                "kv_norm": jnp.ones(g.kv_rank, c.dtype),
                "w_kvb": normal(g.kv_rank, g.heads * (g.d_n + g.d_v)),
                "w_g": normal(c.d_model, g.heads),
                "wo": normal(g.heads * g.d_v, c.d_model, scale=back)}}
        if c.full_layout[i]:
            block["index"] = {
                "w_q": normal(g.q_rank, c.index_heads * c.index_dim),
                "w_k": normal(c.d_model, c.index_dim),
                "k_norm": {"scale": jnp.ones(c.index_dim, c.dtype),
                           "bias": jnp.zeros(c.index_dim, c.dtype)},
                "w_w": normal(c.d_model, c.index_heads)}
        if i < c.first_dense:
            block["mlp"] = {"w1": normal(c.d_model, 2 * c.d_ff),
                            "w2": normal(c.d_ff, c.d_model, scale=back)}
        else:
            block["moe"] = {
                "router": normal(c.d_model, c.n_routed_experts, dtype=F32),
                "router_bias": jnp.zeros(c.n_routed_experts, F32),
                "w1": normal(c.experts_held, c.d_model, 2 * inter),
                "w2": normal(c.experts_held, inter, c.d_model, scale=back),
                "s1": normal(c.d_model, 2 * shared),
                "s2": normal(shared, c.d_model, scale=back)}
        params["blocks"].append(block)
    return params


# ------------------------------------------------------ latent attention

def _ropes(c: Dots3NoteConfig):
    """The (cos, sin) tables by layer kind (1 full, 0 sliding), a block
    longer than the slab: a prompt is padded to whole blocks."""
    rows = c.max_seq_len + max(c.index_block, c.band_block)
    return {1: rope_table(c.qk_rope_head_dim, rows, c.rope_theta),
            0: rope_table(c.swa_qk_rope_head_dim, rows, c.swa_rope_theta)}


def _rescale(c: Dots3NoteConfig, rank: int) -> float:
    return math.sqrt(c.d_model / rank) if c.lora_rescale else 1.0


def _latents(h: jax.Array, p: Params, c: Dots3NoteConfig, g: _Geometry):
    """h [.., D] -> (c_q [.., q_rank], the normed latent [.., kv_rank],
    the shared key part BEFORE its rotation [.., d_r], the gate [..,
    heads] float32)."""
    c_q = rms_norm(mm(h, p["w_qa"]), p["q_norm"], c.norm_eps)
    lat, k_r = jnp.split(mm(h, p["w_kva"]), [g.kv_rank], -1)
    lat = rms_norm(lat, p["kv_norm"], c.norm_eps)
    gate = jax.nn.sigmoid(jnp.dot(h, p["w_g"], preferred_element_type=F32))
    scale = lambda x, rank: (x.astype(F32) * _rescale(c, rank)
                             ).astype(x.dtype)
    return scale(c_q, g.q_rank), scale(lat, g.kv_rank), k_r, gate


def _index_inputs(h: jax.Array, c_q: jax.Array, p: Params,
                  c: Dots3NoteConfig, rope, positions: jax.Array):
    """The indexer's side of queries: h, c_q [B, T, .] at `positions` [B,
    T] -> (q_I [B, T, heads, dim], the first d_r numbers rotated, w [B, T,
    heads] float32 with the indexer's scale)."""
    b, t, _ = h.shape
    q = mm(c_q, p["w_q"]).reshape(b, t, c.index_heads, c.index_dim)
    w = jnp.dot(h, p["w_w"], preferred_element_type=F32) \
        * (c.index_heads ** -0.5 * c.index_dim ** -0.5)
    return _rotated(q, c, rope, positions), w


def _index_keys(h: jax.Array, p: Params, c: Dots3NoteConfig, rope,
                positions: jax.Array) -> jax.Array:
    """h [B, T, D] at `positions` [B, T] -> k_I [B, T, dim]: ONE index
    key a token, normed, its first d_r numbers rotated."""
    k = layer_norm(mm(h, p["w_k"]), p["k_norm"]["scale"],
                   p["k_norm"]["bias"], c.norm_eps)
    return _rotated(k[:, :, None, :], c, rope, positions)[:, :, 0]


def _rotated(x: jax.Array, c: Dots3NoteConfig, rope, positions):
    d_r = c.qk_rope_head_dim
    return jnp.concatenate(
        [apply_rope(x[..., :d_r], *rope, positions), x[..., d_r:]], -1)


def _stacked(xs: list) -> jax.Array:
    return xs[0][None] if len(xs) == 1 else jnp.stack(xs)


def _padded_rows(x: jax.Array, rows: int) -> jax.Array:
    """x [T, ...] -> [rows, ...], zeros behind."""
    if rows == x.shape[0]:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _by_head_groups(x, c_q, lat, k_r, gate, p: Params, c: Dots3NoteConfig,
                    g: _Geometry, rope, attend, rows: int) -> jax.Array:
    """One sequence's attention `head_group` heads at a time: the stream
    x [Tp, D] float32 (Tp whole blocks of `rows`), c_q [Tp, q_rank], lat
    [Tp, kv_rank], k_r [Tp, d_r] rotated, gate [Tp, heads]; `attend(q_n,
    q_r, k_n, v)` over [G, Tp, .] -> [G, Tp, d_v]. Returns x plus the
    layer's output: each group's heads gated and through their rows of
    W_o, added where the stream lies."""
    tp = c_q.shape[0]
    group = min(c.head_group, g.heads)
    w_qb = p["w_qb"].reshape(g.q_rank, g.heads, g.d_n + g.d_r)
    w_kvb = p["w_kvb"].reshape(g.kv_rank, g.heads, g.d_n + g.d_v)
    wo = p["wo"].reshape(g.heads, g.d_v, c.d_model)
    cos, sin = (t[:tp][None] for t in rope)

    def one(y, n):
        at = n * group
        heads = lambda w, axis: jax.lax.dynamic_slice_in_dim(
            w, at, group, axis)
        q = jnp.einsum("tr,rgd->gtd", c_q, heads(w_qb, 1),
                       preferred_element_type=F32).astype(c_q.dtype)
        q_r = q[..., g.d_n:].astype(F32)
        q1, q2 = jnp.split(q_r, 2, -1)
        q_r = jnp.concatenate([q1 * cos - q2 * sin, q2 * cos + q1 * sin],
                              -1).astype(q.dtype)
        kv = jnp.einsum("tc,cgd->gtd", lat, heads(w_kvb, 1),
                        preferred_element_type=F32).astype(lat.dtype)
        a = attend(q[..., :g.d_n], q_r, kv[..., :g.d_n], kv[..., g.d_n:])
        a = (a.astype(F32) * heads(gate, 1).T[..., None]).astype(a.dtype)
        wo_g = heads(wo, 0)

        def into(r, y):         # a block of rows at a time, where y lies:
            at = r * rows       # no [Tp, D] product beside the stream
            out = jnp.einsum(
                "gtd,gdm->tm", jax.lax.dynamic_slice_in_dim(a, at, rows, 1),
                wo_g, preferred_element_type=F32)
            return jax.lax.dynamic_update_slice_in_dim(
                y, jax.lax.dynamic_slice_in_dim(y, at, rows, 0) + out, at, 0)

        return jax.lax.fori_loop(0, tp // rows, into, y), None

    if group == g.heads:
        return one(x, 0)[0]
    return jax.lax.scan(one, x, jnp.arange(g.heads // group))[0]


def _selection(hp, c_q, k_i, p: Params, c: Dots3NoteConfig, rope,
               tokens: int):
    """The selected set of every query of ONE padded prompt hp [Tp, D]
    (c_q [Tp, q_rank], k_i [Tp, dim]) as tiles of a mask
    (`dsa.mask_tiles`), `index_block` queries at a time: their q_I is made
    in the pass, so that no [Tp, heads, dim] stands; and the scores the
    indexer computed."""
    tp = hp.shape[0]
    block = min(c.index_block, tp)
    tiles, scored = dsa.selection_tiles(
        block, tp, tokens, c.index_heads, c.index_dim, c.index_topk)

    def one(args):
        n, h_b, cq_b = args
        at = n * block + jnp.arange(block)[None]
        q_i, w = _index_inputs(h_b[None], cq_b[None], p, c, rope, at)
        return dsa.mask_tiles(dsa.block_selection(
            q_i[0], k_i, w[0], n * block, c.index_topk, tokens, tiles),
            c.attn_block)

    cut = lambda x: x.reshape((tp // block, block) + x.shape[1:])
    out = jax.lax.map(one, (jnp.arange(tp // block), cut(hp), cut(c_q)))
    return out.reshape((-1,) + out.shape[2:]), scored


def _attn_prefill_one(x: jax.Array, p: Params, c: Dots3NoteConfig,
                      layer: int, rope):
    """ONE sequence, the stream x [T, D] float32 from position 0, through
    layer `layer`'s attention -> (x + the layer's output, the layer's
    cache rows {"k": [T, row]} and, of a full layer, {"index": [T,
    index_dim]}, the scores its indexer computed)."""
    g = c.geometry(layer)
    t = x.shape[0]
    full = bool(c.full_layout[layer])
    if full:
        tp = -(-t // c.attn_block) * c.attn_block
        if tp > c.index_block:
            tp = -(-t // c.index_block) * c.index_block
    else:
        tp = -(-t // c.band_block) * c.band_block
    xp = _padded_rows(x, tp)
    hp = _norm1(xp, p, c)
    c_q, lat, k_r, gate = _latents(hp, p["attn"], c, g)
    k_r = apply_rope(k_r[None, :, None, :], *rope)[0, :, 0]
    rows = {"k": latent_row(lat, k_r, g.row, c.dtype)[:t]}
    scored = 0
    if full:
        with jax.named_scope("dsa_select"):
            k_i = _index_keys(hp[None], p["index"], c, rope, None)[0]
            rows["index"] = k_i[:t].astype(c.dtype)
            tiles, scored = _selection(hp, c_q, k_i, p["index"], c, rope, t)

        def attend(q_n, q_r, k_n, v):
            return dsa.selected_prompt_attention(
                q_n, q_r, k_n, k_r, v, tiles, g.scale, c.attn_block, t)
    else:
        def attend(q_n, q_r, k_n, v):
            return band_prompt_attention(
                q_n, q_r, k_n, k_r, v, g.scale, c.window, c.band_block, t)

    with jax.named_scope("mla_selected" if full else "mla_band"):
        out = _by_head_groups(xp, c_q, lat, k_r, gate, p["attn"], c, g, rope,
                              attend, c.attn_block if full else c.band_block)
    return (out if t == tp else out[:t]), rows, scored


def _attn_prefill(x: jax.Array, p: Params, c: Dots3NoteConfig, layer: int,
                  rope, cache: Optional[list], at: Tuple[int, ...]):
    """x <- x + attn(norm1(x)) over x [B, T, D] from position 0. The
    layer's rows land in its entries `at` of the cache, if there is one:
    a full layer's latent rows and index keys in rows [0, T), a sliding
    layer's as the ring holds them after the prompt. Returns (the stream,
    the entries, the scores the indexer computed a sequence)."""
    outs = [_attn_prefill_one(x[b], p, c, layer, rope)
            for b in range(x.shape[0])]
    entries = []
    if cache is not None:
        names = ("k", "index") if c.full_layout[layer] else ("k",)
        for name, i in zip(names, at):
            new = _stacked([o[1][name] for o in outs])
            slab = cache[i]["k"]
            entries.append({"k": jax.lax.dynamic_update_slice(
                slab, ring_rows(new, slab.shape[1]).astype(slab.dtype),
                (0, 0, 0))})
    return _stacked([o[0] for o in outs]), entries, outs[0][2]


def _attn_decode(h: jax.Array, p: Params, c: Dots3NoteConfig, layer: int,
                 rope, cache: list, at: Tuple[int, ...],
                 positions: jax.Array):
    """One token a slot, h [B, 1, D] at `positions` [B, 1]: its rows are
    written where they belong (a ring's at `position mod rows`) and the
    absorbed form reads what the layer may see: a full layer the
    `index_topk` rows its indexer picks from the slab's index keys,
    gathered; a sliding layer its ring under `ring_visible`."""
    g = c.geometry(layer)
    b = h.shape[0]
    pa = p["attn"]
    c_q, lat, k_r, gate = _latents(h, pa, c, g)
    q = mm(c_q, pa["w_qb"]).reshape(b, 1, g.heads, g.d_n + g.d_r)
    q_n = q[..., :g.d_n]
    q_r = apply_rope(q[..., g.d_n:], *rope, positions)
    k_r = apply_rope(k_r[:, :, None, :], *rope, positions)[:, :, 0]
    w_kvb = pa["w_kvb"].reshape(g.kv_rank, g.heads, g.d_n + g.d_v)
    slab = cache[at[0]]["k"]
    slot = jnp.arange(b)[:, None]
    new = latent_row(lat, k_r, g.row, slab.dtype)
    if c.full_layout[layer]:
        slab = slab.at[slot, positions].set(new)
        q_i, w = _index_inputs(h, c_q, p["index"], c, rope, positions)
        k_i = _index_keys(h, p["index"], c, rope, positions)
        keys = cache[at[1]]["k"]
        keys = keys.at[slot, positions].set(k_i.astype(keys.dtype))
        picked, seen = dsa.tick_selection(q_i[:, 0], keys, w[:, 0],
                                          positions[:, 0], c.index_topk)
        with jax.named_scope("mla_selected_tick"):
            rows = jnp.take_along_axis(slab, picked[..., None], axis=1)
            a = absorbed_attention(q_n, q_r, rows, None, w_kvb, g.scale,
                                   visible=seen[:, None])
        entries = [{"k": slab}, {"k": keys}]
    else:
        with jax.named_scope("mla_ring_tick"):
            rows = slab.shape[1]
            slab = slab.at[slot, positions % rows].set(new)
            a = absorbed_attention(
                q_n, q_r, slab, None, w_kvb, g.scale,
                visible=ring_visible(positions, rows, c.window))
        entries = [{"k": slab}]
    a = (a.astype(F32) * gate[..., None]).astype(a.dtype)
    return jnp.dot(a.reshape(b, 1, -1), pa["wo"],
                   preferred_element_type=F32), entries


# ------------------------------------------------- the feed-forward parts

def _shared_mlp(h: jax.Array, w1: jax.Array, w2: jax.Array) -> jax.Array:
    """A SwiGLU of h (in the weights' type), float32 out."""
    mid = _swiglu(jnp.dot(h, w1, preferred_element_type=F32))
    return jnp.dot(mid.astype(h.dtype), w2, preferred_element_type=F32)


COMPACT_ABOVE = 256      # pairs: a tick's handful is not worth compacting


def _held_part(h: jax.Array, chosen: jax.Array, weights: jax.Array,
               p: Params, c: Dots3NoteConfig) -> Tuple[jax.Array, jax.Array]:
    """sum over a token's chosen experts HELD HERE of weight x expert(h):
    (`[T, D]` float32, the rows each held expert got). `held_experts`
    sizes its buffer for EVERY token-expert pair, and a share that holds
    an eighth of the router's width would sort, gather and multiply eight
    rows for each one that counts. So the pairs that fell here are
    compacted first, into a buffer of TWICE the share an even router
    sends (`cap`), the product runs over that, and a token's rows are
    gathered back; a block whose router sends more than `cap` takes the
    whole buffer (`lax.cond`: the same numbers, a token's sum in another
    order)."""
    t, k = chosen.shape
    def every():
        out, counts = held_experts(h, chosen, weights, p["w1"], p["w2"],
                                   c.first_expert, _swiglu)
        return out, counts["sizes"]

    cap = -(-2 * t * k * c.experts_held // c.n_routed_experts // 8) * 8
    if t * k <= COMPACT_ABOVE or cap >= t * k:
        return every()
    flat = chosen.reshape(-1)
    here = (flat >= c.first_expert) & (flat < c.first_expert + c.experts_held)

    def compact():
        pairs = jnp.argsort(~here, stable=True)[:cap]   # those held first
        expert = jnp.where(here[pairs], flat[pairs], c.n_routed_experts)
        out, counts = held_experts(
            h[pairs // k], expert[:, None], weights.reshape(-1)[pairs, None],
            p["w1"], p["w2"], c.first_expert, _swiglu)
        # where each pair's row lies in the buffer; `cap`: a row of zeros
        at = jnp.full((t * k,), cap, jnp.int32).at[pairs].set(
            jnp.arange(cap, dtype=jnp.int32))
        out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
        return out[at].reshape(t, k, -1).sum(1), counts["sizes"]

    return jax.lax.cond(here.sum() <= cap, compact, every)


def expert_layer(h32: jax.Array, valid: jax.Array, p: Params,
                 c: Dots3NoteConfig) -> Tuple[jax.Array, jax.Array]:
    """h32 [T, D] float32, valid [T] bool (a padded row routes nowhere) ->
    (the layer's output on this share [T, D] float32, the rows each held
    expert got [held] int32). The router reads h32 itself; the experts
    read it in the weights' type. The weights are normalised over all
    the chosen experts, held here or not; the shared expert is added
    whole (a deployment's shares are summed with it counted once)."""
    h = h32.astype(c.dtype)
    chosen, weights = sigmoid_topk_route(
        h32, p["router"], p["router_bias"], c.num_experts_per_tok,
        c.routed_scaling_factor, c.norm_topk_prob)
    chosen = jnp.where(valid[:, None], chosen, c.n_routed_experts)
    routed, sizes = _held_part(h, chosen, weights, p, c)
    return routed + _shared_mlp(h, p["s1"], p["s2"]), sizes


def _ffn(x: jax.Array, p: Params, c: Dots3NoteConfig):
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
        h32 = rms_norm(xb, p["norm2"]["scale"], c.norm_eps)
        if "mlp" in p:
            with jax.named_scope("dense_mlp"):
                return xb + _shared_mlp(h32.astype(c.dtype), p["mlp"]["w1"],
                                        p["mlp"]["w2"]), None
        with jax.named_scope("moe"):
            y, sizes = expert_layer(h32, ok, p["moe"], c)
            return xb + y, sizes

    if n + pad == block:
        out, sizes = one((flat, valid))
    else:
        def step(i, carry):     # a block where the stream lies
            out, sizes = carry
            at = i * block
            new, rows = one((
                jax.lax.dynamic_slice_in_dim(out, at, block, 0),
                jax.lax.dynamic_slice_in_dim(valid, at, block, 0)))
            return (jax.lax.dynamic_update_slice_in_dim(out, new, at, 0),
                    None if rows is None else sizes + rows)

        held = None if "mlp" in p else jnp.zeros(
            (p["moe"]["w1"].shape[0],), jnp.int32)
        out, sizes = jax.lax.fori_loop(0, (n + pad) // block, step,
                                       (flat, held))
    return out[:n].reshape(lead + (d,)), sizes


def _head(x: jax.Array, params: Params, c: Dots3NoteConfig) -> jax.Array:
    with jax.named_scope("head"):
        h = rms_norm(x, params["norm_f"]["scale"], c.norm_eps)
        return jnp.dot(h.astype(c.dtype), params["lm_head"],
                       preferred_element_type=F32)


# ------------------------------------------------------------- the model

def entries_of(c: Dots3NoteConfig) -> Tuple[Tuple[int, ...], ...]:
    """The cache entries of each layer: a full layer's latent rows and
    its index keys, a sliding layer's ring."""
    out, n = [], 0
    for full in c.full_layout:
        out.append(tuple(range(n, n + (2 if full else 1))))
        n += 2 if full else 1
    return tuple(out)


def _norm1(x: jax.Array, p: Params, c: Dots3NoteConfig) -> jax.Array:
    return rms_norm(x, p["norm1"]["scale"], c.norm_eps).astype(c.dtype)


def _sum_min(upto: jax.Array, cap: int) -> jax.Array:
    """sum over n in 1 .. upto of min(n, cap), for each entry."""
    low = jnp.minimum(upto, cap)
    return low * (low + 1) // 2 + (upto - low) * cap


def _prefill(params: Params, tokens: jax.Array, c: Dots3NoteConfig,
             cache: list | None):
    """tokens [B, T] from position 0 -> (the stream [B, T, D], the new
    cache, the run's counters)."""
    x = params["tok_emb"][tokens].astype(F32)
    t = tokens.shape[1]
    new_cache = list(cache) if cache is not None else None
    sizes, scored, ropes, at = [], 0, _ropes(c), entries_of(c)
    for i, p in enumerate(params["blocks"]):
        x, entries, n = _attn_prefill(
            x, p, c, i, ropes[c.full_layout[i]], cache, at[i])
        for j, entry in zip(at[i], entries):
            new_cache[j] = entry
        scored = n or scored
        x, rows = _ffn(x, p, c)
        sizes += [] if rows is None else [rows]
    upto = jnp.int32(t)
    return x, new_cache, dict(
        held_counts(sizes),
        # of ONE sequence and ONE layer of its kind (the sums over a long
        # prompt's layers would not fit an int32): the rows the indexer
        # computed a score for, the rows a query could see, the rows it
        # attended, and the rows a sliding layer's queries read
        dsa_rows_scored=jnp.int32(scored),
        dsa_rows_visible=_sum_min(upto, t),
        dsa_rows_selected=_sum_min(upto, c.index_topk),
        ring_rows_read=_sum_min(upto, c.window))


def dots3_note_forward(params: Params, tokens: jax.Array,
                       config: Dots3NoteConfig) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache."""
    x, _, _ = _prefill(params, tokens, config, None)
    return _head(x, params, config)


def dots3_note_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                    config: Dots3NoteConfig, remat: bool = False
                    ) -> jax.Array:
    fwd = dots3_note_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def dots3_note_init_cache(config: Dots3NoteConfig, batch_size: int,
                          max_len: int = 0, dtype: Any = None) -> list:
    """`entries_of`: {"k": [B, S, row]} and {"k": [B, S, index_dim]} a
    full layer, {"k": [B, ring_rows, row]} a sliding layer: latent rows
    alone, of three widths under two row counts."""
    c = config
    if max_len:
        c = dataclasses.replace(c, max_seq_len=max_len)
    out = []
    for i, full in enumerate(c.full_layout):
        row = c.geometry(i).row
        shapes = [(c.max_seq_len, row), (c.max_seq_len, c.index_dim)] \
            if full else [(c.ring_rows, row)]
        out += [{"k": jnp.zeros((batch_size,) + s, dtype or c.dtype)}
                for s in shapes]
    return out


def dots3_note_forward_counted(params: Params, tokens: jax.Array,
                               config: Dots3NoteConfig, cache: list,
                               pos: Any):
    """tokens [B, T] on top of what the cache holds. T > 1 is a prefill
    FROM POSITION 0 (`pos` must be a concrete 0: the prompt forms read
    the run alone, and a ring keeps no earlier rows to resume from); T ==
    1 appends one token at scalar position `pos`. Returns (logits [B, 1,
    vocab] float32 of the LAST position, the new cache, the counters of
    the run: the expert layers' as `decode` gives them, and of one
    sequence and one layer of its kind `dsa_rows_scored`,
    `dsa_rows_visible`, `dsa_rows_selected`, `ring_rows_read`)."""
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
                "forms attend over the run alone (pos must be a concrete "
                "0)")
        x, new_cache, counts = _prefill(params, tokens, c, cache)
        return _head(x[:, -1:], params, c), new_cache, counts
    logits, new_cache, counts = dots3_note_decode(
        params, tokens[:, 0], c, cache,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    return logits[:, None], new_cache, counts


def dots3_note_forward_cached(params: Params, tokens: jax.Array,
                              config: Dots3NoteConfig, cache: list,
                              pos: Any):
    """`dots3_note_forward_counted` less its counters: the cache
    protocol's (logits, cache)."""
    return dots3_note_forward_counted(params, tokens, config, cache,
                                      pos)[:2]


def dots3_note_decode(params: Params, tokens: jax.Array,
                      config: Dots3NoteConfig, cache: list,
                      pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache, the
    step's counters for the engine's loop record: `held_counts` and,
    summed over the slots for one layer of its kind, the rows the indexer
    scored (every row of the slab: the scores are taken where the keys
    lie), could see and picked, and the rows of a ring the step read).
    There is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("this family's decode has no verify form: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]].astype(F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    sizes, ropes, at = [], _ropes(c), entries_of(c)
    for i, p in enumerate(params["blocks"]):
        y, entries = _attn_decode(_norm1(x, p, c), p, c, i,
                                  ropes[c.full_layout[i]], cache, at[i],
                                  positions)
        for j, entry in zip(at[i], entries):
            new_cache[j] = entry
        x, rows = _ffn(x + y, p, c)
        sizes += [] if rows is None else [rows]
    seen = pos_vec + 1
    slab_rows = max(blk["k"].shape[1] for blk in cache)
    return _head(x[:, 0], params, c), new_cache, dict(
        held_counts(sizes),
        dsa_rows_scored=jnp.int32(tokens.shape[0] * slab_rows),
        dsa_rows_visible=seen.sum(),
        dsa_rows_selected=jnp.minimum(seen, c.index_topk).sum(),
        ring_rows_read=jnp.minimum(seen, c.window).sum())


def dots3_note_partition_specs(config: Dots3NoteConfig) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    attn = {"w_qa": P("fsdp", None), "q_norm": P(), "w_qb": P(None, "tp"),
            "w_kva": P("fsdp", None), "kv_norm": P(),
            "w_kvb": P(None, "tp"), "w_g": P("fsdp", "tp"),
            "wo": P("tp", "fsdp")}
    index = {"w_q": P(None, "tp"), "w_k": P("fsdp", None),
             "k_norm": {"scale": P(), "bias": P()}, "w_w": P("fsdp", None)}
    dense = {"mlp": {"w1": P("fsdp", "tp"), "w2": P("tp", "fsdp")}}
    sparse = {"moe": {
        "router": P(), "router_bias": P(),
        "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None),
        "s1": P("fsdp", "tp"), "s2": P("tp", "fsdp")}}
    blocks = [{"norm1": norm, "norm2": norm, "attn": attn,
               **({"index": index} if config.full_layout[i] else {}),
               **(dense if i < config.first_dense else sparse)}
              for i in range(config.num_layers)]
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"), "blocks": blocks}


FAMILY = Family(
    config_type=Dots3NoteConfig, init=dots3_note_init,
    forward=dots3_note_forward, loss=dots3_note_loss,
    partition_specs=dots3_note_partition_specs,
    init_cache=dots3_note_init_cache,
    forward_cached=dots3_note_forward_cached, decode=dots3_note_decode,
    forward_counted=dots3_note_forward_counted)
