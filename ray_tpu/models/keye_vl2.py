"""The Keye-VL-2.0 family (`model_type` KeyeVL2; Keye-VL-2.0-30B-A3B) in
pure functional JAX, its language model alone (token ids in, logits out;
the vision tower is not here, and on text positions the three streams of
its multimodal rotary coincide: the plain rotary, exactly): every layer
is grouped-query attention UNDER A LEARNED SELECTION and an expert layer,
each behind an RMSNorm with a residual, `x <- x + attn(norm1(x)); x <- x +
experts(norm2(x))`; a final RMSNorm and an untied head.

  attention  `num_heads` query heads of `head_dim` over `num_kv_heads`
             heads of keys and values (32 over 4 of 128), no bias; an
             RMSNorm over the `head_dim` numbers of each query and each
             key head (a learned scale a layer); rotary positions
             (`ops/rope.py`, split-half) on all of both. A query attends
             only the rows its layer's INDEXER picks (`ops/dsa.py`): `q_I
             = W_Iq h` as `index_heads` heads of `index_dim` from the
             normed stream h, `k_I = LayerNorm(W_Ik h)`, ONE a token,
             rotary on the first `index_rope_dim` numbers of both, `w =
             W_Iw h * heads^-1/2 dim^-1/2`; `I[t, s] = sum_j w[t, j]
             relu(q_I[t, j] . k_I[s])`, float32; the `index_topk` rows `s
             <= t` of largest I (all of them while `t + 1 <=
             index_topk`).
  experts    `softmax_topk_route` (`ops/grouped_moe.py`) over the float32
             normed stream: the k largest logits, weighed by their
             softmax over the chosen (`norm_topk_prob`). Every expert is
             held here (`held_experts` from `first_expert` 0 on,
             `experts_held` = the router's width): SwiGLU experts, gate
             and up packed in `w1` [E, D, 2 I], `silu(gate) * up` into
             `w2` [E, I, D]. No shared expert.

The residual stream is float32, as `models/deepseek_v2.py` found a router
over a rounded stream needs; weights and every product's inputs are
`dtype` (bf16 as served), products accumulate in float32.

The cache (`init_cache`) is a cache that is pairs AND index
(`models/family.py`): a layer leaves TWO entries of `max_seq_len` rows,
its keys and values `{"k", "v"}` `[B, S, kv heads, head_dim]` and, an
entry of its own, its index keys `{"k"}` `[B, S, index_dim]` (a tick
scores `S x 2 index_dim` bytes a slot and then reads `index_topk` rows of
keys and of values, never all of them).

A prompt (`forward_cached` from position 0, ONE program) goes through a
layer in blocks: the selection `index_block` queries at a time
(`dsa.block_selection`: a mask [T, T] of int8, packed), then `head_group`
query heads a call of the selected form under that mask
(`dsa.gqa_selected_prompt_attention`), then the experts `ffn_block` tokens
a pass; no result depends on a block size beyond rounding. A tick scores
the slab's index keys as far as the furthest live slot stands
(`dsa.tick_rows`: the rows behind are unseen to every slot), takes the
`topk` best, gathers those rows of keys and values and attends over them
(`dsa.tick_selection`, `dsa.gqa_selected_tick`). `forward_counted` and `decode` count what the
indexer and the experts saw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import dsa
from ..ops.grouped_moe import held_counts, held_experts, softmax_topk_route
from ..ops.layers import layer_norm, mm, rms_norm
from ..ops.rope import apply_rope, rope_table
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


@dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    max_seq_len: int = 33792
    num_layers: int = 6
    d_model: int = 2048
    norm_eps: float = 1e-6
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    # the indexer
    index_heads: int = 16
    index_dim: int = 64
    index_rope_dim: int = 32         # the leading numbers that are rotated
    index_topk: int = 2048
    # the expert layer
    num_experts: int = 128           # the router's width
    experts_held: int = 128          # of them, here
    first_expert: int = 0            # the first one held
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    # how a prompt goes through (none of them changes a number beyond
    # rounding)
    attn_block: int = 512            # of the selected prompt form
    index_block: int = 1024          # queries the indexer scores a pass
    head_group: int = 32             # query heads a call of that form
    ffn_block: int = 2048            # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        group = min(self.head_group, self.num_heads)
        if self.num_heads % group \
                or group % (self.num_heads // self.num_kv_heads):
            raise ValueError("head_group is not whole heads of keys that "
                             "divide the heads")
        if self.index_block % self.attn_block:
            raise ValueError("index_block is not whole attn_blocks")
        if self.index_rope_dim % 2 or self.index_rope_dim > self.index_dim:
            raise ValueError("index_rope_dim is no even part of index_dim")
        if not 0 <= self.first_expert \
                <= self.num_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router")

    @staticmethod
    def tiny() -> "KeyeVL2Config":  # tests / dry runs
        return KeyeVL2Config(
            vocab_size=512, max_seq_len=128, num_layers=3, d_model=64,
            num_heads=4, num_kv_heads=2, head_dim=16, index_heads=16,
            index_dim=8, index_rope_dim=4, index_topk=12, num_experts=8,
            experts_held=8, num_experts_per_tok=3, moe_intermediate_size=32,
            attn_block=8, index_block=16, head_group=2, ffn_block=16)


def _swiglu(x: jax.Array) -> jax.Array:
    """[rows, 2 I] (gate | up) -> silu(gate) * up, [rows, I]."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------------ init

def keye_vl2_init(config: KeyeVL2Config, key: jax.Array) -> Params:
    c = config
    keys = iter(jax.random.split(key, 2 + 10 * c.num_layers))

    def normal(*shape, scale=0.02, dtype=None):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(dtype or c.dtype)

    def ones(n):
        return {"scale": jnp.ones(n, c.dtype)}

    # a layer's way back into the residual stream at 0.02 / sqrt(2 L)
    # under an embedding of unit size, as models/dots3_note.py has it
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
                     "q_norm": jnp.ones(c.head_dim, c.dtype),
                     "k_norm": jnp.ones(c.head_dim, c.dtype),
                     "wo": normal(q_dim, c.d_model, scale=back)},
            "index": {"w_q": normal(c.d_model, c.index_heads * c.index_dim),
                      "w_k": normal(c.d_model, c.index_dim),
                      "k_norm": {"scale": jnp.ones(c.index_dim, c.dtype),
                                 "bias": jnp.zeros(c.index_dim, c.dtype)},
                      "w_w": normal(c.d_model, c.index_heads)},
            "moe": {"router": normal(c.d_model, c.num_experts, dtype=F32),
                    "w1": normal(c.experts_held, c.d_model, 2 * inter),
                    "w2": normal(c.experts_held, inter, c.d_model,
                                 scale=back)}})
    return params


# -------------------------------------------------------------- attention

def _ropes(c: KeyeVL2Config):
    """(width, theta) of the heads' rotation and of the indexer's rotated
    part. No table a slab long stands in a program: a prompt's rows are
    0 .. T-1 and a tick's or an indexer block's are its positions' own
    (`_rope`)."""
    return (c.head_dim, c.rope_theta), (c.index_rope_dim, c.rope_theta)


def _rope(x: jax.Array, rope, positions: Optional[jax.Array]) -> jax.Array:
    """x [B, T, H, d] rotated at `positions` [B, T] (None: 0 .. T-1).
    With positions, cos and sin are made for those rows alone, from
    `rope_table`'s own formula: a table of every row the slab has, made
    anew in a tick and in every block of the indexer, cost a tick 0.24 ms
    and a layer of an 8,192-token prompt 0.95 (the chip, PERF.md PR 49)."""
    dim, theta = rope
    b, t = x.shape[:2]
    if positions is None:
        return apply_rope(x, *rope_table(dim, t, theta))
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    freqs = jnp.outer(positions.reshape(-1).astype(F32), inv_freq)
    return apply_rope(x, jnp.cos(freqs), jnp.sin(freqs),
                      jnp.arange(b * t).reshape(b, t))


def _qkv(h: jax.Array, p: Params, c: KeyeVL2Config, rope,
         positions: Optional[jax.Array]):
    """h [B, T, D] at `positions` [B, T] (None: 0 .. T-1) -> q [B, T, H,
    d], k, v [B, T, G, d]: q and k normed a head, then rotated."""
    b, t, _ = h.shape
    q = mm(h, p["wq"]).reshape(b, t, c.num_heads, c.head_dim)
    k = mm(h, p["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    v = mm(h, p["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    q = _rope(rms_norm(q, p["q_norm"], c.norm_eps), rope, positions)
    k = _rope(rms_norm(k, p["k_norm"], c.norm_eps), rope, positions)
    return q, k, v


def _rotated(x: jax.Array, c: KeyeVL2Config, rope, positions):
    d_r = c.index_rope_dim
    return jnp.concatenate(
        [_rope(x[..., :d_r], rope, positions), x[..., d_r:]], -1)


def _index_inputs(h: jax.Array, p: Params, c: KeyeVL2Config, rope,
                  positions: Optional[jax.Array]):
    """The indexer's side of queries: h [B, T, D] at `positions` [B, T]
    -> (q_I [B, T, heads, dim], its leading part rotated, w [B, T, heads]
    float32 with the indexer's scale)."""
    b, t, _ = h.shape
    q = mm(h, p["w_q"]).reshape(b, t, c.index_heads, c.index_dim)
    w = jnp.dot(h, p["w_w"], preferred_element_type=F32) \
        * (c.index_heads ** -0.5 * c.index_dim ** -0.5)
    return _rotated(q, c, rope, positions), w


def _index_keys(h: jax.Array, p: Params, c: KeyeVL2Config, rope,
                positions: Optional[jax.Array]) -> jax.Array:
    """h [B, T, D] -> k_I [B, T, dim]: ONE index key a token, normed, its
    leading part rotated."""
    k = layer_norm(mm(h, p["w_k"]), p["k_norm"]["scale"],
                   p["k_norm"]["bias"], c.norm_eps)
    return _rotated(k[:, :, None, :], c, rope, positions)[:, :, 0]


def _padded_rows(x: jax.Array, rows: int) -> jax.Array:
    """x [T, ...] -> [rows, ...], zeros behind."""
    if rows == x.shape[0]:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _selection(hp, k_i, p: Params, c: KeyeVL2Config, rope, tokens: int):
    """The selected set of every query of ONE padded prompt hp [Tp, D]
    (k_i [Tp, dim]) as tiles of a mask (`dsa.mask_tiles`), `index_block`
    queries at a time: their q_I is made in the pass, so that no [Tp,
    heads, dim] stands; and the scores the indexer computed."""
    tp = hp.shape[0]
    block = min(c.index_block, tp)
    tiles, scored = dsa.selection_tiles(
        block, tp, tokens, c.index_heads, c.index_dim, c.index_topk)

    def one(args):
        n, h_b = args
        at = n * block + jnp.arange(block)[None]
        q_i, w = _index_inputs(h_b[None], p, c, rope, at)
        return dsa.mask_tiles(dsa.block_selection(
            q_i[0], k_i, w[0], n * block, c.index_topk, tokens, tiles),
            c.attn_block)

    out = jax.lax.map(one, (jnp.arange(tp // block),
                            hp.reshape(tp // block, block, -1)))
    return out.reshape((-1,) + out.shape[2:]), scored


def _selected(q, k, v, tiles, c: KeyeVL2Config, tokens: int) -> jax.Array:
    """q [H, Tp, d], k, v [G, Tp, d] -> [H, Tp, d] under the mask,
    `head_group` query heads (whole heads of keys) a call."""
    heads, tp, d = q.shape
    group = min(c.head_group, heads)
    attend = lambda q_g, k_g, v_g: dsa.gqa_selected_prompt_attention(
        q_g, k_g, v_g, tiles, c.head_dim ** -0.5, c.attn_block, tokens)
    if group == heads:
        return attend(q, k, v)
    calls = heads // group
    cut = lambda x: x.reshape((calls, x.shape[0] // calls) + x.shape[1:])
    out = jax.lax.map(lambda a: attend(*a), (cut(q), cut(k), cut(v)))
    return out.reshape(heads, tp, d)


def _attn_prefill_one(x: jax.Array, p: Params, c: KeyeVL2Config, ropes):
    """ONE sequence, the stream x [T, D] float32 from position 0, through
    a layer's attention -> (x + the layer's output, the layer's cache rows
    {"k", "v": [T, G, d], "index": [T, index_dim]}, the scores its indexer
    computed)."""
    t = x.shape[0]
    tp = -(-t // c.attn_block) * c.attn_block
    if tp > c.index_block:
        tp = -(-t // c.index_block) * c.index_block
    xp = _padded_rows(x, tp)
    hp = _norm1(xp, p, c)
    q, k, v = _qkv(hp[None], p["attn"], c, ropes[0], None)
    with jax.named_scope("dsa_select"):
        k_i = _index_keys(hp[None], p["index"], c, ropes[1], None)[0]
        tiles, scored = _selection(hp, k_i, p["index"], c, ropes[1], t)
    rows = {"k": k[0, :t], "v": v[0, :t], "index": k_i[:t].astype(c.dtype)}
    with jax.named_scope("gqa_selected"):
        heads_first = lambda y: jnp.moveaxis(y[0], 1, 0)
        a = _selected(heads_first(q), heads_first(k), heads_first(v), tiles,
                      c, t)
        out = xp + jnp.dot(jnp.moveaxis(a, 0, 1).reshape(tp, -1),
                           p["attn"]["wo"], preferred_element_type=F32)
    return (out if t == tp else out[:t]), rows, scored


def _stacked(xs: list) -> jax.Array:
    return xs[0][None] if len(xs) == 1 else jnp.stack(xs)


def _attn_prefill(x: jax.Array, p: Params, c: KeyeVL2Config, ropes,
                  cache: Optional[list], at: Tuple[int, int]):
    """x <- x + attn(norm1(x)) over x [B, T, D] from position 0. The
    layer's rows land in rows [0, T) of its two entries `at` of the
    cache, if there is one. Returns (the stream, the entries, the scores
    the indexer computed a sequence)."""
    outs = [_attn_prefill_one(x[b], p, c, ropes) for b in range(x.shape[0])]
    entries = []
    if cache is not None:
        def put(slab, name):
            new = _stacked([o[1][name] for o in outs]).astype(slab.dtype)
            return jax.lax.dynamic_update_slice(slab, new, (0,) * slab.ndim)

        pairs, index = cache[at[0]], cache[at[1]]
        entries = [{"k": put(pairs["k"], "k"), "v": put(pairs["v"], "v")},
                   {"k": put(index["k"], "index")}]
    return _stacked([o[0] for o in outs]), entries, outs[0][2]


def _attn_decode(h: jax.Array, p: Params, c: KeyeVL2Config, ropes,
                 cache: list, at: Tuple[int, int], positions: jax.Array):
    """One token a slot, h [B, 1, D] at `positions` [B, 1]: its key, value
    and index key are written at its position, the indexer scores the
    slab's index keys (as far as the furthest slot stands:
    `dsa.tick_rows`) and picks, and the heads attend the picked rows of
    keys and values, gathered."""
    b = h.shape[0]
    q, k, v = _qkv(h, p["attn"], c, ropes[0], positions)
    slot = jnp.arange(b)[:, None]
    pairs, index = cache[at[0]], cache[at[1]]["k"]
    ck = pairs["k"].at[slot, positions].set(k.astype(pairs["k"].dtype))
    cv = pairs["v"].at[slot, positions].set(v.astype(pairs["v"].dtype))
    q_i, w = _index_inputs(h, p["index"], c, ropes[1], positions)
    k_i = _index_keys(h, p["index"], c, ropes[1], positions)
    # a slot at a time, where the entry lies: a scatter into the 64-wide
    # entry is laid out anew, the whole slab copied there and back (0.17
    # ms a layer, 1 of a tick's 7.2: the chip, PERF.md PR 49)
    for s in range(b):
        index = jax.lax.dynamic_update_slice(
            index, k_i[s:s + 1].astype(index.dtype),
            (s, positions[s, 0], 0))
    picked, seen = dsa.tick_selection(
        q_i[:, 0], index, w[:, 0], positions[:, 0], c.index_topk,
        dsa.tick_rows(index.shape[1], c.index_topk))
    a = dsa.gqa_selected_tick(q[:, 0], ck, cv, picked, seen,
                              c.head_dim ** -0.5)
    out = jnp.dot(a.reshape(b, 1, -1), p["attn"]["wo"],
                  preferred_element_type=F32)
    return out, [{"k": ck, "v": cv}, {"k": index}]


# ------------------------------------------------------- the expert layer

def expert_layer(h32: jax.Array, valid: jax.Array, p: Params,
                 c: KeyeVL2Config) -> Tuple[jax.Array, jax.Array]:
    """h32 [T, D] float32, valid [T] bool (a padded row routes nowhere) ->
    (the layer's output [T, D] float32, the rows each held expert got
    [held] int32). The router reads h32 itself; the experts read it in the
    weights' type."""
    chosen, weights = softmax_topk_route(h32, p["router"],
                                         c.num_experts_per_tok)
    chosen = jnp.where(valid[:, None], chosen, c.num_experts)
    out, counts = held_experts(h32.astype(c.dtype), chosen, weights,
                               p["w1"], p["w2"], c.first_expert, _swiglu)
    return out, counts["sizes"]


def _ffn(x: jax.Array, p: Params, c: KeyeVL2Config):
    """x <- x + experts(norm2(x)), in blocks of `ffn_block` tokens; the
    rows each held expert got over all of them."""
    lead, d = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, d)
    n = flat.shape[0]
    block = min(c.ffn_block, n)
    pad = -n % block
    valid = jnp.arange(n + pad) < n
    flat = jnp.pad(flat, ((0, pad), (0, 0)))

    def one(xb, ok):
        with jax.named_scope("moe"):
            h32 = rms_norm(xb, p["norm2"]["scale"], c.norm_eps)
            y, sizes = expert_layer(h32, ok, p["moe"], c)
            return xb + y, sizes

    if n + pad == block:
        out, sizes = one(flat, valid)
    else:
        def step(i, carry):     # a block where the stream lies
            out, sizes = carry
            at = i * block
            new, rows = one(
                jax.lax.dynamic_slice_in_dim(out, at, block, 0),
                jax.lax.dynamic_slice_in_dim(valid, at, block, 0))
            return (jax.lax.dynamic_update_slice_in_dim(out, new, at, 0),
                    sizes + rows)

        out, sizes = jax.lax.fori_loop(
            0, (n + pad) // block, step,
            (flat, jnp.zeros((c.experts_held,), jnp.int32)))
    return out[:n].reshape(lead + (d,)), sizes


def _head(x: jax.Array, params: Params, c: KeyeVL2Config) -> jax.Array:
    with jax.named_scope("head"):
        h = rms_norm(x, params["norm_f"]["scale"], c.norm_eps)
        return jnp.dot(h.astype(c.dtype), params["lm_head"],
                       preferred_element_type=F32)


# ------------------------------------------------------------- the model

def entries_of(c: KeyeVL2Config) -> Tuple[Tuple[int, int], ...]:
    """The cache entries of each layer: its keys and values, its index
    keys."""
    return tuple((2 * i, 2 * i + 1) for i in range(c.num_layers))


def _norm1(x: jax.Array, p: Params, c: KeyeVL2Config) -> jax.Array:
    return rms_norm(x, p["norm1"]["scale"], c.norm_eps).astype(c.dtype)


def _sum_min(upto: jax.Array, cap: int) -> jax.Array:
    """sum over n in 1 .. upto of min(n, cap), for each entry."""
    low = jnp.minimum(upto, cap)
    return low * (low + 1) // 2 + (upto - low) * cap


def _expert_counts(sizes: list, c: KeyeVL2Config) -> Dict[str, jax.Array]:
    """`held_counts` and, beside the fullest expert's rows, the mean
    expert's (whole rows, over the layers and the experts held)."""
    counts = held_counts(sizes)
    return dict(counts, moe_rows_mean=counts["moe_pairs_held"]
                // (len(sizes) * c.experts_held))


def _prefill(params: Params, tokens: jax.Array, c: KeyeVL2Config,
             cache: list | None):
    """tokens [B, T] from position 0 -> (the stream [B, T, D], the new
    cache, the run's counters)."""
    x = params["tok_emb"][tokens].astype(F32)
    t = tokens.shape[1]
    new_cache = list(cache) if cache is not None else None
    sizes, scored, ropes, at = [], 0, _ropes(c), entries_of(c)
    for i, p in enumerate(params["blocks"]):
        x, entries, scored = _attn_prefill(x, p, c, ropes, cache, at[i])
        for j, entry in zip(at[i], entries):
            new_cache[j] = entry
        x, rows = _ffn(x, p, c)
        sizes.append(rows)
    upto = jnp.int32(t)
    return x, new_cache, dict(
        _expert_counts(sizes, c),
        # of ONE sequence and ONE layer (the sums over a long prompt's
        # layers would not fit an int32): the rows the indexer computed a
        # score for, the rows a query could see, the rows it attended;
        # this family keeps no ring
        dsa_rows_scored=jnp.int32(scored),
        dsa_rows_visible=_sum_min(upto, t),
        dsa_rows_selected=_sum_min(upto, c.index_topk),
        ring_rows_read=jnp.int32(0))


def keye_vl2_forward(params: Params, tokens: jax.Array,
                     config: KeyeVL2Config) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache."""
    x, _, _ = _prefill(params, tokens, config, None)
    return _head(x, params, config)


def keye_vl2_loss(params: Params, tokens: jax.Array, targets: jax.Array,
                  config: KeyeVL2Config, remat: bool = False) -> jax.Array:
    fwd = keye_vl2_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def keye_vl2_init_cache(config: KeyeVL2Config, batch_size: int,
                        max_len: int = 0, dtype: Any = None) -> list:
    """`entries_of`: {"k", "v": [B, S, G, d]} and {"k": [B, S,
    index_dim]} a layer: keys and values in pairs beside an index key,
    under one row count."""
    c = config
    rows = max_len or c.max_seq_len
    dtype = dtype or c.dtype
    pairs = (batch_size, rows, c.num_kv_heads, c.head_dim)
    out = []
    for _ in range(c.num_layers):
        out += [{"k": jnp.zeros(pairs, dtype), "v": jnp.zeros(pairs, dtype)},
                {"k": jnp.zeros((batch_size, rows, c.index_dim), dtype)}]
    return out


def keye_vl2_forward_counted(params: Params, tokens: jax.Array,
                             config: KeyeVL2Config, cache: list, pos: Any):
    """tokens [B, T] on top of what the cache holds. T > 1 is a prefill
    FROM POSITION 0 (`pos` must be a concrete 0: the prompt form reads
    the run alone, and a resumed prompt would have to score the rows
    before it too); T == 1 appends one token at scalar position `pos`.
    Returns (logits [B, 1, vocab] float32 of the LAST position, the new
    cache, the counters of the run: the expert layers' as `decode` gives
    them, and of one sequence and one layer `dsa_rows_scored`,
    `dsa_rows_visible`, `dsa_rows_selected`, `ring_rows_read` (0))."""
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
    logits, new_cache, counts = keye_vl2_decode(
        params, tokens[:, 0], c, cache,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    return logits[:, None], new_cache, counts


def keye_vl2_forward_cached(params: Params, tokens: jax.Array,
                            config: KeyeVL2Config, cache: list, pos: Any):
    """`keye_vl2_forward_counted` less its counters: the cache
    protocol's (logits, cache)."""
    return keye_vl2_forward_counted(params, tokens, config, cache, pos)[:2]


def keye_vl2_decode(params: Params, tokens: jax.Array,
                    config: KeyeVL2Config, cache: list, pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache, the
    step's counters for the engine's loop record: the expert layers' and,
    summed over the slots for one layer, the rows the indexer scored
    (the slab's rows as far as the furthest slot stands, `dsa.tick_rows`:
    the scores are taken where the keys lie), could see and picked). There
    is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("this family's decode has no verify form: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]].astype(F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    sizes, ropes, at = [], _ropes(c), entries_of(c)
    for i, p in enumerate(params["blocks"]):
        y, entries = _attn_decode(_norm1(x, p, c), p, c, ropes, cache, at[i],
                                  positions)
        for j, entry in zip(at[i], entries):
            new_cache[j] = entry
        x, rows = _ffn(x + y, p, c)
        sizes.append(rows)
    seen = pos_vec + 1
    upto = dsa.tick_rows(cache[0]["k"].shape[1], c.index_topk)
    return _head(x[:, 0], params, c), new_cache, dict(
        _expert_counts(sizes, c),
        dsa_rows_scored=tokens.shape[0] * jnp.asarray(upto, jnp.int32)[
            dsa.tick_upto(pos_vec, upto)],
        dsa_rows_visible=seen.sum(),
        dsa_rows_selected=jnp.minimum(seen, c.index_topk).sum(),
        ring_rows_read=jnp.int32(0))


def keye_vl2_partition_specs(config: KeyeVL2Config) -> Params:
    """Experts on `ep`; the rest as the Llama path lays a block out."""
    norm = {"scale": P()}
    block = {"norm1": norm, "norm2": norm,
             "attn": {"wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"),
                      "wv": P("fsdp", "tp"), "q_norm": P(), "k_norm": P(),
                      "wo": P("tp", "fsdp")},
             "index": {"w_q": P("fsdp", "tp"), "w_k": P("fsdp", None),
                       "k_norm": {"scale": P(), "bias": P()},
                       "w_w": P("fsdp", None)},
             "moe": {"router": P(),
                     "w1": P("ep", None, "tp"), "w2": P("ep", "tp", None)}}
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "lm_head": P("fsdp", "tp"),
            "blocks": [block for _ in range(config.num_layers)]}


FAMILY = Family(
    config_type=KeyeVL2Config, init=keye_vl2_init, forward=keye_vl2_forward,
    loss=keye_vl2_loss, partition_specs=keye_vl2_partition_specs,
    init_cache=keye_vl2_init_cache, forward_cached=keye_vl2_forward_cached,
    decode=keye_vl2_decode, forward_counted=keye_vl2_forward_counted)
