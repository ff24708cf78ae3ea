"""dots3-note's language model in plain float32, from its published
description (the `model_type` dots3_note config.json, its `layer_types`
and `swa_*` keys, and the catalog's account: latent attention with a
sparse-attention indexer in the full layers, a window of 513 with its own
low-rank latent attention in the sliding layers, a headwise gate): every
layer is latent attention and a feed-forward part, each behind an RMSNorm
with a residual; the feed-forward part of layer i (0-based) is the dense
SwiGLU for `i < first_k_dense_replace`, else the expert layer.

  MLA   at the layer kind's own sizes: `c_q = s_q RMSNorm(q_a(h))`, `q =
        q_b(c_q)` as heads of `[q_n | q_pe]`; `[c | k_pe] = kv_a(h)`, `c
        <- s_kv RMSNorm(c)`, `s = (hidden / rank)^1/2`
        (`apply_mla_qkv_lora_rescale`); `[k_n | v]_h = kv_b(c)`; q_pe of
        every head and the ONE k_pe take rotary positions (`x cos +
        rotate_half(x) sin`, no scaling); `k_h = [k_n,h | k_pe]`; softmax
        of `q_h . k_h (d_n + d_r)^-1/2` over the rows the layer may see,
        times v; each head's output times `g_h = sigmoid(gate(h))_h`;
        then o_proj. Nothing is absorbed and nothing is cached here.
  full  a query sees, of the rows `s <= t`, the `index_topk` of largest
        `I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`, where `q_I =
        wq_b(c_q)` as `index_n_heads` heads of `index_head_dim`, `k_I =
        LayerNorm(wk(h))`, ONE a token, rotary on the first
        `qk_rope_head_dim` numbers of both, `w = weights_proj(h)
        heads^-1/2 dim^-1/2`: dense scores, `top_k`, an explicit mask
        (all of the rows while `t + 1 <= index_topk`).
  sliding a query sees `t - sliding_window_size < s <= t`.
  MoE   `s = sigmoid(h W_g)` over all experts in float32; the
        `num_experts_per_tok` largest of `s + bias` are chosen (no
        groups); they weigh `s` over the sum of the chosen `s`
        (`norm_topk_prob`), times `routed_scaling_factor`; the chosen
        experts HELD HERE add `w_e (silu(h G_e) * (h U_e)) D_e` (what the
        others would add is left out: the reference is given the
        program's share); plus the shared expert.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out]; gate and up stay side by side as the program
packs them); that is all this file takes from the program. One layer's
part is one jitted call; attention goes in blocks of `HEAD_BLOCK` heads
(128 heads' scores over 4,048 tokens are 8.4 GB in float32) and the
indexer's scores in blocks of as many index heads, of an expert layer ONE
expert is cast to float32 at a time, the dense part goes through in
slices of its width and the head in blocks of vocabulary rows, each slice
cut and cast INSIDE its call, so that at most about 1 GB of float32
stands beside a serving engine.

`conf["reference_selection"]` (absent: "topk") is the probe's handle
(`benchmarks/probe_dsa_selection.py`), never a cell's: "dense" lets a full
layer's query see every row `s <= t`, "first" the FIRST `index_topk` rows
instead of the best: the two readings of what the cell's limits tell
apart that belong to this model.

Departures, noted: none from the configuration file's `assumed`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (_f32, _layer_norm, _rms_norm,
                                          _rotate_half)

HEAD_BLOCK = 8
WIDTH_BLOCK = 3072
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        a = b["attn"]
        layer = {"input_layernorm": b["norm1"]["scale"],
                 "post_attention_layernorm": b["norm2"]["scale"],
                 "q_a_proj": a["w_qa"], "q_a_layernorm": a["q_norm"],
                 "q_b_proj": a["w_qb"],
                 "kv_a_proj_with_mqa": a["w_kva"],
                 "kv_a_layernorm": a["kv_norm"], "kv_b_proj": a["w_kvb"],
                 "gate_proj": a["w_g"], "o_proj": a["wo"]}
        if "index" in b:
            i = b["index"]
            layer.update(indexer_wq_b=i["w_q"], indexer_wk=i["w_k"],
                         indexer_k_norm=i["k_norm"]["scale"],
                         indexer_k_norm_bias=i["k_norm"]["bias"],
                         indexer_weights_proj=i["w_w"])
        if "mlp" in b:
            layer.update(gate_up_proj=b["mlp"]["w1"],
                         down_proj=b["mlp"]["w2"])
        else:
            e = b["moe"]
            layer.update(gate=e["router"],
                         e_score_correction_bias=e["router_bias"],
                         experts_gate_up_proj=e["w1"],
                         experts_down_proj=e["w2"],
                         shared_gate_up_proj=e["s1"],
                         shared_down_proj=e["s2"])
        layers.append(layer)
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def geometry(conf: Dict[str, Any], kind: str) -> tuple:
    """(heads, kv rank, d_n, d_r, d_v, theta, window or 0) of a layer
    kind, hashable: a jitted call's static argument."""
    if kind == "full_attention":
        return (int(conf["num_attention_heads"]), int(conf["kv_lora_rank"]),
                int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"]),
                int(conf["v_head_dim"]), float(conf["rope_theta"]), 0)
    return (int(conf["swa_num_attention_heads"]),
            int(conf["swa_kv_lora_rank"]), int(conf["swa_qk_nope_head_dim"]),
            int(conf["swa_qk_rope_head_dim"]), int(conf["swa_v_head_dim"]),
            float(conf["swa_rope_theta"]), int(conf["sliding_window_size"]))


def _rotary(x, theta: float):
    """x [T, heads, d] at positions 0 .. T-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _blocks_of(y, block: int):
    """[T, H, d] -> [H / block, block, T, d]."""
    t, heads, d = y.shape
    return jnp.moveaxis(y, 0, 1).reshape(heads // block, block, t, d)


def selected(h, c_q, w, index: tuple, theta: float, d_r: int, eps: float,
             how: str):
    """The rows each query of a full layer sees, bool [T, T]. `index`:
    (heads, dim, top k)."""
    heads, dim, top = index
    t = h.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if how == "dense":
        return causal
    if how == "first":
        return causal & (jnp.arange(t)[None, :] < top)
    q = (c_q @ _f32(w["indexer_wq_b"])).reshape(t, heads, dim)
    k = _layer_norm(h @ _f32(w["indexer_wk"]), w["indexer_k_norm"],
                    w["indexer_k_norm_bias"], eps)[:, None, :]
    rot = lambda x: jnp.concatenate(
        [_rotary(x[..., :d_r], theta), x[..., d_r:]], -1)
    q, k = rot(q), rot(k)[:, 0]
    per_head = (h @ _f32(w["indexer_weights_proj"])) \
        * (heads ** -0.5 * dim ** -0.5)                     # [T, heads]
    hb = min(HEAD_BLOCK, heads)

    def block(args):                  # a block of index heads at a time
        q_b, w_b = args               # [hb, T, dim], [hb, T]
        s = jax.nn.relu(jnp.einsum("htd,sd->hts", q_b, k))
        return (s * w_b[..., None]).sum(0)

    scores = jax.lax.map(block, (
        _blocks_of(q, hb), per_head.T.reshape(heads // hb, hb, t))).sum(0)
    scores = jnp.where(causal, scores, -jnp.inf)
    _, best = jax.lax.top_k(scores, min(top, t))
    picked = jnp.zeros((t, t), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    return picked & causal


def _attention(x, w, d_model: int, geo: tuple, index: tuple, rescale: bool,
               eps: float, how: str):
    heads, rank, d_n, d_r, d_v, theta, window = geo
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    c_q = _rms_norm(h @ _f32(w["q_a_proj"]), w["q_a_layernorm"], eps)
    c, k_pe = jnp.split(h @ _f32(w["kv_a_proj_with_mqa"]), [rank], -1)
    c = _rms_norm(c, w["kv_a_layernorm"], eps)
    if rescale:
        c_q = c_q * (d_model / c_q.shape[-1]) ** 0.5
        c = c * (d_model / rank) ** 0.5
    q = (c_q @ _f32(w["q_b_proj"])).reshape(t, heads, d_n + d_r)
    kv = (c @ _f32(w["kv_b_proj"])).reshape(t, heads, d_n + d_v)
    q_pe = _rotary(q[..., d_n:], theta)
    k_pe = _rotary(k_pe[:, None, :], theta)[:, 0]
    if window:
        at = jnp.arange(t)
        seen = (at[None, :] <= at[:, None]) \
            & (at[:, None] - at[None, :] < window)
    else:
        seen = selected(h, c_q, w, index, theta, d_r, eps, how)
    scale = (d_n + d_r) ** -0.5

    def block(args):                      # a block of heads at a time
        q_n, q_r, kv_b = args             # [hb, T, .]
        s = (jnp.einsum("htd,hsd->hts", q_n, kv_b[..., :d_n])
             + jnp.einsum("htd,sd->hts", q_r, k_pe)) * scale
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, -1),
                          kv_b[..., d_n:])

    hb = min(HEAD_BLOCK, heads)
    a = jax.lax.map(block, (_blocks_of(q[..., :d_n], hb),
                            _blocks_of(q_pe, hb), _blocks_of(kv, hb)))
    a = jnp.moveaxis(a.reshape(heads, t, d_v), 0, 1)
    a = a * jax.nn.sigmoid(h @ _f32(w["gate_proj"]))[..., None]
    return x + a.reshape(t, heads * d_v) @ _f32(w["o_proj"])


def _swiglu_slice(h, gate_up, down, lo: int, size: int):
    """(silu(h G) * (h U)) D over columns [lo, lo + size) of the width;
    gate_up [D, 2 I] holds G and U side by side."""
    inter = gate_up.shape[-1] // 2
    gate = _f32(jax.lax.dynamic_slice_in_dim(gate_up, lo, size, 1))
    up = _f32(jax.lax.dynamic_slice_in_dim(gate_up, inter + lo, size, 1))
    return (jax.nn.silu(h @ gate) * (h @ up)) \
        @ _f32(jax.lax.dynamic_slice_in_dim(down, lo, size, 0))


def _add_expert(x, h, gate_up, down, per_expert, e, at):
    """x + per_expert[:, at] * expert e's SwiGLU of h: ONE expert in
    float32, and one buffer of x's size a call."""
    return x + per_expert[:, at, None] * _swiglu_slice(
        h, gate_up[e], down[e], 0, down.shape[1])


def _head_block(x, lm_head, lo: int, size: int):
    return x @ _f32(jax.lax.dynamic_slice_in_dim(lm_head, lo, size, 1))


def _norm2(x, w, eps: float):
    return _rms_norm(x, w["post_attention_layernorm"], eps)


def _route(h, gate, bias, top_k: int, normalize: bool, scale: float):
    """Per-expert weights [T, all experts]: 0 where not chosen."""
    s = jax.nn.sigmoid(h @ _f32(gate))
    _, chosen = jax.lax.top_k(s + _f32(bias), top_k)
    rows = jnp.arange(s.shape[0])[:, None]
    picked = s[rows, chosen]
    if normalize:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[rows, chosen].set(picked * scale)


def _sliced(fn, h, gate_up, down):
    """A SwiGLU of any width, in equal slices of at most WIDTH_BLOCK."""
    inter = down.shape[0]
    step = next(n for n in range(min(WIDTH_BLOCK, inter), 0, -1)
                if inter % n == 0)
    return sum(fn(h, gate_up, down, lo, step)
               for lo in range(0, inter, step))


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    held = int(conf["n_routed_experts"])
    first = held * int(conf.get("expert_parallel_rank", 0))
    index = (int(conf["index_n_heads"]), int(conf["index_head_dim"]),
             int(conf["index_topk"]))
    how = conf.get("reference_selection", "topk")
    attention = jax.jit(_attention, static_argnums=tuple(range(2, 8)))
    norm2 = jax.jit(_norm2, static_argnums=(2,))
    part = jax.jit(_swiglu_slice, static_argnums=(3, 4))
    route = jax.jit(_route, static_argnums=(3, 4, 5))
    add_expert = jax.jit(_add_expert)
    head = jax.jit(_head_block, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for i, layer in enumerate(w["layers"]):
            x = attention(x, layer, int(conf["hidden_size"]),
                          geometry(conf, conf["layer_types"][i]), index,
                          bool(conf["apply_mla_qkv_lora_rescale"]), eps,
                          how)
            h = norm2(x, layer, eps)
            if i < int(conf["first_k_dense_replace"]):
                x = x + _sliced(part, h, layer["gate_up_proj"],
                                layer["down_proj"])
                continue
            per_expert = route(
                h, layer["gate"], layer["e_score_correction_bias"],
                int(conf["num_experts_per_tok"]),
                bool(conf["norm_topk_prob"]),
                float(conf["routed_scaling_factor"]))
            for e in range(held):
                x = add_expert(x, h, layer["experts_gate_up_proj"],
                               layer["experts_down_proj"], per_expert, e,
                               first + e)
            x = x + _sliced(part, h, layer["shared_gate_up_proj"],
                            layer["shared_down_proj"])
        x = _rms_norm(x, w["norm"], eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [head(x, w["lm_head"], v, min(VOCAB_BLOCK, vocab - v))
             for v in range(0, vocab, VOCAB_BLOCK)], -1)
