"""Keye-VL-2.0's language model in plain float32, from its published
description (the `model_type` KeyeVL2 config.json's keys and `sa_config`,
the catalog's account: grouped-query attention 32 over 4 with a
sparse-attention indexer, 128 experts of which 8 a token, no shared
expert): every layer is attention and an expert layer, each behind an
RMSNorm with a residual.

  attn  `q = q_proj(h)` as heads of `head_dim`, `k = k_proj(h)`, `v =
        v_proj(h)` as `num_key_value_heads` heads, no bias; `q_norm` and
        `k_norm`, an RMSNorm over each head's numbers; the MULTIMODAL
        rotary on all of q and k: frequency i of the `head_dim / 2` takes
        its angle from the temporal, height or width position stream by
        `mrope_section` ([16, 24, 24]: the first 16 frequencies from the
        first stream, the next 24 from the second, the last 24 from the
        third), `x cos + rotate_half(x) sin`. The three streams default
        to the text positions 0 .. T-1, where the sectioned form IS the
        plain rotary (`positions` [3, T] gives others: a test's handle,
        never a cell's). Softmax of `q_h . k_{h // group} head_dim^-1/2`
        over the rows the query may see, times v; then o_proj. Nothing is
        cached here.
  sees  of the rows `s <= t`, the `sa_config.topk` of largest `I[t, s] =
        sum_j w[t, j] relu(q_I[t, j] . k_I[s])`, where `q_I = wq(h)` as
        `indexer_num_heads` heads of `indexer_head_dim`, `k_I =
        LayerNorm(wk(h))`, ONE a token, plain rotary (text positions) on
        the first `indexer_rope_dim` numbers of both, `w =
        weights_proj(h) heads^-1/2 dim^-1/2`: dense scores, `top_k`, an
        explicit mask (all of the rows while `t + 1 <= topk`).
  MoE   float32 logits `h gate` over all experts; the
        `num_experts_per_tok` largest, weighed by their softmax over the
        chosen (`norm_topk_prob`); `x += sum_e w_e (silu(h G_e) * (h
        U_e)) D_e`.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out]; gate and up stay side by side as the program
packs them); that is all this file takes from the program. One layer's
attention is one jitted call, in blocks of `HEAD_BLOCK` heads (32 heads'
scores over 4,048 tokens are 2.1 GB in float32); a layer's 128 experts
are taken in groups of `EXPERT_GROUP`, ONE expert cast to float32 at a
time inside the call (a layer's experts whole would be 2.4 GB in float32
beside a serving engine), and the head in blocks of vocabulary rows.

`conf["reference_selection"]` (absent: "topk") is the probe's handle
(`benchmarks/probe_gqa_selection.py`), never a cell's: "dense" lets a
query see every row `s <= t`, "first" the FIRST `topk` rows instead of the
best.

Departures, noted: none from the configuration file's `assumed` (the
QK-norms, the indexer's input, norm and rotated part, the ties of the
selection are assumptions there, the same on both sides)."""
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (_f32, _layer_norm, _rms_norm,
                                          _rotate_half)

HEAD_BLOCK = 8
EXPERT_GROUP = 16
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        a, i, e = b["attn"], b["index"], b["moe"]
        layers.append({
            "input_layernorm": b["norm1"]["scale"],
            "post_attention_layernorm": b["norm2"]["scale"],
            "q_proj": a["wq"], "k_proj": a["wk"], "v_proj": a["wv"],
            "q_norm": a["q_norm"], "k_norm": a["k_norm"],
            "o_proj": a["wo"],
            "indexer_wq": i["w_q"], "indexer_wk": i["w_k"],
            "indexer_k_norm": i["k_norm"]["scale"],
            "indexer_k_norm_bias": i["k_norm"]["bias"],
            "indexer_weights_proj": i["w_w"],
            "gate": e["router"], "experts_gate_up_proj": e["w1"],
            "experts_down_proj": e["w2"]})
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def rotary(x, theta: float, sections: tuple, positions):
    """The sectioned multimodal rotary: x [T, heads, d], positions [3, T]
    (temporal, height, width), `sections` the frequencies each stream
    gives (their sum d / 2)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                        total_repeat_length=d // 2)
    ang = _f32(positions)[stream, :].T * inv[None, :]       # [T, d / 2]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _blocks_of(y, block: int):
    """[T, H, d] -> [H / block, block, T, d]."""
    t, heads, d = y.shape
    return jnp.moveaxis(y, 0, 1).reshape(heads // block, block, t, d)


def selected(h, w, index: tuple, theta: float, eps: float, text, how: str):
    """The rows each query sees, bool [T, T]. `index`: (heads, dim, the
    rotated part, top k); `text` the positions [T]."""
    heads, dim, d_r, top = index
    t = h.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if how == "dense":
        return causal
    if how == "first":
        return causal & (jnp.arange(t)[None, :] < top)
    q = (h @ _f32(w["indexer_wq"])).reshape(t, heads, dim)
    k = _layer_norm(h @ _f32(w["indexer_wk"]), w["indexer_k_norm"],
                    w["indexer_k_norm_bias"], eps)[:, None, :]
    plain = jnp.stack([text])           # one stream, one section
    rot = lambda x: jnp.concatenate(
        [rotary(x[..., :d_r], theta, (d_r // 2,), plain), x[..., d_r:]], -1)
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


def _attention(x, w, positions, geo: tuple, index: tuple, eps: float,
               how: str):
    heads, kv_heads, d, theta, sections = geo
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, heads, d)
    k = (h @ _f32(w["k_proj"])).reshape(t, kv_heads, d)
    v = (h @ _f32(w["v_proj"])).reshape(t, kv_heads, d)
    q = rotary(_rms_norm(q, w["q_norm"], eps), theta, sections, positions)
    k = rotary(_rms_norm(k, w["k_norm"], eps), theta, sections, positions)
    seen = selected(h, w, index, theta, eps, jnp.arange(t), how)
    # every query head beside its own head of keys and values
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)

    def block(args):                      # a block of heads at a time
        q_b, k_b, v_b = args              # [hb, T, d]
        s = jnp.einsum("htd,hsd->hts", q_b, k_b) * d ** -0.5
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, -1), v_b)

    hb = min(HEAD_BLOCK, heads)
    a = jax.lax.map(block, (_blocks_of(q, hb), _blocks_of(k, hb),
                            _blocks_of(v, hb)))
    a = jnp.moveaxis(a.reshape(heads, t, d), 0, 1)
    return x + a.reshape(t, heads * d) @ _f32(w["o_proj"])


def _route(x, w, top_k: int, eps: float):
    """(the normed stream, per-expert weights [T, experts]: 0 where not
    chosen)."""
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    logits = h @ _f32(w["gate"])
    top, chosen = jax.lax.top_k(logits, top_k)
    rows = jnp.arange(h.shape[0])[:, None]
    return h, jnp.zeros_like(logits).at[rows, chosen].set(
        jax.nn.softmax(top, -1))


def _add_experts(x, h, gate_up, down, per_expert, lo, n: int):
    """x + sum over experts lo .. lo + n - 1 of their weight times their
    SwiGLU of h: ONE expert in float32 at a time (`lo` is traced: one
    program for every group of a layer)."""
    inter = down.shape[1]

    def one(e, x):
        gu = _f32(jax.lax.dynamic_index_in_dim(gate_up, lo + e, 0, False))
        dn = _f32(jax.lax.dynamic_index_in_dim(down, lo + e, 0, False))
        mid = jax.nn.silu(h @ gu[:, :inter]) * (h @ gu[:, inter:])
        weight = jax.lax.dynamic_index_in_dim(per_expert, lo + e, 1, True)
        return x + weight * (mid @ dn)

    return jax.lax.fori_loop(0, n, one, x)


def _head_block(x, lm_head, lo, size: int):
    """`lo` is traced: one program for every block of one size."""
    return x @ _f32(jax.lax.dynamic_slice_in_dim(lm_head, lo, size, 1))


def logits(w: Dict[str, Any], tokens: jax.Array, conf: Dict[str, Any],
           positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence).
    `positions` [3, T]: the three position streams of the multimodal
    rotary; None, and in every cell, the text positions on all three."""
    eps = float(conf["rms_norm_eps"])
    sa = conf["sa_config"]
    d = int(conf["head_dim"])
    sections = tuple((conf["rope_scaling"] or {}).get(
        "mrope_section", [d // 2]))
    geo = (int(conf["num_attention_heads"]),
           int(conf["num_key_value_heads"]), d, float(conf["rope_theta"]),
           sections)
    index = (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
             int(conf["indexer_rope_dim"]), int(sa["topk"]))
    how = conf.get("reference_selection", "topk")
    experts = int(conf["num_experts"])
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[0]),
                                     (len(sections), tokens.shape[0]))
    attention = jax.jit(_attention, static_argnums=(3, 4, 5, 6))
    route = jax.jit(_route, static_argnums=(2, 3))
    add_experts = jax.jit(_add_experts, static_argnums=(6,))
    head = jax.jit(_head_block, static_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for layer in w["layers"]:
            x = attention(x, layer, positions, geo, index, eps, how)
            h, per_expert = route(x, layer,
                                  int(conf["num_experts_per_tok"]), eps)
            for lo in range(0, experts, EXPERT_GROUP):
                x = add_experts(x, h, layer["experts_gate_up_proj"],
                                layer["experts_down_proj"], per_expert, lo,
                                min(EXPERT_GROUP, experts - lo))
        x = _rms_norm(x, w["norm"], eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [head(x, w["lm_head"], v, min(VOCAB_BLOCK, vocab - v))
             for v in range(0, vocab, VOCAB_BLOCK)], -1)
