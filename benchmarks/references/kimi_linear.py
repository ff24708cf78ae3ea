"""Kimi-Linear in plain float32, from its published description (the
Kimi Linear report, 2025; `model_type` kimi_linear): every layer is a
mixer and a feed-forward part, each behind an RMSNorm with a residual.
Layer i (1-based) is in `linear_attn_config.kda_layers` or in
`full_attn_layers`; its feed-forward part is the dense SwiGLU for
`i <= first_k_dense_replace`, else the expert layer.

  KDA  `q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h
       W_v))`, each a causal depthwise convolution (no bias); q and k
       L2-normalised per head, q scaled by d_k^-1/2; `g = -exp(A_log)
       softplus((h W_fa) W_fb + dt_bias)` per channel, `beta = sigmoid(h
       W_b)` per head; per head, a token at a time as a plain `lax.scan`
       (no chunks), `S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
       + beta_t k_t v_t^T`, `o_t = S_t^T q_t`; `RMSNorm_head(o) *
       sigmoid((h W_ga) W_gb)`; `W_o`.
  MLA  no rotary embedding: `q = h W_q` as heads of d_n + d_r; `[c, k_r]
       = h W_kva`, `c <- RMSNorm(c)`; `[k_n, v]_h = c W_kvb`, `k_h =
       [k_n,h, k_r]`; causal softmax of `q_h . k_h / sqrt(d_n + d_r)`
       (the expanded form; nothing is absorbed here); `W_o`.
  MoE  `s = sigmoid(h W_r)` over all experts; the `num_experts_per_token`
       largest of `s + b` are chosen; their `s`, renormalised over all of
       them, times `routed_scaling_factor`, weigh them; the chosen
       experts HELD HERE add `w_e (silu(h G_e) * (h U_e)) D_e` (what the
       others would add is left out: the reference is given the
       program's share); plus the shared expert, a SwiGLU.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out] and a convolution [kernel, channels], the
transposes of the checkpoint's; the program's packed matrices are cut
into the published ones); that is all this file takes from the program.
One layer's part is one jitted call, the experts held go through it in
blocks of at most 16 and the head in blocks of vocabulary rows, so that
at most about 0.5 GB of float32 stands beside a serving engine.

Departures, noted: none from the configuration file's `assumed`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _f32, _rms_norm

EXPERT_BLOCK = 16
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        layer = {"input_layernorm": b["norm1"]["scale"],
                 "post_attention_layernorm": b["norm2"]["scale"]}
        if "kda" in b:
            m = b["kda"]
            inner = m["w_out"].shape[0]
            low = m["w_decay"].shape[0]
            cuts = [inner, 2 * inner, 3 * inner, 3 * inner + low,
                    3 * inner + 2 * low]
            q, k, v, fa, ga, bp = jnp.split(m["w_in"], cuts, axis=1)
            cq, ck, cv = jnp.split(m["conv_w"], 3, axis=1)
            layer.update(q_proj=q, k_proj=k, v_proj=v, f_a_proj=fa,
                         g_a_proj=ga, b_proj=bp, q_conv1d=cq, k_conv1d=ck,
                         v_conv1d=cv, f_b_proj=m["w_decay"],
                         dt_bias=m["dt_bias"], A_log=m["A_log"],
                         g_b_proj=m["w_gate"], o_norm=m["norm"],
                         o_proj=m["w_out"])
        else:
            a = b["mla"]
            layer.update(q_proj=a["wq"], kv_a_proj_with_mqa=a["w_kva"],
                         kv_a_layernorm=a["kv_norm"], kv_b_proj=a["w_kvb"],
                         o_proj=a["wo"])
        if "mlp" in b:
            gate, up = jnp.split(b["mlp"]["w1"], 2, axis=1)
            layer.update(gate_proj=gate, up_proj=up,
                         down_proj=b["mlp"]["w2"])
        else:
            e = b["moe"]
            s_gate, s_up = jnp.split(e["s1"], 2, axis=1)
            # the routed experts' gate_proj and up_proj stay side by
            # side as the program packs them ([held, D, 2 I]) and are
            # cut a block at a time in `_experts`: cut here, a second
            # copy of every expert layer (4.2 GB at the cell's size)
            # would stand beside a serving engine
            layer.update(gate=e["router"],
                         e_score_correction_bias=e["router_bias"],
                         experts_gate_up_proj=e["w1"],
                         experts_down_proj=e["w2"],
                         shared_gate_proj=s_gate, shared_up_proj=s_up,
                         shared_down_proj=e["s2"])
        layers.append(layer)
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def _conv_silu(x, w, kernel: int):
    t = x.shape[0]
    padded = jnp.pad(x, ((kernel - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + t] * _f32(w)[i]
                           for i in range(kernel)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(x, w, heads: int, hd: int, kernel: int, eps: float):
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q, k, v = (_conv_silu(h @ _f32(w[n + "_proj"]), w[n + "_conv1d"],
                          kernel).reshape(t, heads, hd) for n in "qkv")
    q, k = _l2(q) / jnp.sqrt(jnp.float32(hd)), _l2(k)
    g = -jnp.exp(_f32(w["A_log"]))[:, None] * jax.nn.softplus(
        (h @ _f32(w["f_a_proj"])) @ _f32(w["f_b_proj"])
        + _f32(w["dt_bias"])).reshape(t, heads, hd)
    beta = jax.nn.sigmoid(h @ _f32(w["b_proj"]))              # [T, H]

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp                 # [H, d] .. [H]
        s = jnp.exp(g_t)[:, :, None] * s              # Diag(alpha) S
        s = s - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum(
            "hk,hkv->hv", k_t, s)[:, None, :]         # (I - b k k^T) .
        s = s + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, hd, hd)),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * _f32(w["o_norm"])
    gate = jax.nn.sigmoid((h @ _f32(w["g_a_proj"])) @ _f32(w["g_b_proj"]))
    return x + (o.reshape(t, heads * hd) * gate) @ _f32(w["o_proj"])


def _mla(x, w, heads: int, rank: int, d_n: int, d_r: int, d_v: int,
         eps: float):
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, heads, d_n + d_r)
    c, k_r = jnp.split(h @ _f32(w["kv_a_proj_with_mqa"]), [rank], -1)
    kv = (_rms_norm(c, w["kv_a_layernorm"], eps)
          @ _f32(w["kv_b_proj"])).reshape(t, heads, d_n + d_v)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r[:, None, :], (t, heads, d_r))],
        -1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d_n + d_r))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., d_n:])
    return x + a.reshape(t, heads * d_v) @ _f32(w["o_proj"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _dense(x, w, eps: float):
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    return x + _swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"])


def _route(x, w, top_k: int, scale: float, normalize: bool, eps: float):
    """(h, per-expert weights [T, all experts]: 0 where not chosen)."""
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    s = jax.nn.sigmoid(h @ _f32(w["gate"]))
    _, chosen = jax.lax.top_k(s + _f32(w["e_score_correction_bias"]),
                              top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    if normalize:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return h, jnp.zeros_like(s).at[rows, chosen].set(picked * scale)


def _experts(h, gate_up, down, weight):
    """sum_e weight[:, e] * (silu(h G_e) * (h U_e)) D_e over one block;
    gate_up [E, D, 2 I] holds G_e and U_e side by side."""
    gate, up = jnp.split(_f32(gate_up), 2, axis=2)
    mid = jax.nn.silu(jnp.einsum("td,edi->eti", h, gate)) \
        * jnp.einsum("td,edi->eti", h, up)
    return jnp.einsum("eti,eid,te->td", mid, _f32(down), weight)


def _moe_close(x, h, routed, w):
    return x + routed + _swiglu(h, w["shared_gate_proj"],
                                w["shared_up_proj"], w["shared_down_proj"])


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    lin = conf["linear_attn_config"]
    held = int(conf["num_experts"])
    first = held * int(conf.get("expert_parallel_rank", 0))
    kda = jax.jit(_kda, static_argnums=(2, 3, 4, 5))
    mla = jax.jit(_mla, static_argnums=(2, 3, 4, 5, 6, 7))
    dense = jax.jit(_dense, static_argnums=(2,))
    route = jax.jit(_route, static_argnums=(2, 3, 4, 5))
    experts, close = jax.jit(_experts), jax.jit(_moe_close)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for i, layer in enumerate(w["layers"], start=1):
            if i in lin["kda_layers"]:
                x = kda(x, layer, int(lin["num_heads"]),
                        int(lin["head_dim"]),
                        int(lin["short_conv_kernel_size"]), eps)
            elif i in lin["full_attn_layers"]:
                x = mla(x, layer, int(conf["num_attention_heads"]),
                        int(conf["kv_lora_rank"]),
                        int(conf["qk_nope_head_dim"]),
                        int(conf["qk_rope_head_dim"]),
                        int(conf["v_head_dim"]), eps)
            else:
                raise ValueError(f"layer {i} is in neither layer list")
            if i <= int(conf["first_k_dense_replace"]):
                x = dense(x, layer, eps)
                continue
            h, per_expert = route(
                x, layer, int(conf["num_experts_per_token"]),
                float(conf["routed_scaling_factor"]),
                bool(conf["moe_renormalize"]), eps)
            routed = jnp.zeros_like(x)
            for e in range(0, held, EXPERT_BLOCK):
                end = min(e + EXPERT_BLOCK, held)
                routed = routed + experts(
                    h, layer["experts_gate_up_proj"][e:end],
                    layer["experts_down_proj"][e:end],
                    per_expert[:, first + e:first + end])
            x = close(x, h, routed, layer)
        x = _rms_norm(x, w["norm"], eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [x @ _f32(w["lm_head"][:, v:v + VOCAB_BLOCK])
             for v in range(0, vocab, VOCAB_BLOCK)], -1)[:, :vocab]
