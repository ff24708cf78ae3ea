"""Plain references: each architecture's forward pass written from its
published equations in float32 `jax.numpy`, with
`default_matmul_precision("highest")`, no kernels, no cache, no batching.
Nothing here is imported from `ray_tpu.models`; the only thing taken from
the program is its weights, renamed by the two `*_weights` adapters.

GPT-2 (Radford et al. 2019; openai-community/gpt2): learned positions,
pre-LayerNorm blocks, fused qkv projection with bias, causal softmax
attention, tanh-approximate GELU ("gelu_new"), tied output head.

Mistral-7B (Jiang et al. 2023; mistralai/Mistral-7B-v0.3): RMSNorm, rotary
positions in the split-half ("rotate_half") layout, grouped-query
attention, SwiGLU feed-forward, untied head, no sliding window in v0.3.

Departure, noted: the norm epsilon is the configuration file's (the
program's `rms_norm` has 1e-6 fixed where Mistral publishes 1e-5).
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(x: Any) -> jax.Array:
    return jnp.asarray(x, F32)


# ------------------------------------------------------------------ GPT-2

def gpt2_weights(params: Any) -> Dict[str, Any]:
    """The program's GPT-2 pytree under the published names."""
    return {
        "wte": params["wte"], "wpe": params["wpe"],
        "ln_f.g": params["ln_f"]["scale"], "ln_f.b": params["ln_f"]["bias"],
        "h": [{
            "ln_1.g": b["ln_1"]["scale"], "ln_1.b": b["ln_1"]["bias"],
            "c_attn.w": b["attn"]["qkv"], "c_attn.b": b["attn"]["qkv_b"],
            "attn.c_proj.w": b["attn"]["proj"],
            "attn.c_proj.b": b["attn"]["proj_b"],
            "ln_2.g": b["ln_2"]["scale"], "ln_2.b": b["ln_2"]["bias"],
            "c_fc.w": b["mlp"]["fc"], "c_fc.b": b["mlp"]["fc_b"],
            "mlp.c_proj.w": b["mlp"]["proj"],
            "mlp.c_proj.b": b["mlp"]["proj_b"],
        } for b in params["blocks"]],
    }


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(g) + _f32(b)


def _causal_attention(q, k, v):
    """q, k, v [T, heads, hd] -> [T, heads, hd]; softmax(q.k/sqrt(hd))."""
    t, _, hd = q.shape
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(F32(hd))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _gpt2_block(x, w, n_head: int, eps: float):
    t, d = x.shape
    h = _layer_norm(x, w["ln_1.g"], w["ln_1.b"], eps)
    qkv = h @ _f32(w["c_attn.w"]) + _f32(w["c_attn.b"])
    q, k, v = (z.reshape(t, n_head, d // n_head)
               for z in jnp.split(qkv, 3, -1))
    a = _causal_attention(q, k, v).reshape(t, d)
    x = x + a @ _f32(w["attn.c_proj.w"]) + _f32(w["attn.c_proj.b"])
    h = _layer_norm(x, w["ln_2.g"], w["ln_2.b"], eps)
    h = _gelu_new(h @ _f32(w["c_fc.w"]) + _f32(w["c_fc.b"]))
    return x + h @ _f32(w["mlp.c_proj.w"]) + _f32(w["mlp.c_proj.b"])


def gpt2_logits(w: Dict[str, Any], tokens: jax.Array, conf: Dict[str, Any]
                ) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["layer_norm_epsilon"])
    block = jax.jit(_gpt2_block, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        x = _f32(w["wte"])[tokens] + _f32(w["wpe"])[:t]
        for layer in w["h"]:
            x = block(x, layer, int(conf["n_head"]), eps)
        x = _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)
        return x @ _f32(w["wte"])[:conf["vocab_size"]].T


# ---------------------------------------------------------------- Mistral

def llama_weights(params: Any) -> Dict[str, Any]:
    """The program's Llama-path pytree under the published names (each
    projection stored [in, out], the transpose of the checkpoint's)."""
    return {
        "embed_tokens": params["tok_emb"], "norm": params["norm_f"]["scale"],
        "lm_head": params["lm_head"],
        "layers": [{
            "input_layernorm": b["attn_norm"]["scale"],
            "q_proj": b["attn"]["wq"], "k_proj": b["attn"]["wk"],
            "v_proj": b["attn"]["wv"], "o_proj": b["attn"]["wo"],
            "post_attention_layernorm": b["ffn_norm"]["scale"],
            "gate_proj": b["mlp"]["w_gate"], "up_proj": b["mlp"]["w_up"],
            "down_proj": b["mlp"]["w_down"],
        } for b in params["blocks"]],
    }


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(g)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([-x2, x1], -1)


def _rope(x, theta: float):
    """x [T, heads, hd]: x*cos + rotate_half(x)*sin at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _llama_block(x, w, n_head: int, n_kv: int, theta: float, eps: float):
    t, d = x.shape
    hd = d // n_head
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, n_head, hd)
    k = (h @ _f32(w["k_proj"])).reshape(t, n_kv, hd)
    v = (h @ _f32(w["v_proj"])).reshape(t, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv, 1)
    v = jnp.repeat(v, n_head // n_kv, 1)
    x = x + _causal_attention(q, k, v).reshape(t, d) @ _f32(w["o_proj"])
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    ff = jax.nn.silu(h @ _f32(w["gate_proj"])) * (h @ _f32(w["up_proj"]))
    return x + ff @ _f32(w["down_proj"])


def llama_logits(w: Dict[str, Any], tokens: jax.Array,
                 conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence). One
    layer is one jitted call, so that at most one layer's weights exist in
    float32 at a time beside the serving engine's memory."""
    eps = float(conf["rms_norm_eps"])
    block = jax.jit(_llama_block, static_argnums=(2, 3, 4, 5))
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for layer in w["layers"]:
            x = block(x, layer, int(conf["num_attention_heads"]),
                      int(conf["num_key_value_heads"]),
                      float(conf["rope_theta"]), eps)
        x = _rms_norm(x, w["norm"], eps)
        return (x @ _f32(w["lm_head"]))[:, :conf["vocab_size"]]


# ------------------------------------------------------------- front ends

def logits(conf: Dict[str, Any], params: Any, tokens: Any) -> jax.Array:
    tokens = jnp.asarray(tokens, jnp.int32)
    if conf["family"] == "gpt2":
        return gpt2_logits(gpt2_weights(params), tokens, conf)
    if conf["family"] == "llama":
        return llama_logits(llama_weights(params), tokens, conf)
    raise ValueError(f"no reference for family {conf['family']!r}")


def mean_loss(conf: Dict[str, Any], params: Any, tokens: Any,
              targets: Any) -> float:
    """Mean next-token cross-entropy over a batch [B, T], one sequence at
    a time (the logits of one sequence are all that is ever held)."""
    total, count = 0.0, 0
    for row, tgt in zip(tokens, targets):
        lp = jax.nn.log_softmax(logits(conf, params, row), -1)
        tgt = jnp.asarray(tgt, jnp.int32)
        total += float(-jnp.sum(jnp.take_along_axis(
            lp, tgt[:, None], -1)))
        count += int(tgt.shape[0])
    return total / count


def score_emitted(conf: Dict[str, Any], params: Any, prompt: List[int],
                  emitted: List[int]) -> List[Dict[str, float]]:
    """For each emitted token: the reference's log-probability of it, and
    its margin (the reference's best logit minus this token's; 0 when the
    reference would have emitted the same token). One full forward pass
    over prompt + emitted[:-1], teacher-forced."""
    seq = list(prompt) + list(emitted[:-1])
    lg = logits(conf, params, seq)[len(prompt) - 1:]
    lp = jax.nn.log_softmax(lg, -1)
    out = []
    for j, tok in enumerate(emitted):
        out.append({"logprob": float(lp[j, tok]),
                    "margin": float(jnp.max(lg[j]) - lg[j, tok])})
    return out
