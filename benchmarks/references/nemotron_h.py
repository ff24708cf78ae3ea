"""Nemotron-H in plain float32, from its published description (NVIDIA
Nemotron-H and Nemotron-3 reports; `model_type` nemotron_h): every layer
is one mixer behind an RMSNorm with a residual, `x <- x + mixer(norm(x))`,
in the order of `hybrid_override_pattern`.

  M  Mamba-2: `[z | xBC | dt] = h W_in`; xBC through a causal depthwise
     convolution (kernel `conv_kernel`, with bias) and SiLU; `[x | B | C]
     = xBC`, x as heads, B and C as `n_groups` groups (head h reads group
     h // (heads / groups)); `dt = softplus(dt + dt_bias)`, `A =
     -exp(A_log)`; per head `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`,
     `y_t = S_t C_t + D x_t`, here as a plain `lax.scan` over the tokens
     (no chunks); `y * silu(z)` through an RMSNorm over each group's
     channels, with its weight; `W_out`.
  *  grouped-query attention, causal softmax at 1/sqrt(head_dim), no
     positional embedding.
  E  LatentMoE: `s = sigmoid(h W_r)` over all experts; the
     `num_experts_per_tok` largest of `s + b` are chosen; their `s`,
     normalised over all of them, times `routed_scaling_factor`, weigh
     them; `u = h W_down`; the chosen experts HELD HERE add `w_e
     relu(u W1_e)^2 W2_e` (what the others would add is left out: the
     reference is given the program's share); `W_up`; plus one shared
     expert `relu(h S1)^2 S2` at full width.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out] and the convolution [kernel, channels], the
transposes of the checkpoint's); that is all this file takes from the
program. One layer is one jitted call, the experts held go through it in
blocks of at most 16 and the head in blocks of vocabulary rows, so that
at most about 0.5 GB of float32 stands beside a serving engine.

Departures, noted: no rotary embedding in the attention layers though the
config holds `rope_theta` (the configuration file's `assumed`)."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _causal_attention, _f32, _rms_norm

EXPERT_BLOCK = 16
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        layer = {"norm": b["norm"]["scale"]}
        if "mamba" in b:
            m = b["mamba"]
            layer.update(in_proj=m["w_in"], conv1d_weight=m["conv_w"],
                         conv1d_bias=m["conv_b"], dt_bias=m["dt_bias"],
                         A_log=m["A_log"], D=m["D"], mixer_norm=m["norm"],
                         out_proj=m["w_out"])
        elif "attn" in b:
            a = b["attn"]
            layer.update(q_proj=a["wq"], k_proj=a["wk"], v_proj=a["wv"],
                         o_proj=a["wo"])
        else:
            e = b["moe"]
            layer.update(gate=e["router"],
                         e_score_correction_bias=e["router_bias"],
                         fc1_latent_proj=e["w_down"],
                         fc2_latent_proj=e["w_up"],
                         experts_up_proj=e["w1"],
                         experts_down_proj=e["w2"],
                         shared_up_proj=e["s1"], shared_down_proj=e["s2"])
        layers.append(layer)
    return {"embeddings": params["tok_emb"],
            "norm_f": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mamba(x, w, heads: int, head_dim: int, groups: int, state: int,
           kernel: int, eps: float):
    t = x.shape[0]
    inner, gn = heads * head_dim, groups * state
    h = _rms_norm(x, w["norm"], eps)
    z, xbc, dt = jnp.split(h @ _f32(w["in_proj"]),
                           [inner, 2 * inner + 2 * gn], -1)
    padded = jnp.pad(xbc, ((kernel - 1, 0), (0, 0)))
    conv = _f32(w["conv1d_bias"]) + sum(
        padded[i:i + t] * _f32(w["conv1d_weight"])[i]
        for i in range(kernel))
    xs, bm, cm = jnp.split(jax.nn.silu(conv), [inner, inner + gn], -1)
    xs = xs.reshape(t, heads, head_dim)
    per = heads // groups
    bm = jnp.repeat(bm.reshape(t, groups, state), per, 1)
    cm = jnp.repeat(cm.reshape(t, groups, state), per, 1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    a = -jnp.exp(_f32(w["A_log"]))

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state)),
                        (xs, bm, cm, dt))
    y = (y + _f32(w["D"])[:, None] * xs).reshape(t, inner)
    y = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(t, inner) * _f32(w["mixer_norm"])
    return x + y @ _f32(w["out_proj"])


def _attention(x, w, n_head: int, n_kv: int, hd: int, eps: float):
    t = x.shape[0]
    h = _rms_norm(x, w["norm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, n_head, hd)
    k = (h @ _f32(w["k_proj"])).reshape(t, n_kv, hd)
    v = (h @ _f32(w["v_proj"])).reshape(t, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, 1)
    v = jnp.repeat(v, n_head // n_kv, 1)
    return x + _causal_attention(q, k, v).reshape(t, n_head * hd) \
        @ _f32(w["o_proj"])


def _route(x, w, top_k: int, scale: float, normalize: bool, eps: float):
    """(h, u, per-expert weights [T, all experts]: 0 where not chosen)."""
    h = _rms_norm(x, w["norm"], eps)
    s = jax.nn.sigmoid(h @ _f32(w["gate"]))
    _, chosen = jax.lax.top_k(s + _f32(w["e_score_correction_bias"]),
                              top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    if normalize:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    per_expert = jnp.zeros_like(s).at[rows, chosen].set(picked * scale)
    return h, h @ _f32(w["fc1_latent_proj"]), per_expert


def _experts(u, up, down, weight):
    """sum_e weight[:, e] * relu(u up_e)^2 down_e over one block."""
    mid = _relu2(jnp.einsum("tl,eli->eti", u, _f32(up)))
    return jnp.einsum("eti,eil,te->tl", mid, _f32(down), weight)


def _moe_close(x, h, routed, w):
    shared = _relu2(h @ _f32(w["shared_up_proj"])) \
        @ _f32(w["shared_down_proj"])
    return x + routed @ _f32(w["fc2_latent_proj"]) + shared


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["norm_eps"])
    held = int(conf["n_routed_experts"])
    first = held * int(conf.get("expert_parallel_rank", 0))
    mamba = jax.jit(_mamba, static_argnums=(2, 3, 4, 5, 6, 7))
    attention = jax.jit(_attention, static_argnums=(2, 3, 4, 5))
    route = jax.jit(_route, static_argnums=(2, 3, 4, 5))
    experts, close = jax.jit(_experts), jax.jit(_moe_close)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embeddings"][tokens])
        for kind, layer in zip(conf["hybrid_override_pattern"],
                               w["layers"]):
            if kind == "M":
                x = mamba(x, layer, int(conf["mamba_num_heads"]),
                          int(conf["mamba_head_dim"]),
                          int(conf["n_groups"]),
                          int(conf["ssm_state_size"]),
                          int(conf["conv_kernel"]), eps)
            elif kind == "*":
                x = attention(x, layer, int(conf["num_attention_heads"]),
                              int(conf["num_key_value_heads"]),
                              int(conf["head_dim"]), eps)
            else:
                h, u, per_expert = route(
                    x, layer, int(conf["num_experts_per_tok"]),
                    float(conf["routed_scaling_factor"]),
                    bool(conf["norm_topk_prob"]), eps)
                routed = jnp.zeros_like(u)
                for e in range(0, held, EXPERT_BLOCK):
                    end = min(e + EXPERT_BLOCK, held)
                    routed = routed + experts(
                        u, layer["experts_up_proj"][e:end],
                        layer["experts_down_proj"][e:end],
                        per_expert[:, first + e:first + end])
                x = close(x, h, routed, layer)
        x = _rms_norm(x, w["norm_f"], eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [x @ _f32(w["lm_head"][:, v:v + VOCAB_BLOCK])
             for v in range(0, vocab, VOCAB_BLOCK)], -1)[:, :vocab]
