"""Granite 4.0-H in plain float32, from its published description (the
`model_type` granitemoehybrid config.json's keys, the catalog's account
and the family's public modelling code): with the four multipliers,

    x = embedding_multiplier * E[t]
    x = x + residual_multiplier * mixer(RMSNorm(x))
    x = x + residual_multiplier * (MoE(RMSNorm(x)) + Shared(RMSNorm(x)))
    logits = RMSNorm(x) E^T / logits_scaling      (the head is tied)

in the order of `layer_types`.

  mamba      Mamba-2: `[z | xBC | dt] = h W_in`; xBC through a causal
             depthwise convolution (`mamba_d_conv` taps, with bias) and
             SiLU; `[x | B | C] = xBC`, x as heads, B and C as
             `mamba_n_groups` groups (head h reads group h // (heads /
             groups)); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
             per head `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t
             = S_t C_t + D x_t`, here as a plain `lax.scan` over the
             TOKENS (no chunks); `y * silu(z)` through an RMSNorm over
             each group's channels, with its weight; `W_out`.
  attention  grouped-query, NO positional embedding, scores `(q . k) *
             attention_multiplier` (not `head_dim ** -0.5`) under a dense
             causal mask, softmax in float32; `W_o`.
  MoE        `r = h W_r` over all experts; the `num_experts_per_tok`
             largest logits are chosen and weighed by their softmax over
             the chosen alone; the chosen experts HELD HERE add `w_e
             (silu(h G_e) * (h U_e)) D_e` (what the others would add is
             left out: the reference is given the program's share).
  Shared     `(silu(h G) * (h U)) D` at `shared_intermediate_size`.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out], gate and up side by side as the program packs
them, the gate half first, and the convolution [kernel, channels], the
transposes of the checkpoint's); that is all this file takes from the
program. One layer's mixer is one jitted call, the experts held go
through theirs in blocks of at most 6 and the head in blocks of
vocabulary rows, so that at most about 0.5 GB of float32 weights stands
beside a serving engine.

`conf["reference_without"]` (absent: nothing) is a test's handle, never a
cell's: a list of the multipliers to set to their neutral value
("embedding_multiplier", "residual_multiplier", "logits_scaling": 1;
"attention_multiplier": `head_dim ** -0.5`), so that a test can show
that each is in the program.

Departures, noted: none from the configuration file's `assumed`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _f32, _rms_norm

EXPERT_BLOCK = 6
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        layer = {"input_layernorm": b["norm1"]["scale"],
                 "post_attention_layernorm": b["norm2"]["scale"],
                 "router": b["router"],
                 "experts_input_linear": b["moe"]["w1"],
                 "experts_output_linear": b["moe"]["w2"],
                 "shared_input_linear": b["shared"]["w1"],
                 "shared_output_linear": b["shared"]["w2"]}
        if "mamba" in b:
            m = b["mamba"]
            layer.update(in_proj=m["w_in"], conv1d_weight=m["conv_w"],
                         conv1d_bias=m["conv_b"], dt_bias=m["dt_bias"],
                         A_log=m["A_log"], D=m["D"], mamba_norm=m["norm"],
                         out_proj=m["w_out"])
        else:
            a = b["attn"]
            layer.update(q_proj=a["wq"], k_proj=a["wk"], v_proj=a["wv"],
                         o_proj=a["wo"])
        layers.append(layer)
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"], "layers": layers}


def _swiglu(x):
    gate, up = jnp.split(x, 2, -1)
    return jax.nn.silu(gate) * up


def _mamba(x, w, heads: int, head_dim: int, groups: int, state: int,
           kernel: int, eps: float, residual: float):
    t = x.shape[0]
    inner, gn = heads * head_dim, groups * state
    h = _rms_norm(x, w["input_layernorm"], eps)
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
    y = y.reshape(t, inner) * _f32(w["mamba_norm"])
    return x + residual * (y @ _f32(w["out_proj"]))


def _attention(x, w, n_head: int, n_kv: int, hd: int, eps: float,
               scale: float, residual: float):
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, n_head, hd)
    k = (h @ _f32(w["k_proj"])).reshape(t, n_kv, hd)
    v = (h @ _f32(w["v_proj"])).reshape(t, n_kv, hd)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def group(qkv):
        # one key-value head and its query heads at a time: the dense
        # [heads, T, T] scores of all of them are 1.2 GB at 3,047 tokens
        qg, kg, vg = qkv
        s = jnp.einsum("trd,sd->rts", qg, kg) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("rts,sd->trd", jax.nn.softmax(s, -1), vg)

    rep = n_head // n_kv
    a = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(t, n_kv, rep, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    a = jnp.moveaxis(a, 0, 1).reshape(t, n_head * hd)
    return x + residual * (a @ _f32(w["o_proj"]))


def _route(x, w, top_k: int, eps: float):
    """(h, per-expert weights [T, all experts]: 0 where not chosen)."""
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    r = h @ _f32(w["router"])
    top, chosen = jax.lax.top_k(r, top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    return h, jnp.zeros_like(r).at[rows, chosen].set(
        jax.nn.softmax(top, -1))


def _experts(h, w_in, w_out, weight):
    """sum_e weight[:, e] * (silu(h G_e) * (h U_e)) D_e over one block."""
    mid = _swiglu(jnp.einsum("td,edi->eti", h, _f32(w_in)))
    return jnp.einsum("eti,eid,te->td", mid, _f32(w_out), weight)


def _ffn_close(x, h, routed, w, residual: float):
    shared = _swiglu(h @ _f32(w["shared_input_linear"])) \
        @ _f32(w["shared_output_linear"])
    return x + residual * (routed + shared)


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    hd = int(conf["head_dim"])
    without = set(conf.get("reference_without") or ())
    mult = {k: 1.0 if k in without else float(conf[k]) for k in (
        "embedding_multiplier", "residual_multiplier", "logits_scaling")}
    scale = hd ** -0.5 if "attention_multiplier" in without \
        else float(conf["attention_multiplier"])
    res = mult["residual_multiplier"]
    held = int(conf["num_local_experts"])
    first = held * int(conf.get("expert_parallel_rank", 0))
    mamba = jax.jit(_mamba, static_argnums=(2, 3, 4, 5, 6, 7, 8))
    attention = jax.jit(_attention, static_argnums=(2, 3, 4, 5, 6, 7))
    route = jax.jit(_route, static_argnums=(2, 3))
    experts = jax.jit(_experts)
    close = jax.jit(_ffn_close, static_argnums=(4,))
    with jax.default_matmul_precision("highest"):
        x = mult["embedding_multiplier"] * _f32(w["embed_tokens"][tokens])
        for kind, layer in zip(conf["layer_types"], w["layers"]):
            if kind == "mamba":
                x = mamba(x, layer, int(conf["mamba_n_heads"]),
                          int(conf["mamba_d_head"]),
                          int(conf["mamba_n_groups"]),
                          int(conf["mamba_d_state"]),
                          int(conf["mamba_d_conv"]), eps, res)
            else:
                x = attention(x, layer, int(conf["num_attention_heads"]),
                              int(conf["num_key_value_heads"]), hd, eps,
                              scale, res)
            h, per_expert = route(x, layer,
                                  int(conf["num_experts_per_tok"]), eps)
            routed = jnp.zeros_like(x)
            for e in range(0, held, EXPERT_BLOCK):
                end = min(e + EXPERT_BLOCK, held)
                routed = routed + experts(
                    h, layer["experts_input_linear"][e:end],
                    layer["experts_output_linear"][e:end],
                    per_expert[:, first + e:first + end])
            x = close(x, h, routed, layer, res)
        x = _rms_norm(x, w["norm"], eps)
        vocab = int(conf["vocab_size"])
        table = w["embed_tokens"]
        return jnp.concatenate(
            [x @ _f32(table[v:v + VOCAB_BLOCK]).T
             for v in range(0, vocab, VOCAB_BLOCK)], -1)[:, :vocab] \
            / mult["logits_scaling"]
