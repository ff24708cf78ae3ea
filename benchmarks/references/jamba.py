"""Jamba in plain float32, from its published description (Lieber et
al., "Jamba: a hybrid Transformer-Mamba language model", 2024; the keys
of a `model_type` jamba config.json; Mamba: Gu & Dao 2023): every layer
is two residual sublayers behind RMSNorms, `x <- x + mixer(norm(x)); x <-
x + mlp(norm(x))`; layer i's mixer is attention iff `i %
attn_layer_period == attn_layer_offset`, else Mamba.

  Mamba      `[u | z] = h W_in`; u through a causal depthwise convolution
             (kernel `mamba_d_conv`, with bias) and SiLU; `[r | B | C] =
             u W_x` with `mamba_dt_rank` and twice `mamba_d_state`
             columns, each part through an RMSNorm with its own scale;
             `dt = softplus(r W_dt + b_dt)`; `A = -exp(A_log)` [channels,
             states]; per token, here as a plain `lax.scan` over the
             tokens: `S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T`, `y_t
             = S_t C_t + D u_t`; `(y * silu(z)) W_out`.
  attention  `num_attention_heads` query heads over `num_key_value_heads`
             key-value heads, no bias, no positional embedding, causal
             softmax at `1 / sqrt(head size)` under a dense [T, T] mask,
             a head at a time.
  mlp        `(silu(h W_gate) * (h W_up)) W_down`.

then a final RMSNorm and the embedding as the head. One layer is one
jitted call.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out], the convolution [kernel, channels], `A_log`
[states, channels]: the transposes of the checkpoint's; `_mamba` turns
`A_log` to the published [channels, states]). The program keeps its
layers STACKED by run, and a slice of a stack is a copy, 6 GB over the
model: so `weights` hands each layer its run's stack and its index in
it, and the layer is cut out and cast to float32 inside its jitted call:
at most one layer's weights in float32 (0.4 GB) stand beside a serving
engine. That is all this file takes from the program.

Departures, noted: none from the configuration file's `assumed`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _f32, _rms_norm

VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for run in params["runs"]:
        stack = {"input_layernorm": run["norm1"]["scale"],
                 "pre_ff_layernorm": run["norm2"]["scale"],
                 "gate_proj": run["mlp"]["w_gate"],
                 "up_proj": run["mlp"]["w_up"],
                 "down_proj": run["mlp"]["w_down"]}
        if "mamba" in run:
            m = run["mamba"]
            stack.update(in_proj=m["w_in"], conv1d_weight=m["conv_w"],
                         conv1d_bias=m["conv_b"], x_proj=m["w_x"],
                         dt_layernorm=m["norm_dt"],
                         b_layernorm=m["norm_b"], c_layernorm=m["norm_c"],
                         dt_proj_weight=m["w_dt"], dt_proj_bias=m["dt_bias"],
                         A_log=m["A_log"], D=m["D"], out_proj=m["w_out"])
        else:
            a = run["attn"]
            stack.update(q_proj=a["wq"], k_proj=a["wk"], v_proj=a["wv"],
                         o_proj=a["wo"])
        n = stack["input_layernorm"].shape[0]
        layers += [{"stack": stack, "at": j} for j in range(n)]
    return {"embed_tokens": params["tok_emb"],
            "final_layernorm": params["norm_f"]["scale"], "layers": layers}


def _mlp(x, w, eps: float):
    h = _rms_norm(x, w["pre_ff_layernorm"], eps)
    return x + (jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])) \
        @ w["down_proj"]


def _mamba(x, stack, at, rank: int, states: int, kernel: int, eps: float):
    w = jax.tree.map(lambda a: _f32(a[at]), stack)
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    u, z = jnp.split(h @ w["in_proj"], 2, -1)
    padded = jnp.pad(u, ((kernel - 1, 0), (0, 0)))
    u = jax.nn.silu(w["conv1d_bias"] + sum(
        padded[i:i + t] * w["conv1d_weight"][i] for i in range(kernel)))
    r, bm, cm = jnp.split(u @ w["x_proj"], [rank, rank + states], -1)
    r = _rms_norm(r, w["dt_layernorm"], eps)
    bm = _rms_norm(bm, w["b_layernorm"], eps)
    cm = _rms_norm(cm, w["c_layernorm"], eps)
    dt = jax.nn.softplus(r @ w["dt_proj_weight"] + w["dt_proj_bias"])
    a = -jnp.exp(w["A_log"].T)                    # [channels, states]

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (u, dt, bm, cm))
    y = (y + w["D"] * u) * jax.nn.silu(z)
    return _mlp(x + y @ w["out_proj"], w, eps)


def _attention(x, stack, at, n_head: int, n_kv: int, eps: float):
    w = jax.tree.map(lambda a: _f32(a[at]), stack)
    t, d = x.shape
    hd = d // n_head
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ w["q_proj"]).reshape(t, n_head, hd)
    k = (h @ w["k_proj"]).reshape(t, n_kv, hd)
    v = (h @ w["v_proj"]).reshape(t, n_kv, hd)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def head(i):
        g = i // (n_head // n_kv)
        s = q[:, i] @ k[:, g].T / jnp.sqrt(jnp.float32(hd))
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ v[:, g]

    a = jax.lax.map(head, jnp.arange(n_head))     # [heads, T, hd]
    a = jnp.moveaxis(a, 0, 1).reshape(t, n_head * hd)
    return _mlp(x + a @ w["o_proj"], w, eps)


def _head(x, emb, scale, eps: float):
    return _rms_norm(x, scale, eps) @ _f32(emb).T


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    period, offset = conf["attn_layer_period"], conf["attn_layer_offset"]
    mamba = jax.jit(_mamba, static_argnums=(3, 4, 5, 6))
    attention = jax.jit(_attention, static_argnums=(3, 4, 5))
    head = jax.jit(_head, static_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for i, layer in enumerate(w["layers"]):
            if i % period == offset:
                x = attention(x, layer["stack"], layer["at"],
                              int(conf["num_attention_heads"]),
                              int(conf["num_key_value_heads"]), eps)
            else:
                x = mamba(x, layer["stack"], layer["at"],
                          int(conf["mamba_dt_rank"]),
                          int(conf["mamba_d_state"]),
                          int(conf["mamba_d_conv"]), eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [head(x, w["embed_tokens"][v:v + VOCAB_BLOCK],
                  w["final_layernorm"], eps)
             for v in range(0, vocab, VOCAB_BLOCK)], -1)[:, :vocab]
