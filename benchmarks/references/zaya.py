"""ZAYA1 in plain float32, from its published description (the
`model_type` zaya config.json's keys, the catalog's account, and the two
papers of the family: arXiv:2510.04476 for the attention, CCA, and
arXiv:2511.17127 for the router): every layer is an attention sublayer
and an expert sublayer, each behind an RMSNorm (`x = norm(h)`), and each
sublayer's sum is residual-scaled, `h <- (s h + s0) + (o out + o0)`.

  CCA   `q~ = x W_q` (`num_attention_heads x head_dim` wide), `k~ = x
        W_k` (`num_key_value_heads x head_dim`); `u = [q~ ; k~]`.
        Convolution 0, depthwise, causal, `cca_time0` taps: `a_t[c] =
        sum_j w0[j, c] u_{t - (cca_time0 - 1) + j}[c] + b0[c]`, `u` at a
        negative position 0. Convolution 1, grouped by head, causal,
        `cca_time1` taps: `b_t[head] = sum_j a_{t - (cca_time1 - 1) +
        j}[head] W1[head, j] + b1[head]`, `a` at a negative position 0
        (NOT the bias). `b` splits into `q^c` and `k^c`. The query-key
        mean: `q_i = q^c_i + (q~_i + k~_{i // g}) / 2`, `k_j = k^c_j +
        (mean of q~_i over the g heads of group j + k~_j) / 2`. Values: a
        head's first `head_dim / 2` channels are `x_t W_v1`'s, its last
        `x_{t-1} W_v2`'s (0 at t = 0). `q <- sqrt(d) q / |q|`, `k <-
        sqrt(d) k / |k| * tau_j`. Rotary positions (split-half,
        `rope_theta`) over the first `partial_rotary_factor` of each
        head. Causal softmax attention at scale `d^-1/2`, every query
        head beside its group's keys and values; `W_o`. Nothing is
        cached here and nothing carried: a shift along the sequence is a
        shift.
  MoE   `r_l = x W_d + gamma_l r_{l-1}` (`r` of the layer before, 0
        ahead of the first); `p = softmax(MLP(RMSNorm(r_l)))` over
        `num_experts + 1`, the MLP `R -> R` GELU `-> R` GELU `-> E + 1`
        with the exact (erf) GELU; `e = argmax(p + b)`; `e < E`: `out =
        p_e (silu(x G_e) * (x U_e)) D_e`; `e = E`: `out = 0`. `r_l` goes
        on to the next layer.

`weights` renames the program's pytree to the names used here (each
matrix stored [in, out]; the in-projection's four parts and an expert's
gate and up stay side by side as the program packs them; convolution 1's
taps stay side by side along a head's input channels); that is all this
file takes from the program. One layer's attention is one jitted call and
so is its router; a layer's experts are taken ONE expert cast to float32
at a time inside one call (sixteen experts whole would be 800 MB in
float32 beside a serving engine), and the head in blocks of vocabulary
rows (262,272 rows of 2,048 are 2.1 GB in float32).

`conf["reference_without"]` (absent: nothing) is a test's and a probe's
handle, never a cell's: a list of the assumed terms to leave out, each
set to its neutral value ("conv_bias", "qk_mean", "tau", "value_shift",
"residual_scale", "gamma", "router_bias", "skip"), so that a test can
show that each is in the program.

Departures, noted: none from the configuration file's `assumed` (the
residual scales, the form of gamma, the MLP's depth and its GELU, the
skip, the layout of a head's halves, the convolutions' biases and the
epsilon under the L2 norm are assumptions there, the same on both
sides). The program rounds `u` and `a` to the state's type before it
convolves them (`ops/cca.py`: what a carried tail holds); this file
rounds nothing."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _f32, _rms_norm, _rotate_half

VOCAB_BLOCK = 16384
L2_EPS = 1e-12


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        a, r, e = b["attn"], b["router"], b["moe"]
        layers.append({
            "input_norm": b["norm1"]["scale"],
            "post_attention_norm": b["norm2"]["scale"],
            "attn_res": b["res1"], "moe_res": b["res2"],
            "qkv_proj": a["w_in"], "conv0_weight": a["conv0_w"],
            "conv0_bias": a["conv0_b"], "conv1_weight": a["conv1_w"],
            "conv1_bias": a["conv1_b"], "temperature": a["tau"],
            "o_proj": a["wo"],
            "router_down": r["w_down"], "router_gamma": r["gamma"],
            "router_norm": r["norm"],
            "router_mlp": (r["w1"], r["w2"], r["w3"]),
            "router_bias": r["bias"],
            "experts_gate_up_proj": e["w1"], "experts_down_proj": e["w2"]})
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"], "layers": layers}


def _shift(z, n: int):
    """z [T, ...] -> z_{t - n}, zeros ahead of the sequence."""
    if n == 0:
        return z
    return jnp.concatenate([jnp.zeros_like(z[:n]), z[:-n]], 0)


def _partial_rope(x, rotary: int, theta: float):
    """x [T, heads, d]: the first `rotary` channels of every head rotated
    at positions 0 .. T-1, the rest as they are."""
    t = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                          / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = x[..., :rotary]
    return jnp.concatenate(
        [rot * jnp.cos(ang) + _rotate_half(rot) * jnp.sin(ang),
         x[..., rotary:]], -1)


def _unit(x):
    d = x.shape[-1]
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS) \
        * jnp.sqrt(jnp.float32(d))


def _merge(h, out, res, without: tuple):
    if "residual_scale" in without:
        return h + out
    return (h * _f32(res["stream_scale"]) + _f32(res["stream_bias"])
            + out * _f32(res["out_scale"]) + _f32(res["out_bias"]))


def _attention(h, w, geo: tuple, eps: float, without: tuple):
    heads, kv, d, rotary, theta, time0, time1 = geo
    t, g = h.shape[0], heads // kv
    lat_q, lat_k, half = heads * d, kv * d, kv * d // 2
    x = _rms_norm(h, w["input_norm"], eps)
    proj = x @ _f32(w["qkv_proj"])
    q_lat = proj[:, :lat_q].reshape(t, heads, d)
    k_lat = proj[:, lat_q:lat_q + lat_k].reshape(t, kv, d)
    v1 = proj[:, lat_q + lat_k:lat_q + lat_k + half].reshape(t, kv, d // 2)
    v2 = proj[:, lat_q + lat_k + half:].reshape(t, kv, d // 2)
    u = proj[:, :lat_q + lat_k]
    bias = 0.0 if "conv_bias" in without else 1.0
    w0 = _f32(w["conv0_weight"])                            # [time0, C]
    a = sum(w0[j] * _shift(u, time0 - 1 - j) for j in range(time0)) \
        + bias * _f32(w["conv0_bias"])
    a = a.reshape(t, heads + kv, d)
    w1 = _f32(w["conv1_weight"]).reshape(heads + kv, time1, d, d)
    b = sum(jnp.einsum("thc,hcd->thd", _shift(a, time1 - 1 - j), w1[:, j])
            for j in range(time1)) \
        + bias * _f32(w["conv1_bias"]).reshape(heads + kv, d)
    q, k = b[:, :heads], b[:, heads:]
    if "qk_mean" not in without:
        q = q + 0.5 * (q_lat + jnp.repeat(k_lat, g, axis=1))
        k = k + 0.5 * (q_lat.reshape(t, kv, g, d).mean(2) + k_lat)
    tau = 1.0 if "tau" in without else _f32(w["temperature"])[:, None]
    q = _partial_rope(_unit(q), rotary, theta)
    k = _partial_rope(_unit(k) * tau, rotary, theta)
    before = v2 if "value_shift" in without else _shift(v2, 1)
    v = jnp.concatenate([v1, before], -1)                   # [T, kv, d]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return _merge(h, out.reshape(t, heads * d) @ _f32(w["o_proj"]),
                  w["attn_res"], without)


def _route(h, carried, w, eps: float, without: tuple):
    """(the normed stream, per-choice weights [T, E + 1]: p of the ONE
    choice, 0 elsewhere; this layer's router state)."""
    x = _rms_norm(h, w["post_attention_norm"], eps)
    gamma = 0.0 if "gamma" in without else _f32(w["router_gamma"])
    r = x @ _f32(w["router_down"]) + gamma * carried
    z = _rms_norm(r, w["router_norm"], eps)
    *hidden, last = w["router_mlp"]
    for m in hidden:
        z = jax.nn.gelu(z @ _f32(m), approximate=False)
    p = jax.nn.softmax(z @ _f32(last), -1)
    bias = 0.0 if "router_bias" in without else _f32(w["router_bias"])
    if "skip" in without:          # the last choice may not be taken
        bias = bias + jnp.zeros_like(p[0]).at[-1].set(-jnp.inf)
    chosen = jnp.argmax(p + bias, -1)
    return x, jax.nn.one_hot(chosen, p.shape[-1]) * p, r


def _experts(x, gate_up, down, per_choice):
    """sum over the experts of their weight times their SwiGLU of x: ONE
    expert in float32 at a time."""
    inter = down.shape[1]

    def one(e, out):
        gu = _f32(jax.lax.dynamic_index_in_dim(gate_up, e, 0, False))
        dn = _f32(jax.lax.dynamic_index_in_dim(down, e, 0, False))
        mid = jax.nn.silu(x @ gu[:, :inter]) * (x @ gu[:, inter:])
        weight = jax.lax.dynamic_index_in_dim(per_choice, e, 1, True)
        return out + weight * (mid @ dn)

    return jax.lax.fori_loop(0, gate_up.shape[0], one, jnp.zeros_like(x))


def _expert_layer(h, x, w, per_choice, without: tuple):
    out = _experts(x, w["experts_gate_up_proj"], w["experts_down_proj"],
                   per_choice)
    return _merge(h, out, w["moe_res"], without)


def _head_block(x, embed, lo, size: int):
    """`lo` is traced: one program for every block of one size."""
    return x @ _f32(jax.lax.dynamic_slice_in_dim(embed, lo, size, 0)).T


def logits(w: Dict[str, Any], tokens: jax.Array, conf: Dict[str, Any]
           ) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    rope = conf["rope_parameters"]["hybrid"]
    d = int(conf["head_dim"])
    geo = (int(conf["num_attention_heads"]),
           int(conf["num_key_value_heads"]), d,
           int(d * float(rope["partial_rotary_factor"])),
           float(rope["rope_theta"]), int(conf["cca_time0"]),
           int(conf["cca_time1"]))
    without = tuple(conf.get("reference_without", ()))
    attention = jax.jit(_attention, static_argnums=(2, 3, 4))
    route = jax.jit(_route, static_argnums=(3, 4))
    expert_layer = jax.jit(_expert_layer, static_argnums=(4,))
    head = jax.jit(_head_block, static_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        h = _f32(w["embed_tokens"][tokens])
        carried = jnp.zeros((h.shape[0], int(conf["router_hidden_size"])),
                            jnp.float32)
        for layer in w["layers"]:
            h = attention(h, layer, geo, eps, without)
            x, per_choice, carried = route(h, carried, layer, eps, without)
            h = expert_layer(h, x, layer, per_choice, without)
        x = _rms_norm(h, w["norm"], eps)
        vocab = int(conf["vocab_size"])
        return jnp.concatenate(
            [head(x, w["embed_tokens"], v, min(VOCAB_BLOCK, vocab - v))
             for v in range(0, vocab, VOCAB_BLOCK)], -1)
