"""DeepSeek-V2 in plain float32, from its published description (the
DeepSeek-V2 report, 2024, and the `model_type` deepseek_v2 modelling code
beside its config.json): every layer is latent attention and a
feed-forward part, each behind an RMSNorm with a residual; the
feed-forward part of layer i (0-based) is the dense SwiGLU for `i <
first_k_dense_replace`, else the expert layer.

  MLA   `q = q_b(RMSNorm(q_a(h)))` as heads of `[q_n | q_pe]` (d_n + d_r);
        `[c | k_pe] = kv_a(h)`, `c <- RMSNorm(c)`; `[k_n | v]_h = kv_b(c)`;
        q_pe of every head and the ONE k_pe take rotary positions (`x cos
        + rotate_half(x) sin`, the halves as the weights give them: the
        configuration file's `assumed` says why the published
        de-interleaving is left out); `k_h = [k_n,h | k_pe]`; causal
        softmax of `q_h . k_h * scale`, times v, then o_proj. Nothing is
        absorbed and nothing is cached here.
  YaRN  over the d_r / 2 frequencies `f_i = theta^(-2i / d_r)`: `cd(r) =
        d_r ln(original / (2 pi r)) / (2 ln theta)`, `low =
        floor(cd(beta_fast))`, `high = ceil(cd(beta_slow))`, `ramp_i =
        clip((i - low) / (high - low), 0, 1)`, `inv_freq_i = f_i (1 -
        ramp_i) + (f_i / factor) ramp_i`; cos and sin times `m(mscale) /
        m(mscale_all_dim)`, `m(a) = 0.1 a ln(factor) + 1`; `scale = (d_n +
        d_r)^-1/2 m(mscale_all_dim)^2`.
  MoE   `s = softmax(h W_g)` over all experts in float32; the experts
        stand in `n_group` groups, a group's score is its largest `s`,
        the `topk_group` best groups are kept and the
        `num_experts_per_tok` largest `s` inside them chosen; they weigh
        `s * routed_scaling_factor`, NOT renormalised; the chosen experts
        HELD HERE add `w_e (silu(h G_e) * (h U_e)) D_e` (what the others
        would add is left out: the reference is given the program's
        share); plus the shared experts, one SwiGLU of their summed
        width.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out], the transposes of the checkpoint's; gate and up
stay side by side as the program packs them and are cut where they are
used); that is all this file takes from the program. One layer's part is
one jitted call; of an expert layer ONE expert is cast to float32 at a
time, the dense part goes through in slices of its width, attention in
blocks of heads and the head in blocks of vocabulary rows, each slice cut
and cast INSIDE its call (cut outside, the calls are dispatched ahead of
the chip and every block's copy stands at once: 3.5 GB for the head, my
chip run, PR 33), so that at most about 1 GB of float32 stands beside a
serving engine.

Departures, noted: none from the configuration file's `assumed`."""
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import _f32, _rms_norm, _rotate_half

HEAD_BLOCK = 16
WIDTH_BLOCK = 3072
VOCAB_BLOCK = 16384


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        a = b["mla"]
        layer = {"input_layernorm": b["norm1"]["scale"],
                 "post_attention_layernorm": b["norm2"]["scale"],
                 "q_a_proj": a["w_qa"], "q_a_layernorm": a["q_norm"],
                 "q_b_proj": a["w_qb"],
                 "kv_a_proj_with_mqa": a["w_kva"],
                 "kv_a_layernorm": a["kv_norm"], "kv_b_proj": a["w_kvb"],
                 "o_proj": a["wo"]}
        if "mlp" in b:
            layer.update(gate_up_proj=b["mlp"]["w1"],
                         down_proj=b["mlp"]["w2"])
        else:
            e = b["moe"]
            layer.update(gate=e["router"],
                         experts_gate_up_proj=e["w1"],
                         experts_down_proj=e["w2"],
                         shared_gate_up_proj=e["s1"],
                         shared_down_proj=e["s2"])
        layers.append(layer)
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def yarn(conf: Dict[str, Any]):
    """(inv_freq [d_r / 2] as a list, the factor on cos and sin, the
    softmax scale), from the configuration's numbers by the equations
    above, in Python floats."""
    d = int(conf["qk_rope_head_dim"])
    theta = float(conf["rope_theta"])
    rs = conf["rope_scaling"]
    factor = float(rs["factor"])
    original = float(rs["original_max_position_embeddings"])

    def cd(rotations: float) -> float:
        return d * math.log(original / (2.0 * math.pi * rotations)) \
            / (2.0 * math.log(theta))

    def m(a: float) -> float:
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1.0 else 1.0

    low = max(math.floor(cd(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(cd(float(rs["beta_slow"]))), d - 1)
    inv = []
    for i in range(d // 2):
        f = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        inv.append(f * (1.0 - ramp) + f / factor * ramp)
    width = int(conf["qk_nope_head_dim"]) + d
    return (inv, m(float(rs["mscale"])) / m(float(rs["mscale_all_dim"])),
            width ** -0.5 * m(float(rs["mscale_all_dim"])) ** 2)


def _rotary(x, inv_freq, on_table: float):
    """x [T, heads, d_r] at positions 0 .. T-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    return x * (jnp.cos(ang) * on_table) \
        + _rotate_half(x) * (jnp.sin(ang) * on_table)


def _mla(x, w, heads: int, rank: int, d_n: int, d_r: int, d_v: int,
         eps: float, inv_freq: tuple, on_table: float, scale: float):
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (_rms_norm(h @ _f32(w["q_a_proj"]), w["q_a_layernorm"], eps)
         @ _f32(w["q_b_proj"])).reshape(t, heads, d_n + d_r)
    c, k_pe = jnp.split(h @ _f32(w["kv_a_proj_with_mqa"]), [rank], -1)
    kv = (_rms_norm(c, w["kv_a_layernorm"], eps)
          @ _f32(w["kv_b_proj"])).reshape(t, heads, d_n + d_v)
    q_pe = _rotary(q[..., d_n:], inv_freq, on_table)
    k_pe = _rotary(k_pe[:, None, :], inv_freq, on_table)[:, 0]
    seen = jnp.tril(jnp.ones((t, t), bool))

    def block(args):                      # a block of heads at a time
        q_n, q_r, kv_b = args             # [hb, T, .]
        s = (jnp.einsum("htd,hsd->hts", q_n, kv_b[..., :d_n])
             + jnp.einsum("htd,sd->hts", q_r, k_pe)) * scale
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, -1),
                          kv_b[..., d_n:])

    hb = min(HEAD_BLOCK, heads)

    def cut(y):                           # [T, H, d] -> [H / hb, hb, T, d]
        return jnp.moveaxis(y, 0, 1).reshape(heads // hb, hb, t, -1)

    a = jax.lax.map(block, (cut(q[..., :d_n]), cut(q_pe), cut(kv)))
    a = jnp.moveaxis(a.reshape(heads, t, d_v), 0, 1)
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
    """Columns [lo, lo + size) of the logits: the slice and its float32
    copy live inside the call, not side by side with the next one's."""
    return x @ _f32(jax.lax.dynamic_slice_in_dim(lm_head, lo, size, 1))


def _norm2(x, w, eps: float):
    return _rms_norm(x, w["post_attention_layernorm"], eps)


def _route(h, gate, top_k: int, n_group: int, topk_group: int,
           scale: float):
    """Per-expert weights [T, all experts]: 0 where not chosen."""
    s = jax.nn.softmax(h @ _f32(gate), -1)
    t, e = s.shape
    group_score = s.reshape(t, n_group, e // n_group).max(-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    rows = jnp.arange(t)[:, None]
    in_kept = jnp.zeros((t, n_group), bool).at[rows, kept].set(True)
    allowed = jnp.repeat(in_kept, e // n_group, axis=1)
    picked, chosen = jax.lax.top_k(jnp.where(allowed, s, 0.0), top_k)
    return jnp.zeros_like(s).at[rows, chosen].set(picked * scale)


def _sliced(fn, h, gate_up, down):
    """A SwiGLU of any width, a slice of at most WIDTH_BLOCK at a time."""
    inter = down.shape[0]
    step = min(WIDTH_BLOCK, inter)
    if inter % step:
        raise ValueError(f"width {inter} is not whole slices of {step}")
    return sum(fn(h, gate_up, down, lo, step)
               for lo in range(0, inter, step))


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> jax.Array:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence)."""
    eps = float(conf["rms_norm_eps"])
    held = int(conf["n_routed_experts"])
    first = held * int(conf.get("expert_parallel_rank", 0))
    inv_freq, on_table, scale = yarn(conf)
    mla = jax.jit(_mla, static_argnums=tuple(range(2, 11)))
    norm2 = jax.jit(_norm2, static_argnums=(2,))
    part = jax.jit(_swiglu_slice, static_argnums=(3, 4))
    route = jax.jit(_route, static_argnums=(2, 3, 4, 5))
    add_expert = jax.jit(_add_expert)
    head = jax.jit(_head_block, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for i, layer in enumerate(w["layers"]):
            x = mla(x, layer, int(conf["num_attention_heads"]),
                    int(conf["kv_lora_rank"]),
                    int(conf["qk_nope_head_dim"]),
                    int(conf["qk_rope_head_dim"]),
                    int(conf["v_head_dim"]), eps, tuple(inv_freq),
                    on_table, scale)
            h = norm2(x, layer, eps)
            if i < int(conf["first_k_dense_replace"]):
                x = x + _sliced(part, h, layer["gate_up_proj"],
                                layer["down_proj"])
                continue
            per_expert = route(
                h, layer["gate"], int(conf["num_experts_per_tok"]),
                int(conf["n_group"]), int(conf["topk_group"]),
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
