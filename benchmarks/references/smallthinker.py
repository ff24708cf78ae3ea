"""SmallThinker in plain float32, from its published description (the
SmallThinker report, 2025, and the keys of its config.json): every layer
is grouped-query attention and an expert layer, each behind an RMSNorm
with a residual, and the layer's router reads the layer's INPUT.

For a layer's input x [T, D] (layer i, 0-based):

  router  `r = x W_r` over all `moe_num_primary_experts`, from x as it
          enters the layer (before the attention's norm); the
          `moe_num_active_primary_experts` largest are chosen and weigh
          `softmax` over those logits alone.
  GQA     `h = RMSNorm(x)`; q = h W_q as `num_attention_heads` heads of
          `head_dim`, k = h W_k and v = h W_v as `num_key_value_heads`;
          where `rope_layout[i]` is 1, q and k take rotary positions (`x
          cos + rotate_half(x) sin`, theta `rope_theta`, no scaling);
          head h reads key-value head `h // (heads / kv heads)`; scores
          `q . k / sqrt(head_dim)`, causal; where
          `sliding_window_layout[i]` is 1, query i sees keys j with `i -
          sliding_window_size < j <= i`; `x <- x + a W_o`.
  ReGLU   `u = RMSNorm(x)`; `x <- x + sum over the chosen e of w_e
          ((relu(u G_e) * (u U_e)) D_e)`. No shared expert.

then a final RMSNorm and the untied head. A dense [block, T] mask for
each block of queries: no ring, no band that is skipped, no kernel.

`weights` renames the program's pytree to the published names (each
matrix stored [in, out]; gate and up stay side by side as the program
packs them and are cut where they are used); that is all this file takes
from the program. One layer's attention is one jitted call, a block of
queries at a time; of an expert layer `EXPERT_BLOCK` experts are cast to
float32 at a time; the head goes through in blocks of vocabulary rows,
each block cut and cast inside its call and brought to the HOST as it is
made (170 MB at a time, under the harness's 256 MiB staging buffer): the
logits of a 5,200-token check are 3.2 GB in float32, which a chip that
serves the model beside them does not have, so `logits` hands back the
host's array.

Departures, noted: none from the configuration file's `assumed`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference import _f32, _rms_norm, _rope

QUERY_BLOCK = 512
EXPERT_BLOCK = 8
VOCAB_BLOCK = 8192


def weights(params: Any) -> Dict[str, Any]:
    layers = []
    for b in params["blocks"]:
        layers.append({
            "input_layernorm": b["norm1"]["scale"],
            "post_attention_layernorm": b["norm2"]["scale"],
            "q_proj": b["attn"]["wq"], "k_proj": b["attn"]["wk"],
            "v_proj": b["attn"]["wv"], "o_proj": b["attn"]["wo"],
            "primary_router": b["moe"]["router"],
            "experts_gate_up_proj": b["moe"]["w1"],
            "experts_down_proj": b["moe"]["w2"]})
    return {"embed_tokens": params["tok_emb"],
            "norm": params["norm_f"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


def _route(x, router, top_k: int):
    """Per-expert weights [T, experts] from the layer's input: softmax
    over the chosen logits, 0 where not chosen."""
    r = x @ _f32(router)
    top, chosen = jax.lax.top_k(r, top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, chosen].set(jax.nn.softmax(top, -1))


def _attention(x, w, heads: int, kv_heads: int, head_dim: int, eps: float,
               theta: float, rotary: bool, window: int):
    """x + attention(RMSNorm(x)) W_o; `window` 0: every earlier key."""
    t = x.shape[0]
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = (h @ _f32(w["q_proj"])).reshape(t, heads, head_dim)
    k = (h @ _f32(w["k_proj"])).reshape(t, kv_heads, head_dim)
    v = (h @ _f32(w["v_proj"])).reshape(t, kv_heads, head_dim)
    if rotary:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    key_at = jnp.arange(t)[None, :]

    def one(args):                 # a block of queries, a dense mask
        first, q_b = args          # [block, heads, head_dim]
        at = first + jnp.arange(block)[:, None]
        seen = key_at <= at
        if window:
            seen &= key_at > at - window
        s = jnp.einsum("tgrd,sgd->grts",
                       q_b.reshape(block, kv_heads, rep, head_dim), k) \
            / jnp.sqrt(jnp.float32(head_dim))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(one, (jnp.arange(0, t + pad, block),
                          q.reshape(-1, block, heads, head_dim)))
    a = a.reshape(t + pad, heads * head_dim)[:t]
    return x + a @ _f32(w["o_proj"])


def _norm2(x, w, eps: float):
    return _rms_norm(x, w["post_attention_layernorm"], eps)


def _add_experts(x, u, gate_up, down, per_expert, first, count: int):
    """x + the ReGLU of experts [first, first + count) of u, each
    weighed per token: `count` experts in float32 a call."""
    inter = down.shape[1]
    for e in range(count):
        gu = _f32(jax.lax.dynamic_index_in_dim(gate_up, first + e, 0,
                                               keepdims=False))
        dn = _f32(jax.lax.dynamic_index_in_dim(down, first + e, 0,
                                               keepdims=False))
        mid = jax.nn.relu(u @ gu[:, :inter]) * (u @ gu[:, inter:])
        weight = jax.lax.dynamic_index_in_dim(per_expert, first + e, 1)
        x = x + weight * (mid @ dn)
    return x


def _head_block(x, norm, lm_head, eps: float, lo, size: int):
    """Columns [lo, lo + size) of the logits: the slice and its float32
    copy live inside the call (`lo` is traced: one program a size, not
    one a block)."""
    return _rms_norm(x, norm, eps) @ _f32(
        jax.lax.dynamic_slice_in_dim(lm_head, lo, size, 1))


def logits(w: Dict[str, Any], tokens: jax.Array,
           conf: Dict[str, Any]) -> np.ndarray:
    """tokens [T] -> logits [T, vocab_size] float32 (one sequence), on
    the host."""
    eps = float(conf["rms_norm_eps"])
    experts = int(conf["moe_num_primary_experts"])
    step = min(EXPERT_BLOCK, experts)
    if experts % step:
        raise ValueError(f"{experts} experts are not whole blocks of {step}")
    attention = jax.jit(_attention, static_argnums=tuple(range(2, 9)))
    route = jax.jit(_route, static_argnums=(2,))
    norm2 = jax.jit(_norm2, static_argnums=(2,))
    add_experts = jax.jit(_add_experts, static_argnums=(6,))
    head = jax.jit(_head_block, static_argnums=(3, 5))
    vocab = int(conf["vocab_size"])
    out = np.empty((int(tokens.shape[0]), vocab), np.float32)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        for i, layer in enumerate(w["layers"]):
            per_expert = route(
                x, layer["primary_router"],
                int(conf["moe_num_active_primary_experts"]))
            x = attention(
                x, layer, int(conf["num_attention_heads"]),
                int(conf["num_key_value_heads"]), int(conf["head_dim"]),
                eps, float(conf["rope_theta"]),
                bool(conf["rope_layout"][i]),
                int(conf["sliding_window_size"])
                if conf["sliding_window_layout"][i] else 0)
            u = norm2(x, layer, eps)
            for first in range(0, experts, step):
                x = add_experts(x, u, layer["experts_gate_up_proj"],
                                layer["experts_down_proj"], per_expert,
                                first, step)
        for lo in range(0, vocab, VOCAB_BLOCK):
            size = min(VOCAB_BLOCK, vocab - lo)
            out[:, lo:lo + size] = np.asarray(head(
                x, w["norm"], w["lm_head"], eps, lo, size))
    return out
