"""GPT-2 in pure functional JAX, designed for the MXU and named-axis meshes.

The reference's GPT-2 benchmarks wrap HuggingFace torch models in DDP
(/root/reference/release/air_tests/air_benchmarks/ — workload defs only);
here the model itself is framework code: a pytree of arrays + jit-able
forward, with a PartitionSpec tree (`gpt2_partition_specs`) giving the
megatron-style TP layout (attention and MLP split on the `tp` axis, 2D
[fsdp, tp] sharding for the big matmuls) so the same function runs dp-only,
fsdp, tp, or combinations by changing only the mesh.

TPU-first choices: bf16 params/activations by default with fp32 layernorm
stats (ops.layers), flash attention (ops.attention — Pallas on TPU), weight
tying for the LM head, static shapes throughout, no python control flow in
the jitted path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import flash_attention
from ..ops.layers import layer_norm
from .family import Family

Params = Dict[str, Any]


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    dtype: Any = jnp.bfloat16
    # pad vocab up so the embedding matmul tiles cleanly on the MXU / tp axis
    vocab_pad_multiple: int = 128

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @staticmethod
    def small() -> "GPT2Config":  # 125M — the benchmark flagship
        return GPT2Config()

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(num_layers=24, num_heads=16, d_model=1024)

    @staticmethod
    def tiny() -> "GPT2Config":  # test/dry-run size
        return GPT2Config(vocab_size=512, max_seq_len=128, num_layers=2,
                          num_heads=4, d_model=128)


def gpt2_init(config: GPT2Config, key: jax.Array) -> Params:
    """Initialize parameters (GPT-2 scheme: N(0, 0.02), residual projections
    scaled by 1/sqrt(2*n_layers))."""
    c = config
    k_iter = iter(jax.random.split(key, 4 + 12 * c.num_layers))

    def norm(k, *shape, scale=0.02):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * scale).astype(c.dtype)

    resid_scale = 0.02 / np.sqrt(2 * c.num_layers)
    params: Params = {
        "wte": norm(next(k_iter), c.padded_vocab, c.d_model),
        "wpe": norm(next(k_iter), c.max_seq_len, c.d_model, scale=0.01),
        "ln_f": {"scale": jnp.ones(c.d_model, c.dtype),
                 "bias": jnp.zeros(c.d_model, c.dtype)},
        "blocks": [],
    }
    for _ in range(c.num_layers):
        params["blocks"].append({
            "ln_1": {"scale": jnp.ones(c.d_model, c.dtype),
                     "bias": jnp.zeros(c.d_model, c.dtype)},
            "attn": {
                "qkv": norm(next(k_iter), c.d_model, 3 * c.d_model),
                "qkv_b": jnp.zeros(3 * c.d_model, c.dtype),
                "proj": norm(next(k_iter), c.d_model, c.d_model,
                             scale=resid_scale),
                "proj_b": jnp.zeros(c.d_model, c.dtype),
            },
            "ln_2": {"scale": jnp.ones(c.d_model, c.dtype),
                     "bias": jnp.zeros(c.d_model, c.dtype)},
            "mlp": {
                "fc": norm(next(k_iter), c.d_model, 4 * c.d_model),
                "fc_b": jnp.zeros(4 * c.d_model, c.dtype),
                "proj": norm(next(k_iter), 4 * c.d_model, c.d_model,
                             scale=resid_scale),
                "proj_b": jnp.zeros(c.d_model, c.dtype),
            },
        })
    return params


def _attn_proj_res(x: jax.Array, a: jax.Array, p: Params,
                   config: GPT2Config) -> jax.Array:
    """Attention output projection + residual (shared by the training,
    prefix-cache, and per-slot decode blocks)."""
    a = jnp.dot(a, p["attn"]["proj"],
                preferred_element_type=jnp.float32).astype(config.dtype)
    return x + a + p["attn"]["proj_b"]


def _mlp_res(x: jax.Array, p: Params, config: GPT2Config) -> jax.Array:
    h = layer_norm(x, p["ln_2"]["scale"], p["ln_2"]["bias"])
    h = jnp.dot(h, p["mlp"]["fc"],
                preferred_element_type=jnp.float32).astype(config.dtype)
    # tanh-approximate gelu: GPT-2's historical activation, and cheaper
    # on the VPU than the erf form
    h = jax.nn.gelu(h + p["mlp"]["fc_b"], approximate=True)
    h = jnp.dot(h, p["mlp"]["proj"],
                preferred_element_type=jnp.float32).astype(config.dtype)
    return x + h + p["mlp"]["proj_b"]


def _block(x: jax.Array, p: Params, config: GPT2Config) -> jax.Array:
    c = config
    b, t, _ = x.shape
    h = layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"])
    qkv = jnp.dot(h, p["attn"]["qkv"],
                  preferred_element_type=jnp.float32).astype(c.dtype)
    qkv = qkv + p["attn"]["qkv_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, t, c.num_heads, c.head_dim)
    k = k.reshape(b, t, c.num_heads, c.head_dim)
    v = v.reshape(b, t, c.num_heads, c.head_dim)
    a = flash_attention(q, k, v, True).reshape(b, t, c.d_model)
    return _mlp_res(_attn_proj_res(x, a, p, c), p, c)


def _constrain(x: jax.Array, spec: Optional[P]) -> jax.Array:
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def gpt2_hidden(params: Params, tokens: jax.Array, config: GPT2Config,
                remat: bool = False,
                act_spec: Optional[P] = None) -> jax.Array:
    """tokens [B, T] int32 -> final hidden states [B, T, d_model].

    remat=True checkpoints each transformer block (per-layer remat — the
    backward recomputes one layer at a time, peak activation memory is one
    layer's worth). act_spec, if given, pins the residual-stream sharding
    after every block so XLA never falls back to involuntary full
    rematerialization when tp/fsdp axes are active (requires an enclosing
    mesh context, e.g. TrainStep's)."""
    c = config
    t = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:t]
    x = _constrain(x, act_spec)
    block_fn = _block
    if remat:
        block_fn = jax.checkpoint(_block, static_argnums=(2,))
    for p in params["blocks"]:
        x = _constrain(block_fn(x, p, c), act_spec)
    return layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])


def gpt2_forward(params: Params, tokens: jax.Array,
                 config: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, padded_vocab] (fp32)."""
    x = gpt2_hidden(params, tokens, config)
    # tied LM head
    return jnp.dot(x, params["wte"].T, preferred_element_type=jnp.float32)


# ------------------------------------------------------- KV-cache decode


def _heads_per_row(config: GPT2Config) -> int:
    """How many heads the cache holds side by side in one row: heads
    narrower than the chip's 128 lanes are packed into rows of whole
    lane tiles, at most two of them (heads of 64: 4 to a row of 256
    lanes; of 32, where there are only 4: 4 to a row of 128). The count
    divides num_heads; 1 where no such count exists and for heads of
    128 and wider, which fill their lanes alone. Why two tiles and not
    one: PERF.md section 6, PR 30 (the decode tick is the same either
    way; the programs of one-tile rows took longer to load)."""
    c = config
    if c.head_dim >= 128:
        return 1
    for p in range(min(c.num_heads, 256 // c.head_dim), 1, -1):
        if c.num_heads % p == 0 and (p * c.head_dim) % 128 == 0:
            return p
    return 1


def gpt2_init_kv_cache(config: GPT2Config, batch_size: int,
                       max_len: int = 0, dtype: Any = None) -> list:
    """Per-layer K/V buffers [B, S, heads // p, p * head_dim], p =
    `_heads_per_row`: a row holds p consecutive heads side by side, so
    it fills whole 128-lane tiles (GPT-2 small: [B, S, 3, 256]; models/
    llama.py init_kv_cache has a head of 128 to a row) and a program
    reads and updates the slab where it lies. With head_dim 64 alone as
    the last axis the chip's default layout made S the minor-most one,
    and every tick copied every entry to the scatter's layout and
    back."""
    c = config
    s = max_len or c.max_seq_len
    dt = dtype or c.dtype
    p = _heads_per_row(c)
    shape = (batch_size, s, c.num_heads // p, p * c.head_dim)
    return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for _ in range(c.num_layers)]


def _qkv_rows(qkv: jax.Array, cache: Params):
    """Split the fused projection [B, t, 3D] into q, k, v in the cache's
    own row shape [B, t, heads // p, p * head_dim] (a few KB reshaped,
    never the slab): head r of a row lies in lanes [r * head_dim,
    (r + 1) * head_dim). k and v come in the cache's dtype."""
    b, t = qkv.shape[0], qkv.shape[1]
    rows = (b, t) + cache["k"].shape[2:]
    q, k, v = (x.reshape(rows) for x in jnp.split(qkv, 3, axis=-1))
    return q, k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)


def _cache_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     positions: jax.Array, head_dim: int) -> jax.Array:
    """A PROMPT's and a SUFFIX's attention alone (`_attention` chooses; a
    tick walks): masked float32 scores against ALL S rows of the cache as
    it lies. q [B, t, g, W], ck/cv [B, S, g, W], p = W // head_dim heads
    to a row; query (b, j) sees rows <= positions[b, j] ([B, t] or [1,
    t]). Rows are contracted whole, ops/swa.slab_attention with g rows
    and p queries a row: head r's query is its row with every lane
    outside its own head_dim set to zero, so its scores are exactly its
    own (the other lanes add 0 * k), and its output is its own lanes of
    the row its probabilities give. Nothing narrower than a row is ever
    formed. Returns [B, t, g * W], heads in their order."""
    b, t, g, w = q.shape
    p = w // head_dim
    own = jnp.arange(w)[None, :] // head_dim == jnp.arange(p)[:, None]
    qr = jnp.where(own, q[:, :, :, None, :], 0)          # [B, t, g, p, W]
    scores = jnp.einsum("btgrd,bsgd->bgrts", qr, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / (head_dim ** 0.5)
    col = jnp.arange(ck.shape[1])[None, None, None, None, :]
    visible = col <= positions[:, None, None, :, None]
    scores = jnp.where(visible, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    a = jnp.einsum("bgrts,bsgd->btgrd", probs, cv)       # [B, t, g, p, W]
    out = a[:, :, :, 0]
    for r in range(1, p):
        out = jnp.where(own[r], a[:, :, :, r], out)
    return out.reshape(b, t, g * w)


def _block_cached(x: jax.Array, p: Params, config: GPT2Config,
                  cache: Params, pos: jax.Array):
    """Cache-path block: tokens at [pos, pos+t) attend the full written
    prefix — the GPT-2 analog of llama_block_cached. The new rows go
    into the cache in its own row shape (`_qkv_rows`), and the
    attention reads it where it lies (`_attention`)."""
    c = config
    t = x.shape[1]
    h = layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"])
    qkv = jnp.dot(h, p["attn"]["qkv"],
                  preferred_element_type=jnp.float32).astype(c.dtype)
    qkv = qkv + p["attn"]["qkv_b"]
    q, k, v = _qkv_rows(qkv, cache)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    positions = pos + jnp.arange(t)[None, :]
    a = _attention(q, ck, cv, positions, c.head_dim)
    return _mlp_res(_attn_proj_res(x, a, p, c), p, c), {"k": ck, "v": cv}


def _block_decode(x: jax.Array, p: Params, config: GPT2Config,
                  cache: Params, pos_vec: jax.Array,
                  lora: Optional[Dict[str, Any]] = None):
    """Ragged-batch decode with PER-SLOT positions (continuous
    batching) — the GPT-2 analog of llama_block_decode. x [B, t, D];
    pos_vec [B] is each slot's BASE position (t == 1: the classic
    one-token tick; t == k+1: the speculative verify pass — see
    llama_block_decode for the masking contract the oracle rests on).
    One row a slot and position is scattered into the cache in its own
    row shape, and the attention is `_block_cached`'s (`_attention`:
    the decode walk), so a verify row is a sequential tick's math.

    `lora` (optional, serve/lora.py mixed-tenant decode): this layer's
    per-slot adapter selection for the fused qkv projection —
    ``{"qkv": (a [B,D,r], b [B,r,3D]), "scale": [B]}`` — added to the
    base matmul; null-adapter slots add an exact-zero delta."""
    c = config
    b, t = x.shape[0], x.shape[1]
    h = layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"])
    qkv = jnp.dot(h, p["attn"]["qkv"],
                  preferred_element_type=jnp.float32).astype(c.dtype)
    if lora is not None:
        from ..ops.layers import lora_delta

        qkv = qkv + lora_delta(h, *lora["qkv"], lora["scale"])
    qkv = qkv + p["attn"]["qkv_b"]
    q, k, v = _qkv_rows(qkv, cache)
    positions = pos_vec[:, None] + jnp.arange(t)[None, :]   # [B, t]
    # the row axis is indexed too, so the scatter's window is one row of
    # lanes: with the rows of a position as its window the chip wants
    # the whole entry in another layout, and copies it there and back
    at = (jnp.arange(b)[:, None, None], positions[:, :, None],
          jnp.arange(k.shape[2])[None, None, :])
    ck = cache["k"].at[at].set(k)
    cv = cache["v"].at[at].set(v)
    a = _attention(q, ck, cv, positions, c.head_dim)
    return _mlp_res(_attn_proj_res(x, a, p, c), p, c), {"k": ck, "v": cv}


def gpt2_decode(params: Params, tokens: jax.Array, config: GPT2Config,
                cache: list, pos_vec: jax.Array,
                lora: Optional[Dict[str, Any]] = None):
    """One decode step for a ragged batch: tokens [B] at per-slot
    positions pos_vec [B] ([B, q] is the speculative verify form —
    logits come back [B, q, padded_vocab]; see llama_decode). `lora`
    (optional): adapter-pool stacks + per-slot indices ``{"idx": [B],
    "scale": [P], "qkv": (a [P,L,D,r], b [P,L,r,3D])}`` — see
    llama_decode for the contract."""
    c = config
    ragged = tokens.ndim == 1
    if ragged:
        x = params["wte"][tokens[:, None]] \
            + params["wpe"][pos_vec][:, None]
    else:
        positions = pos_vec[:, None] + jnp.arange(
            tokens.shape[1])[None, :]
        x = params["wte"][tokens] + params["wpe"][positions]
    sel = None
    if lora is not None:
        idx = lora["idx"]
        sel = (lora["qkv"][0][idx], lora["qkv"][1][idx])
        scale = lora["scale"][idx]
    new_cache = []
    for li, (p, blk) in enumerate(zip(params["blocks"], cache)):
        lora_l = None if sel is None else {
            "qkv": (sel[0][:, li], sel[1][:, li]), "scale": scale}
        x, nc = _block_decode(x, p, c, blk, pos_vec, lora_l)
        new_cache.append(nc)
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if ragged:
        x = x[:, 0]
    return jnp.dot(x, params["wte"].T,
                   preferred_element_type=jnp.float32), new_cache


def gpt2_forward_cached(params: Params, tokens: jax.Array,
                        config: GPT2Config, cache: list, pos: jax.Array):
    """Append tokens [B, T] at scalar position `pos`; returns (logits
    [B, T, padded_vocab] fp32, new_cache). pos=0 + whole prompt =
    prefill; T=1 afterwards = decode."""
    c = config
    t = tokens.shape[1]
    wpe = jax.lax.dynamic_slice(params["wpe"], (pos, 0),
                                (t, c.d_model))
    x = params["wte"][tokens] + wpe
    new_cache = []
    for p, blk in zip(params["blocks"], cache):
        x, nc = _block_cached(x, p, c, blk, pos)
        new_cache.append(nc)
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return jnp.dot(x, params["wte"].T,
                   preferred_element_type=jnp.float32), new_cache


def _ce_sum(x: jax.Array, targets: jax.Array, wte: jax.Array,
            vocab_size: int) -> jax.Array:
    """Sum of next-token cross-entropy. x [..., d], targets [...]."""
    logits = jnp.dot(x, wte.T, preferred_element_type=jnp.float32)
    if wte.shape[0] != vocab_size:  # mask the vocab padding
        col = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(col < vocab_size, logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll)


def gpt2_loss(params: Params, tokens: jax.Array, targets: jax.Array,
              config: GPT2Config, remat: bool = False,
              loss_chunk_rows: int = 2048,
              act_spec: Optional[P] = None) -> jax.Array:
    """Mean next-token cross-entropy, computed in sequence chunks so the
    [B, T, padded_vocab] fp32 logits never materialize whole (at GPT-2
    vocab one full-batch logits tensor is gigabytes; chunking caps it near
    loss_chunk_rows * padded_vocab, recomputed per chunk in the backward).
    Chunking splits the sequence axis, so dp/fsdp batch sharding is
    untouched and each chunk stays a full-width MXU matmul.
    """
    c = config
    x = gpt2_hidden(params, tokens, config, remat=remat, act_spec=act_spec)
    b, t = targets.shape

    from ..ops.fused_ce import fused_ce_supported, linear_cross_entropy
    if fused_ce_supported(b * t, c.d_model, c.padded_vocab):
        # fused kernel: logits never materialize (ops/fused_ce.py)
        losses = linear_cross_entropy(
            x.reshape(b * t, c.d_model), params["wte"],
            targets.reshape(b * t), c.vocab_size)
        return jnp.sum(losses) / (b * t)

    n_chunks = min(t, max(1, (b * t) // loss_chunk_rows))
    while t % n_chunks != 0:
        n_chunks -= 1

    def chunk_fn(args):
        xi, ti = args
        return _ce_sum(xi, ti, params["wte"], c.vocab_size)

    if n_chunks == 1:
        total = chunk_fn((x, targets))
    else:
        xc = x.reshape(b, n_chunks, t // n_chunks,
                       c.d_model).swapaxes(0, 1)
        tc = targets.reshape(b, n_chunks, t // n_chunks).swapaxes(0, 1)
        total = jnp.sum(jax.lax.map(jax.checkpoint(chunk_fn), (xc, tc)))
    return total / (b * t)


def gpt2_partition_specs(config: GPT2Config) -> Params:
    """PartitionSpec tree for the params: megatron TP layout with fsdp on
    the other matmul dimension. With tp=1/fsdp=1 every spec collapses to
    replicated, so one tree serves all mesh shapes."""
    block = {
        "ln_1": {"scale": P(), "bias": P()},
        "attn": {
            "qkv": P("fsdp", "tp"),     # column-parallel
            "qkv_b": P("tp"),
            "proj": P("tp", "fsdp"),    # row-parallel
            "proj_b": P(),
        },
        "ln_2": {"scale": P(), "bias": P()},
        "mlp": {
            "fc": P("fsdp", "tp"),      # column-parallel
            "fc_b": P("tp"),
            "proj": P("tp", "fsdp"),    # row-parallel
            "proj_b": P(),
        },
    }
    return {
        # vocab sharded over BOTH model axes, d_model replicated: a 2D-
        # sharded wte ([tp, fsdp]) forces XLA into "involuntary full
        # rematerialization" reconciling the embedding-gather and LM-head
        # grad shardings (replicate-then-reshard on every step); single-dim
        # vocab sharding keeps the memory scaling and compiles clean, and
        # logits come out vocab-sharded — Megatron-style vocab-parallel CE
        "wte": P(("tp", "fsdp"), None),
        "wpe": P(None, "fsdp"),
        "ln_f": {"scale": P(), "bias": P()},
        "blocks": [block for _ in range(config.num_layers)],
    }


def gpt2_lora_targets(config: GPT2Config):
    return (("qkv", config.d_model, 3 * config.d_model),)


# Down here, and imported here, because the training step's Mosaic
# kernels are keyed in the compile cache by the lines of their call
# sites above (`_block`, `gpt2_loss`; ROADMAP D21): those must not move.
from ..ops.swa import DECODE_ROWS, decode_attention  # noqa: E402


def _attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
               positions: jax.Array, head_dim: int) -> jax.Array:
    """The cached paths' attention in the form the run's length asks
    for, read from `q`'s shape alone as `ops/swa.cache_attention` reads
    it. A run of at most `DECODE_ROWS` rows is a tick's (one token a
    slot, or the speculative verify's k + 1) and takes the decode walk:
    `_cache_attention`'s zeroed rows ride `ops/swa.decode_attention` as
    g x p query heads over g key-value heads of W numbers, scaled by a
    head's own width, so slot b's blocks up to its position are read and
    no other. A longer one (a prompt, a suffix on a cached prefix,
    `generate()`'s prefill) takes `_cache_attention` over the slab."""
    b, t, g, w = q.shape
    if t > DECODE_ROWS:
        return _cache_attention(q, ck, cv, positions, head_dim)
    p = w // head_dim
    own = jnp.arange(w)[None, :] // head_dim == jnp.arange(p)[:, None]
    qr = jnp.where(own, q[:, :, :, None, :], 0)          # [B, t, g, p, W]
    a = decode_attention(qr.reshape(b, t, g * p, w), ck, cv,
                         jnp.broadcast_to(positions, (b, t)),
                         head_dim ** -0.5).reshape(b, t, g, p, w)
    # a head's own lanes of the row its probabilities gave
    return jnp.where(own, a, 0).sum(3).reshape(b, t, g * w)


FAMILY = Family(
    config_type=GPT2Config, init=gpt2_init, forward=gpt2_forward,
    loss=gpt2_loss, partition_specs=gpt2_partition_specs,
    init_cache=gpt2_init_kv_cache, forward_cached=gpt2_forward_cached,
    decode=gpt2_decode, lora_targets=gpt2_lora_targets, decode_walks=True)
