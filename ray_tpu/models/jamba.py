"""The Jamba family (`model_type` jamba; AI21 Jamba, Jamba2) in pure
functional JAX: a stack of layers of TWO residual sublayers each, a mixer
and a feed-forward, each behind an RMSNorm: `x <- x + mixer(norm1(x)); x
<- x + mlp(norm2(x))`. Layer i's mixer is attention iff `i % attn_period
== attn_offset`, else Mamba-1; a final RMSNorm, and the head is the
embedding (tied).

  mamba      (`ops/mamba1.py`) `[u | z] = h W_in`; u through the causal
             depthwise convolution (with bias) and SiLU; `[r | B | C] = u
             W_x`, each through an RMSNorm of its own with a learned
             scale (the family's inner norms); `dt = softplus(r W_dt +
             b_dt)`; the selective recurrence over a state [N, C] a
             channel and state (no matrix form: module docstring there),
             gated by `silu(z)` inside it; `W_out`. It owns state with NO
             sequence axis: the float32 recurrence state and the last K-1
             inputs of the convolution.
  attention  `num_heads` query heads over `num_kv_heads` key-value heads
             (20 over ONE as published: multi-query), no bias, NO
             positional embedding. A run of tokens from position 0 goes
             through the prompt form over the run alone
             (`ops/swa.prompt_attention`, whose block follows from the
             group's size); a suffix and the decode tick through
             `ops/swa.cache_attention` over the cache as it lies.
  mlp        SwiGLU, `W_down(silu(h W_gate) * h W_up)`, dense in every
             layer (`num_experts` 1).

The residual stream is float32 (weights and every product's inputs are
`dtype`, bf16 as served; products accumulate in float32 and go back into
the stream unrounded).

Weights are STACKED by run (`runs`): the layers between two attention
layers are one pytree with a leading layer axis, and a program walks
them as ONE loop (`lax.scan`), so a 28-layer model compiles five layer
bodies (three Mamba runs of 7, 13 and 6, two attention layers) and not
28. The cache (`init_cache`) follows: first one {"k", "v"} [B, S,
kv_heads, head_dim] an attention layer, then one {"ssm" [B, n, N, C]
float32, "conv" [B, n, K-1, C]} a Mamba RUN of n layers; the engine
(`models/engine.py`) splices entries with "k"/"v" by rows and any other
entry whole.

A prompt is walked in blocks of `token_block` tokens, the state and the
convolution's tail carried from block to block: a [T, 2 C] in-projection
at 32,768 tokens has no room beside the weights. A ragged last block is
padded; the padding neither decays nor feeds the state (`dt = 0` AFTER
the softplus) and stays out of the tail, so the state handed to the
first tick is the one after the last REAL token. `forward_cached`
continues from whatever the cache holds, hands back the logits of the
LAST position only (`num_logits_to_keep` 1), and `forward_counted` adds
`scan_blocks`, the time blocks the scan walked over all layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.layers import mm, rms_norm
from ..ops.mamba1 import selective_scan, selective_step
from ..ops.mamba2 import causal_conv
from ..ops.swa import cache_attention, prompt_attention
from .family import Family

Params = Dict[str, Any]
F32 = jnp.float32


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    max_seq_len: int = 33280
    num_layers: int = 28
    d_model: int = 2560
    norm_eps: float = 1e-6
    attn_period: int = 14
    attn_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    d_ff: int = 8192
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_d_conv: int = 4
    token_block: int = 2048          # tokens of a prompt a pass
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError("attn_offset lies outside the period")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """The layers as runs of one kind: ("M", n) for n Mamba layers in
        a row, ("A", 1) for an attention layer."""
        out: List[Tuple[str, int]] = []
        for i in range(self.num_layers):
            kind = "A" if i % self.attn_period == self.attn_offset else "M"
            if kind == "M" and out and out[-1][0] == "M":
                out[-1] = ("M", out[-1][1] + 1)
            else:
                out.append((kind, 1))
        return tuple(out)

    @staticmethod
    def tiny() -> "JambaConfig":  # tests / dry runs
        return JambaConfig(
            vocab_size=512, max_seq_len=128, num_layers=5, d_model=64,
            attn_period=3, attn_offset=1, num_heads=4, num_kv_heads=1,
            head_dim=16, d_ff=96, mamba_d_state=4, mamba_dt_rank=8,
            token_block=4)


# ------------------------------------------------------------------ init

def jamba_init(config: JambaConfig, key: jax.Array) -> Params:
    """Every matrix normal(0, 0.02), the embedding too: it is the head,
    and at 0.02 the logits have a spread of about 1 (a unit embedding
    would make every token predict itself, by |E|^2). Mamba's own init
    for what is its own: A[c, n] = -(n + 1), dt's bias the inverse
    softplus of dt log-uniform in [0.001, 0.1], D ones, dt's projection
    uniform at 1 / sqrt(R), the convolution normal at 1 / sqrt(K), its
    bias at 0.1 so that it counts."""
    c = config
    keys = iter(jax.random.split(key, 1 + 16 * len(c.runs)))
    ci, n, r, k = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv

    def normal(*shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, F32)
                * scale).astype(c.dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, c.dtype)}

    def mlp(layers):
        return {"w_gate": normal(layers, c.d_model, c.d_ff),
                "w_up": normal(layers, c.d_model, c.d_ff),
                "w_down": normal(layers, c.d_ff, c.d_model)}

    q_dim, kv_dim = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    runs = []
    for kind, layers in c.runs:
        run: Params = {"norm1": ones(layers, c.d_model),
                       "norm2": ones(layers, c.d_model), "mlp": mlp(layers)}
        if kind == "M":
            dt = jnp.exp(jax.random.uniform(next(keys), (layers, ci), F32)
                         * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            bound = r ** -0.5
            run["mamba"] = {
                "w_in": normal(layers, c.d_model, 2 * ci),
                "conv_w": normal(layers, k, ci, scale=k ** -0.5),
                "conv_b": normal(layers, ci, scale=0.1),
                "w_x": normal(layers, ci, r + 2 * n),
                "norm_dt": jnp.ones((layers, r), c.dtype),
                "norm_b": jnp.ones((layers, n), c.dtype),
                "norm_c": jnp.ones((layers, n), c.dtype),
                "w_dt": jax.random.uniform(next(keys), (layers, r, ci), F32,
                                           -bound, bound).astype(c.dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                # [N, C]: the channels along the lanes (ops/mamba1.py)
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=F32))[None, :, None], (layers, n, ci)),
                "D": jnp.ones((layers, ci), F32),
                "w_out": normal(layers, ci, c.d_model),
            }
        else:
            run["attn"] = {"wq": normal(layers, c.d_model, q_dim),
                           "wk": normal(layers, c.d_model, kv_dim),
                           "wv": normal(layers, c.d_model, kv_dim),
                           "wo": normal(layers, q_dim, c.d_model)}
        runs.append(run)
    return {"tok_emb": normal(c.vocab_size, c.d_model),
            "norm_f": ones(c.d_model), "runs": runs}


# ------------------------------------------------------------ sublayers

def _norm(x: jax.Array, scale: jax.Array, c: JambaConfig) -> jax.Array:
    """The float32 stream through an RMSNorm, in the weights' type."""
    return rms_norm(x, scale, c.norm_eps).astype(c.dtype)


def _mlp(x: jax.Array, p: Params, c: JambaConfig) -> jax.Array:
    """x <- x + mlp(norm2(x)); x [.., D] float32."""
    with jax.named_scope("mlp"):
        h = _norm(x, p["norm2"]["scale"], c)
        gate = jnp.dot(h, p["mlp"]["w_gate"], preferred_element_type=F32)
        up = jnp.dot(h, p["mlp"]["w_up"], preferred_element_type=F32)
        mid = (jax.nn.silu(gate) * up).astype(c.dtype)
        return x + jnp.dot(mid, p["mlp"]["w_down"],
                           preferred_element_type=F32)


def _mamba_inputs(x: jax.Array, p: Params, c: JambaConfig, tail: jax.Array):
    """x [B, T, D] float32 on top of the convolution's `tail` -> (u [B, T,
    C] convolved, z, dt [B, T, C] float32 after softplus, B, C [B, T, N]
    float32, a [N, C], the inputs of the convolution for the next tail)."""
    m = p["mamba"]
    h = _norm(x, p["norm1"]["scale"], c)
    raw, z = jnp.split(mm(h, m["w_in"]), 2, axis=-1)
    u, _ = causal_conv(raw, tail, m["conv_w"], m["conv_b"])
    # the inputs the convolution saw (the tail, then the run): a ragged
    # block takes its new tail from among them
    window = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)
    r, n = c.mamba_dt_rank, c.mamba_d_state
    low, bm, cm = jnp.split(
        jnp.dot(u, m["w_x"], preferred_element_type=F32), [r, r + n], -1)
    low = rms_norm(low, m["norm_dt"], c.norm_eps).astype(c.dtype)
    dt = jax.nn.softplus(
        jnp.dot(low, m["w_dt"], preferred_element_type=F32) + m["dt_bias"])
    return (u, z, dt, rms_norm(bm, m["norm_b"], c.norm_eps),
            rms_norm(cm, m["norm_c"], c.norm_eps), -jnp.exp(m["A_log"]),
            window)


def _mamba_block(x: jax.Array, p: Params, c: JambaConfig, ssm: jax.Array,
                 tail: jax.Array, real: jax.Array, tokens: int):
    """One Mamba LAYER (both sublayers) over a block of a prompt: x [B, T,
    D] of which the first `real` tokens are the prompt's (the rest
    padding), on top of the state `ssm` [B, N, C] and the convolution's
    `tail` [B, K-1, C]. Returns (x, the state and the tail after the last
    real token)."""
    with jax.named_scope("mamba1"):
        u, z, dt, bm, cm, a, window = _mamba_inputs(x, p, c, tail)
        dt = jnp.where((jnp.arange(x.shape[1]) < real)[None, :, None],
                       dt, 0.0)
        y, ssm = selective_scan(u, dt, a, bm, cm, p["mamba"]["D"], z, ssm,
                                tokens)
        tail = jax.lax.dynamic_slice_in_dim(
            window, real, c.mamba_d_conv - 1, axis=1).astype(tail.dtype)
        x = x + jnp.dot(y, p["mamba"]["w_out"], preferred_element_type=F32)
    return _mlp(x, p, c), ssm.astype(c.state_dtype), tail


def _mamba_tick(x: jax.Array, p: Params, c: JambaConfig, ssm: jax.Array,
                tail: jax.Array):
    """One Mamba layer for one token a row: x [B, 1, D]."""
    with jax.named_scope("mamba1"):
        u, z, dt, bm, cm, a, window = _mamba_inputs(x, p, c, tail)
        y, ssm = selective_step(u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                p["mamba"]["D"], z[:, 0], ssm)
        x = x + jnp.dot(y[:, None], p["mamba"]["w_out"],
                        preferred_element_type=F32)
    return _mlp(x, p, c), ssm, window[:, 1:].astype(tail.dtype)


def _layer(run: Params, i) -> Params:
    """Layer `i` of a run's stacked weights."""
    return jax.tree.map(lambda w: w[i], run)


def _mamba_run(x: jax.Array, run: Params, c: JambaConfig, state: Params,
               one) -> Tuple[jax.Array, Params]:
    """A run of Mamba layers as ONE loop over its stacked weights, the
    run's state {"ssm" [B, n, N, C], "conv" [B, n, K-1, C]} carried whole
    and each layer's part read and written in place. `one(x, p, ssm,
    tail) -> (x, ssm, tail)` is the layer."""
    def body(carry, inp):
        x, ssm, conv = carry
        p, i = inp
        x, s, t = one(x, p, take(ssm, i), take(conv, i))
        return (x, put(ssm, s, i), put(conv, t, i)), None

    take = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 1, False)
    put = lambda a, new, i: jax.lax.dynamic_update_index_in_dim(
        a, new.astype(a.dtype), i, 1)
    layers = state["ssm"].shape[1]
    (x, ssm, conv), _ = jax.lax.scan(
        body, (x, state["ssm"], state["conv"]), (run, jnp.arange(layers)))
    return x, {"ssm": ssm, "conv": conv}


def _to_blocks(x: jax.Array, c: JambaConfig) -> jax.Array:
    """A prompt x [B, T, D] as [blocks, B, block, D], in blocks of
    `token_block` tokens (one block of T for a shorter prompt), the last
    padded with zeros."""
    b, t, d = x.shape
    block = min(c.token_block, t)
    xb = jnp.pad(x, ((0, 0), (0, -t % block), (0, 0)))
    return jnp.moveaxis(xb.reshape(b, -1, block, d), 1, 0)


def _from_blocks(xb: jax.Array, t: int) -> jax.Array:
    """`_to_blocks` undone: the first `t` tokens as [B, t, D]."""
    nb, b, block, d = xb.shape
    return jnp.moveaxis(xb, 0, 1).reshape(b, nb * block, d)[:, :t]


def _mamba_prefill(x: jax.Array, run: Params, c: JambaConfig,
                   state: Params) -> Tuple[jax.Array, Params]:
    """A run of Mamba layers over a prompt x [B, T, D], in blocks of
    `token_block` tokens (a layer walks the blocks with its state and
    tail carried; the run walks its layers)."""
    t = x.shape[1]
    xb = _to_blocks(x, c)
    nb, _, block, _ = xb.shape
    real = jnp.clip(t - jnp.arange(nb) * block, 0, block)

    def one(xb, p, ssm, tail):
        def step(carry, inp):
            x_i, ssm, tail = _mamba_block(inp[0], p, c, *carry, inp[1], t)
            return (ssm, tail), x_i

        (ssm, tail), xb = jax.lax.scan(step, (ssm, tail), (xb, real))
        return xb, ssm, tail

    xb, state = _mamba_run(xb, run, c, state, one)
    return _from_blocks(xb, t), state


def _mlp_in_blocks(x: jax.Array, p: Params, c: JambaConfig) -> jax.Array:
    """`_mlp` over a prompt x [B, T, D] in blocks of `token_block`."""
    xb = _to_blocks(x, c)
    if xb.shape[0] == 1:
        return _mlp(x, p, c)
    return _from_blocks(jax.lax.map(lambda blk: _mlp(blk, p, c), xb),
                        x.shape[1])


def _qkv(x: jax.Array, p: Params, c: JambaConfig):
    b, t, _ = x.shape
    h = _norm(x, p["norm1"]["scale"], c)
    a = p["attn"]
    return (mm(h, a["wq"]).reshape(b, t, c.num_heads, c.head_dim),
            mm(h, a["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim),
            mm(h, a["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim))


def _attn_out(a: jax.Array, p: Params) -> jax.Array:
    return jnp.dot(a.reshape(a.shape[:2] + (-1,)), p["attn"]["wo"],
                   preferred_element_type=F32)


def _from_zero(pos: Any) -> bool:
    """Whether `pos` is a 0 known while tracing: a run of tokens from it
    attends over itself alone."""
    try:
        return int(pos) == 0
    except TypeError:       # a tracer
        return False


def _attn_run(x: jax.Array, p: Params, c: JambaConfig, cache: Params | None,
              pos: Any) -> Tuple[jax.Array, Params | None]:
    """An attention layer's mixer over a run of tokens x [B, T, D] at
    scalar position `pos`: from a concrete 0 the prompt form over the run
    alone, else (a suffix) over the cache as it lies. The rows land in
    the cache, if there is one."""
    with jax.named_scope("attention"):
        b, t, _ = x.shape
        q, k, v = _qkv(x, p, c)
        if cache is not None:
            cache = {n: jax.lax.dynamic_update_slice(
                cache[n], new.astype(cache[n].dtype), (0, pos, 0, 0))
                for n, new in (("k", k), ("v", v))}
        if cache is None or _from_zero(pos):
            a, _ = prompt_attention(q, k, v)
        else:
            positions = jnp.broadcast_to(pos + jnp.arange(t)[None, :],
                                         (b, t))
            a = cache_attention(q, cache["k"], cache["v"], positions)
        return x + _attn_out(a, p), cache


def _attn_tick(x: jax.Array, p: Params, c: JambaConfig, cache: Params,
               positions: jax.Array) -> Tuple[jax.Array, Params]:
    """One token a row at `positions` [B, 1]."""
    with jax.named_scope("attention"):
        q, k, v = _qkv(x, p, c)
        at = (jnp.arange(x.shape[0])[:, None], positions)
        ck = cache["k"].at[at].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[at].set(v.astype(cache["v"].dtype))
        a = cache_attention(q, ck, cv, positions)
        return x + _attn_out(a, p), {"k": ck, "v": cv}


def _head(x: jax.Array, params: Params, c: JambaConfig) -> jax.Array:
    """The final norm, then the embedding as the head."""
    with jax.named_scope("head"):
        h = _norm(x, params["norm_f"]["scale"], c)
        return jax.lax.dot_general(
            h, params["tok_emb"], (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=F32)


# ------------------------------------------------------------- the model

def _mamba_state(c: JambaConfig, batch: int, layers: int) -> Params:
    return {"ssm": jnp.zeros((batch, layers, c.mamba_d_state, c.d_inner),
                             c.state_dtype),
            "conv": jnp.zeros((batch, layers, c.mamba_d_conv - 1, c.d_inner),
                              c.dtype)}


def jamba_init_cache(config: JambaConfig, batch_size: int, max_len: int = 0,
                     dtype: Any = None) -> list:
    """The cache by kind: one {"k", "v"} [B, S, kv_heads, head_dim] per
    attention layer first, then one {"ssm", "conv"} per RUN of Mamba
    layers (module docstring), each in the order of the layers."""
    c = config
    kv = (batch_size, max_len or c.max_seq_len, c.num_kv_heads, c.head_dim)
    dt = dtype or c.dtype
    return ([{"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
             for kind, _ in c.runs if kind == "A"]
            + [_mamba_state(c, batch_size, n)
               for kind, n in c.runs if kind == "M"])


def _cache_slots(c: JambaConfig) -> List[int]:
    """Run -> its entry in the cache list."""
    n_attn = sum(kind == "A" for kind, _ in c.runs)
    seen = {"A": 0, "M": n_attn}
    out = []
    for kind, _ in c.runs:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def _walk(params: Params, tokens: jax.Array, c: JambaConfig,
          cache: list | None, pos: Any):
    """tokens [B, T] at scalar position `pos` through every run -> (the
    stream [B, T, D] float32, the new cache or None)."""
    x = params["tok_emb"][tokens].astype(F32)
    b = tokens.shape[0]
    new_cache = list(cache) if cache is not None else None
    for (kind, n), run, at in zip(c.runs, params["runs"], _cache_slots(c)):
        if kind == "M":
            state = cache[at] if cache is not None \
                else _mamba_state(c, b, n)
            x, state = _mamba_prefill(x, run, c, state)
            if new_cache is not None:
                new_cache[at] = state
        else:
            p = _layer(run, 0)
            x, entry = _attn_run(x, p, c,
                                 cache[at] if cache is not None else None,
                                 pos)
            if new_cache is not None:
                new_cache[at] = entry
            x = _mlp_in_blocks(x, p, c)
    return x, new_cache


def jamba_forward(params: Params, tokens: jax.Array, config: JambaConfig
                  ) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab] float32, no cache: every
    sequence from an empty state."""
    x, _ = _walk(params, tokens, config, None, 0)
    return _head(x, params, config)


def jamba_loss(params: Params, tokens: jax.Array, targets: jax.Array,
               config: JambaConfig, remat: bool = False) -> jax.Array:
    fwd = jamba_forward
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))
    logp = jax.nn.log_softmax(fwd(params, tokens, config), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def scan_blocks(config: JambaConfig, tokens: int) -> int:
    """The time blocks the scan walks over a run of `tokens`, all Mamba
    layers: what `forward_counted` reports."""
    layers = sum(n for kind, n in config.runs if kind == "M")
    return layers * -(-tokens // min(config.token_block, tokens))


def jamba_forward_counted(params: Params, tokens: jax.Array,
                          config: JambaConfig, cache: list, pos: Any):
    """tokens [B, T] at scalar position `pos` on top of what the cache
    holds: the state continues from the cache's, the attention sees the
    cache's rows below `pos` (a prompt from a concrete 0 sees itself
    alone). T == 1 is one step of `decode`. Returns (logits [B, 1, vocab]
    float32 of the LAST position, the new cache, {"scan_blocks"})."""
    c = config
    b, t = tokens.shape
    if t == 1:
        logits, new_cache = jamba_decode(
            params, tokens[:, 0], c, cache,
            jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
        return logits[:, None], new_cache, {"scan_blocks": jnp.int32(0)}
    x, new_cache = _walk(params, tokens, c, cache, pos)
    return (_head(x[:, -1:], params, c), new_cache,
            {"scan_blocks": jnp.int32(scan_blocks(c, t))})


def jamba_forward_cached(params: Params, tokens: jax.Array,
                         config: JambaConfig, cache: list, pos: Any):
    """`jamba_forward_counted` less its counters: the cache protocol's
    (logits, cache). The engine's prefill takes the counted form
    (`FAMILY.forward_counted`)."""
    return jamba_forward_counted(params, tokens, config, cache, pos)[:2]


def jamba_decode(params: Params, tokens: jax.Array, config: JambaConfig,
                 cache: list, pos_vec: jax.Array):
    """One step for a ragged batch: tokens [B], slot b at position
    pos_vec[b]. Returns (logits [B, vocab] float32, the new cache). A
    state cannot be un-advanced, so there is no [B, k+1] verify form."""
    c = config
    if tokens.ndim != 1:
        raise ValueError("a recurrent state cannot verify drafted tokens: "
                         "tokens must be [B]")
    x = params["tok_emb"][tokens[:, None]].astype(F32)
    positions = pos_vec[:, None]
    new_cache = list(cache)
    for (kind, _), run, at in zip(c.runs, params["runs"], _cache_slots(c)):
        if kind == "M":
            x, new_cache[at] = _mamba_run(
                x, run, c, cache[at],
                lambda x, p, ssm, tail: _mamba_tick(x, p, c, ssm, tail))
        else:
            p = _layer(run, 0)
            x, new_cache[at] = _attn_tick(x, p, c, cache[at], positions)
            x = _mlp(x, p, c)
    return _head(x[:, 0], params, c), new_cache


def jamba_partition_specs(config: JambaConfig) -> Params:
    """As the Llama path lays a block out, behind each run's layer axis."""
    norm = {"scale": P()}
    mlp = {"w_gate": P(None, "fsdp", "tp"), "w_up": P(None, "fsdp", "tp"),
           "w_down": P(None, "tp", "fsdp")}
    kinds = {
        "M": {"norm1": norm, "norm2": norm, "mlp": mlp, "mamba": {
            "w_in": P(None, "fsdp", None), "conv_w": P(), "conv_b": P(),
            "w_x": P(), "norm_dt": P(), "norm_b": P(), "norm_c": P(),
            "w_dt": P(), "dt_bias": P(), "A_log": P(), "D": P(),
            "w_out": P(None, None, "fsdp")}},
        "A": {"norm1": norm, "norm2": norm, "mlp": mlp, "attn": {
            "wq": P(None, "fsdp", "tp"), "wk": P(None, "fsdp", None),
            "wv": P(None, "fsdp", None), "wo": P(None, "tp", "fsdp")}},
    }
    return {"tok_emb": P("tp", "fsdp"), "norm_f": norm,
            "runs": [kinds[kind] for kind, _ in config.runs]}


FAMILY = Family(
    config_type=JambaConfig, init=jamba_init, forward=jamba_forward,
    loss=jamba_loss, partition_specs=jamba_partition_specs,
    init_cache=jamba_init_cache, forward_cached=jamba_forward_cached,
    decode=jamba_decode, forward_counted=jamba_forward_counted,
    decode_walks=True)
