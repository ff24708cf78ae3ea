"""Jamba (`conf["family"] == "jamba"`; AI21 Jamba and Jamba2): from the
published keys of a `model_type` jamba config.json to the program's
`models/jamba.py`: layers of two sublayers, a Mamba-1 mixer (attention
where `i % attn_layer_period == attn_layer_offset`) and a dense SwiGLU,
under a tied head. The contract of this file is in `README.md` beside
it."""
from typing import Any, Dict

MODULE = "ray_tpu.models.jamba"
INIT = "jamba_init"
LOSS = "jamba_loss"
PARTITION_SPECS = "jamba_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
WEIGHT_BYTES = 2        # bf16, the cache's type too
STATE_BYTES = 4         # the recurrence state, float32
# both kinds of layer and a period shorter than the depth (M A M M A:
# runs of one and of two Mamba layers); 128 channels, so that the
# rehearsal's interpret mode takes the kernel; a block of 4 tokens, so
# that the rehearsal's 8- and 16-token prompts walk several
TOY = {"hidden_size": 64, "num_hidden_layers": 5, "attn_layer_period": 3,
       "attn_layer_offset": 1, "num_attention_heads": 4,
       "num_key_value_heads": 1, "intermediate_size": 96,
       "mamba_d_state": 4, "mamba_dt_rank": 8, "vocab_size": 512,
       "max_position_embeddings": 128, "prefill_token_block": 4}


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.jamba import JambaConfig

    refusals = {
        "expert layers (num_experts other than 1)":
            (conf["num_experts"], conf["num_experts_per_tok"]) != (1, 1),
        "sliding window": conf["sliding_window"] is not None,
        "untied head": not conf["tie_word_embeddings"],
        "bias on the Mamba projections": bool(conf["mamba_proj_bias"]),
        "convolution without a bias": not conf["mamba_conv_bias"],
        "activation other than silu": conf["hidden_act"] != "silu",
        "more logits of a prefill than the last (num_logits_to_keep)":
            conf["num_logits_to_keep"] != 1,
        "heads that do not divide the hidden size":
            conf["hidden_size"] % conf["num_attention_heads"] != 0,
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(f"the program's Jamba path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    return JambaConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        attn_period=conf["attn_layer_period"],
        attn_offset=conf["attn_layer_offset"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], mamba_expand=conf["mamba_expand"],
        mamba_d_state=conf["mamba_d_state"],
        mamba_dt_rank=conf["mamba_dt_rank"],
        mamba_d_conv=conf["mamba_d_conv"],
        token_block=conf["prefill_token_block"])


def attention_layers(conf: Dict[str, Any]) -> int:
    return sum(i % conf["attn_layer_period"] == conf["attn_layer_offset"]
               for i in range(conf["num_hidden_layers"]))


def layer_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The parameters of a layer's parts: the matrices of a Mamba mixer
    (M), what else it holds (the convolution and its bias, dt's bias,
    A_log, D, the three inner norms: `M_rest`), an attention mixer (A),
    the feed-forward (mlp), a layer's two norms."""
    d = conf["hidden_size"]
    ci = conf["mamba_expand"] * d
    n, r, k = (conf["mamba_d_state"], conf["mamba_dt_rank"],
               conf["mamba_d_conv"])
    head = d // conf["num_attention_heads"]
    return {"M": d * 2 * ci + ci * (r + 2 * n) + r * ci + ci * d,
            "M_rest": k * ci + ci + ci + n * ci + ci + r + 2 * n,
            "A": 2 * d * d + 2 * d * conf["num_key_value_heads"] * head,
            "mlp": 3 * d * conf["intermediate_size"], "norms": 2 * d}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers, d = conf["num_hidden_layers"], conf["hidden_size"]
    attn = attention_layers(conf)
    mamba = layers - attn
    ci = conf["mamba_expand"] * d
    n, k = conf["mamba_d_state"], conf["mamba_d_conv"]
    head = d // conf["num_attention_heads"]
    emb = conf["vocab_size"] * d
    matrices = mamba * per["M"] + attn * per["A"] + layers * per["mlp"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": head, "d_model": d, "vocab": conf["vocab_size"],
            # the embedding once: it is the head
            "matmul_params": int(matrices + emb),
            # for this family's own readers and harness/jamba_cost.py
            "kv_heads": conf["num_key_value_heads"],
            "mamba_layers": mamba, "attention_layers": attn,
            "d_inner": ci, "d_state": n, "d_conv": k,
            "token_block": conf["prefill_token_block"],
            # every parameter held, and those of them held in float32
            # (A_log, D, dt's bias)
            "params": int(matrices + mamba * per["M_rest"]
                          + layers * per["norms"] + emb + d),
            "float32_params": int(mamba * (n * ci + 2 * ci)),
            # a slot's state: the recurrence state and the
            # convolution's tail of every Mamba layer
            "state_bytes": int(mamba * (n * ci * STATE_BYTES
                                        + (k - 1) * ci * WEIGHT_BYTES)),
            # keys and values of one token over the attention layers
            "row_bytes": int(attn * 2 * conf["num_key_value_heads"] * head
                             * WEIGHT_BYTES)}
