"""Granite 4.0-H (`conf["family"] == "granite_hybrid"`; IBM Granite 4.0):
from the published keys of a `model_type` granitemoehybrid config.json to
the program's `models/granite_hybrid.py`: Mamba-2 and attention mixers in
the order `layer_types` gives, an expert layer and a shared MLP in EVERY
layer, four multipliers. The contract of this file is in `README.md`
beside it.

The expert layers are one share of an expert-parallel deployment:
`num_local_experts` counts the experts HELD HERE, `expert_parallel_size`
the chips that share a layer (the router's width is their product), and
`expert_parallel_rank` (0 when absent) which run of experts this share
holds."""
from typing import Any, Dict

MODULE = "ray_tpu.models.granite_hybrid"
INIT = "granite_hybrid_init"
LOSS = "granite_hybrid_loss"
PARTITION_SPECS = "granite_hybrid_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
# both kinds of layer, a token block and a chunk shorter than the
# rehearsal's 8- and 16-token prompts, 4 experts held of 8
TOY = {"hidden_size": 64, "num_hidden_layers": 4,
       "layer_types": ["mamba", "mamba", "attention", "mamba"],
       "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
       "mamba_chunk_size": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16,
       "attention_multiplier": 0.0625, "num_local_experts": 4,
       "num_experts_per_tok": 3, "intermediate_size": 32,
       "shared_intermediate_size": 64, "vocab_size": 512,
       "max_position_embeddings": 128, "prefill_token_block": 6}
WEIGHT_BYTES = 2        # bf16 as served
STATE_BYTES = 4         # the float32 recurrence state
_KINDS = {"mamba": "M", "attention": "*"}


def _router_width(conf: Dict[str, Any]) -> int:
    return conf["num_local_experts"] * conf.get("expert_parallel_size", 1)


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    kinds = conf["layer_types"]
    refusals = {
        "a layer kind other than mamba and attention":
            bool(set(kinds) - set(_KINDS)),
        "num_hidden_layers is not the length of layer_types":
            len(kinds) != conf["num_hidden_layers"],
        "mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head":
            conf["mamba_expand"] * conf["hidden_size"]
            != conf["mamba_n_heads"] * conf["mamba_d_head"],
        "a position embedding (position_embedding_type other than nope)":
            conf["position_embedding_type"] != "nope",
        "activation other than silu": conf["hidden_act"] != "silu",
        "norm other than rmsnorm":
            conf["normalization_function"] != "rmsnorm",
        "a bias on a projection, or no bias on the convolution":
            bool(conf["attention_bias"] or conf["mamba_proj_bias"])
            or not conf["mamba_conv_bias"],
        "an untied head": not conf["tie_word_embeddings"],
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(f"the program's Granite hybrid path has no "
                             f"{what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    held = conf["num_local_experts"]
    return GraniteHybridConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        pattern="".join(_KINDS[k] for k in kinds),
        d_model=conf["hidden_size"], norm_eps=float(conf["rms_norm_eps"]),
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=float(conf["residual_multiplier"]),
        attention_multiplier=float(conf["attention_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        mamba_num_heads=conf["mamba_n_heads"],
        mamba_head_dim=conf["mamba_d_head"],
        mamba_d_state=conf["mamba_d_state"],
        mamba_n_groups=conf["mamba_n_groups"],
        mamba_d_conv=conf["mamba_d_conv"],
        mamba_chunk_size=conf["mamba_chunk_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        num_experts=_router_width(conf), experts_held=held,
        first_expert=held * conf.get("expert_parallel_rank", 0),
        num_experts_per_tok=conf["num_experts_per_tok"],
        intermediate_size=conf["intermediate_size"],
        shared_intermediate_size=conf["shared_intermediate_size"],
        prefill_token_block=conf["prefill_token_block"])


def layer_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The parameters of one layer's parts: the Mamba mixer's two
    matrices (`M`) and what else it holds (`M_rest`: the convolution and
    its bias, dt_bias, A_log, D, the inner norm), the attention's four
    matrices (`*`), the router, ONE expert, the shared MLP, a layer's two
    norms."""
    d = conf["hidden_size"]
    heads = conf["mamba_n_heads"]
    inner = heads * conf["mamba_d_head"]
    conv = inner + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
    attn = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return {"M": d * (inner + conv + heads) + inner * d,
            "M_rest": (conf["mamba_d_conv"] + 1) * conv + 3 * heads + inner,
            "*": 2 * d * attn + 2 * d * kv,
            "router": d * _router_width(conf),
            "expert": 3 * d * conf["intermediate_size"],
            "shared": 3 * d * conf["shared_intermediate_size"],
            "norms": 2 * d}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    kinds = conf["layer_types"]
    layers, d = conf["num_hidden_layers"], conf["hidden_size"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    held = conf["num_local_experts"]
    heads, p = conf["mamba_n_heads"], conf["mamba_d_head"]
    n, g = conf["mamba_d_state"], conf["mamba_n_groups"]
    conv = heads * p + 2 * g * n
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    head = conf["vocab_size"] * d
    # of a token's chosen experts, the held share of the router's width
    # falls here
    here = conf["num_experts_per_tok"] * held / _router_width(conf)
    mixers = n_m * per["M"] + n_a * per["*"]
    every = per["router"] + per["shared"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": hd, "d_model": d, "vocab": conf["vocab_size"],
            # what ONE token's matrix products touch
            "matmul_params": int(head + mixers + layers * (
                every + here * per["expert"])),
            "held_params": int(head + d + mixers + n_m * per["M_rest"]
                               + layers * (every + per["norms"]
                                           + held * per["expert"])),
            # for the readers: harness/granite_hybrid_cost.py and the
            # expert layers' that are there
            "kv_heads": kv, "expert_layers": layers, "experts_held": held,
            "expert_params": int(per["expert"]),
            # the scan's sizes under the harness's own names
            "scan_layers": n_m, "scan_heads": heads, "scan_head_dim": p,
            "scan_state": n, "scan_groups": g,
            "scan_chunk": conf["mamba_chunk_size"],
            # the weights every tick reads whatever is routed, in bytes
            # as served: the mixers, the routers, the shared MLPs, the
            # norms; the head once (the tied embedding: a tick gathers a
            # row a slot besides)
            "dense_bytes": int(WEIGHT_BYTES * (
                mixers + n_m * per["M_rest"]
                + layers * (every + per["norms"]))),
            "head_bytes": int(WEIGHT_BYTES * (head + d)),
            # a token's keys and values over the attention layers, a
            # slot's state and tails over the Mamba layers, as served
            "row_bytes": int(WEIGHT_BYTES * n_a * 2 * kv * hd),
            "state_bytes": int(n_m * (
                STATE_BYTES * heads * p * n
                + WEIGHT_BYTES * (conf["mamba_d_conv"] - 1) * conv))}
