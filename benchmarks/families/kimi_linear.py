"""Kimi-Linear (`conf["family"] == "kimi_linear"`; Moonshot AI's
Kimi-Linear-48B-A3B): from the published keys of a `model_type`
kimi_linear config.json to the program's `models/kimi_linear.py`: Kimi
Delta Attention and latent-attention mixers in the order
`linear_attn_config`'s two layer lists give, a dense SwiGLU in the first
`first_k_dense_replace` layers and the expert layer in the others. The
contract of this file is in `README.md` beside it.

The expert layers are one share of an expert-parallel deployment:
`num_experts` counts the experts HELD HERE, `expert_parallel_size` the
chips that share a layer (the router's width is their product), and
`expert_parallel_rank` (0 when absent) which run of experts this share
holds."""
from typing import Any, Dict

MODULE = "ray_tpu.models.kimi_linear"
INIT = "kimi_linear_init"
LOSS = "kimi_linear_loss"
PARTITION_SPECS = "kimi_linear_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
LOW_RANK = 128          # of the decay's and the gate's pairs (`assumed`)
# both kinds of mixer, a dense first layer, a chunk shorter than the
# rehearsal's 8- and 16-token prompts, 4 experts held of 16
TOY = {"hidden_size": 64, "num_hidden_layers": 4, "head_dim": 16,
       "linear_attn_config": {"full_attn_layers": [3], "head_dim": 16,
                              "kda_layers": [1, 2, 4], "num_heads": 4,
                              "short_conv_kernel_size": 4},
       "kda_chunk_size": 4, "num_attention_heads": 4,
       "num_key_value_heads": 4, "kv_lora_rank": 32,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "num_experts": 4, "expert_parallel_size": 4,
       "num_experts_per_token": 3, "vocab_size": 512,
       "model_max_length": 128}


def _router_width(conf: Dict[str, Any]) -> int:
    return conf["num_experts"] * conf.get("expert_parallel_size", 1)


def pattern(conf: Dict[str, Any]) -> str:
    """A character a layer, K or A, from the two 1-based layer lists."""
    lin = conf["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    layers = range(1, conf["num_hidden_layers"] + 1)
    if kda & full or kda | full != set(layers):
        raise ValueError(
            "the program's Kimi-Linear path has no layer that is not in "
            "exactly one of kda_layers and full_attn_layers")
    return "".join("K" if i in kda else "A" for i in layers)


def _low_rank(conf: Dict[str, Any]) -> int:
    return min(LOW_RANK, conf["linear_attn_config"]["head_dim"])


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.kimi_linear import KimiLinearConfig

    lin = conf["linear_attn_config"]
    refusals = {
        "activation other than silu, or a router other than sigmoid":
            (conf["hidden_act"], conf["moe_router_activation_func"])
            != ("silu", "sigmoid"),
        "rotary embedding in the latent layers (mla_use_nope false, or "
        "a rope_scaling)":
            not conf["mla_use_nope"] or conf["rope_scaling"] is not None,
        "low-rank query projection (q_lora_rank)":
            conf["q_lora_rank"] is not None,
        "expert groups (num_expert_group, topk_group other than 1)":
            (conf["num_expert_group"], conf["topk_group"]) != (1, 1),
        "dense layers among the expert layers (moe_layer_freq)":
            conf["moe_layer_freq"] != 1,
        "grouped keys and values in the latent layers":
            conf["num_key_value_heads"] != conf["num_attention_heads"],
        "tied head": bool(conf["tie_word_embeddings"]),
        "multi-token prediction modules (they only draft, and the engine "
        "refuses speculation over a recurrent state)":
            conf["num_nextn_predict_layers"] != 0,
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(
                f"the program's Kimi-Linear path has no {what}")
    if max_seq_len > conf["model_max_length"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['model_max_length']} positions")
    held = conf["num_experts"]
    return KimiLinearConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        pattern=pattern(conf), d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        first_dense=conf["first_k_dense_replace"],
        d_ff=conf["intermediate_size"],
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_low_rank=_low_rank(conf),
        conv_kernel=lin["short_conv_kernel_size"],
        chunk_size=conf["kda_chunk_size"],
        num_heads=conf["num_attention_heads"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_routed_experts=_router_width(conf), experts_held=held,
        first_expert=held * conf.get("expert_parallel_rank", 0),
        num_experts_per_tok=conf["num_experts_per_token"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        num_shared_experts=conf["num_shared_experts"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        norm_topk_prob=bool(conf["moe_renormalize"]))


def layer_params(conf: Dict[str, Any]) -> Dict[str, float]:
    """The matrix-multiplication parameters ONE token touches in a part
    of each kind on this share: the two mixers (K, A), the dense
    feed-forward part and the expert layer (E), where of a token's
    `num_experts_per_token` chosen experts the held share of the router's
    width falls here; `expert` is one routed expert."""
    d = conf["hidden_size"]
    lin = conf["linear_attn_config"]
    kda, lo = lin["num_heads"] * lin["head_dim"], _low_rank(conf)
    heads = conf["num_attention_heads"]
    q = heads * (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"])
    rank = conf["kv_lora_rank"]
    kvb = heads * (conf["qk_nope_head_dim"] + conf["v_head_dim"])
    expert = 3 * d * conf["moe_intermediate_size"]
    here = conf["num_experts_per_token"] * conf["num_experts"] \
        / _router_width(conf)
    return {
        # q, k, v, two low-rank pairs, beta, out
        "K": d * (3 * kda + 2 * lo + lin["num_heads"]) + 2 * lo * kda
        + kda * d,
        "A": d * q + d * (rank + conf["qk_rope_head_dim"]) + rank * kvb
        + heads * conf["v_head_dim"] * d,
        "dense": 3 * d * conf["intermediate_size"],
        "expert": expert,
        "E": d * _router_width(conf) + here * expert
        + conf["num_shared_experts"] * expert,
    }


def held_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The parameters this share holds, by where a decode tick meets
    them: `always` (read for every token: mixers with their convolutions,
    decays and norms, the dense part, each expert layer's router, bias
    and shared expert, the layer norms, the final norm and the head),
    `expert` (ONE routed expert), `experts` (all held, every expert
    layer), `embedding` (a tick gathers a row a slot)."""
    per = layer_params(conf)
    kinds = pattern(conf)
    d = conf["hidden_size"]
    lin = conf["linear_attn_config"]
    inner = lin["num_heads"] * lin["head_dim"]
    dense = conf["first_k_dense_replace"]
    sparse = conf["num_hidden_layers"] - dense
    width = _router_width(conf)
    small = {"K": lin["short_conv_kernel_size"] * 3 * inner  # convolutions
             + inner + lin["num_heads"] + lin["head_dim"],   # dt, A, norm
             "A": conf["kv_lora_rank"]}                      # latent norm
    always = (sum(per[k] + small[k] for k in kinds) + dense * per["dense"]
              + sparse * (d * width + width
                          + conf["num_shared_experts"] * per["expert"])
              + conf["num_hidden_layers"] * 2 * d + d
              + conf["vocab_size"] * d)
    return {"always": int(always), "expert": int(per["expert"]),
            "experts": int(sparse * conf["num_experts"] * per["expert"]),
            "embedding": conf["vocab_size"] * d}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    kinds = pattern(conf)
    dense = conf["first_k_dense_replace"]
    sparse = conf["num_hidden_layers"] - dense
    lin = conf["linear_attn_config"]
    inner = lin["num_heads"] * lin["head_dim"]
    held = held_params(conf)
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "head_dim": conf["head_dim"], "d_model": conf["hidden_size"],
            "vocab": conf["vocab_size"],
            "matmul_params": int(sum(per[k] for k in kinds)
                                 + dense * per["dense"] + sparse * per["E"]
                                 + conf["vocab_size"] * conf["hidden_size"]),
            # for this family's own readers
            "expert_layers": sparse,
            "experts_held": conf["num_experts"],
            # for harness/kimi_linear_cost.py: parameters by where a
            # tick meets them, and what a slot owns, in numbers (the
            # state float32; tails and latent rows as the weights)
            "always_params": held["always"],
            "expert_params": held["expert"],
            "held_params": held["always"] + held["experts"]
            + held["embedding"],
            "state_per_slot": kinds.count("K") * inner * lin["head_dim"],
            "tail_per_slot": kinds.count("K") * 3 * inner
            * (lin["short_conv_kernel_size"] - 1),
            "row_per_token": kinds.count("A")
            * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])}
