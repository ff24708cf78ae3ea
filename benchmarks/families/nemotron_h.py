"""Nemotron-H (`conf["family"] == "nemotron_h"`; NVIDIA Nemotron-3): from
the published keys of a `model_type` nemotron_h config.json to the
program's `models/nemotron_h.py`: Mamba-2, attention and LatentMoE layers
in the order `hybrid_override_pattern` gives. The contract of this file is
in `README.md` beside it.

The expert layers are one share of an expert-parallel deployment:
`n_routed_experts` counts the experts HELD HERE, `expert_parallel_size` the
chips that share a layer (the router's width is their product), and
`expert_parallel_rank` (0 when absent) which run of experts this share
holds."""
from typing import Any, Dict

MODULE = "ray_tpu.models.nemotron_h"
INIT = "nemotron_h_init"
LOSS = "nemotron_h_loss"
PARTITION_SPECS = "nemotron_h_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
# every kind of layer, a chunk shorter than the rehearsal's 8- and
# 16-token prompts, 4 experts held of 16
TOY = {"hidden_size": 64, "num_hidden_layers": 5,
       "hybrid_override_pattern": "MEM*E", "mamba_num_heads": 8,
       "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
       "chunk_size": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "n_routed_experts": 4, "expert_parallel_size": 4,
       "num_experts_per_tok": 3, "moe_intermediate_size": 32,
       "intermediate_size": 32, "moe_latent_size": 32,
       "moe_shared_expert_intermediate_size": 64, "vocab_size": 512,
       "max_position_embeddings": 128}


def _router_width(conf: Dict[str, Any]) -> int:
    return conf["n_routed_experts"] * conf.get("expert_parallel_size", 1)


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.nemotron_h import NemotronHConfig

    pattern = conf["hybrid_override_pattern"]
    refusals = {
        "a layer kind other than M, E and * in the pattern":
            bool(set(pattern) - set("ME*")),
        "num_hidden_layers is not the pattern's length":
            len(pattern) != conf["num_hidden_layers"],
        "expand x hidden_size is not mamba_num_heads x mamba_head_dim":
            conf["expand"] * conf["hidden_size"]
            != conf["mamba_num_heads"] * conf["mamba_head_dim"],
        "expert groups (n_group, topk_group other than 1)":
            (conf["n_group"], conf["topk_group"]) != (1, 1),
        "activations other than relu2 (experts) and silu (Mamba)":
            (conf["mlp_hidden_act"], conf["mamba_hidden_act"])
            != ("relu2", "silu"),
        "a bias on a projection, or no bias on the convolution":
            any(conf[k] for k in ("attention_bias", "mlp_bias", "use_bias",
                                  "mamba_proj_bias"))
            or not conf["use_conv_bias"],
        "a tied head, a sliding window, a residual kept in float32":
            bool(conf["tie_word_embeddings"] or conf["residual_in_fp32"])
            or conf["sliding_window"] is not None,
        "other than one shared expert": conf["n_shared_experts"] != 1,
        "expert widths that differ (intermediate_size, "
        "moe_intermediate_size)":
            conf["intermediate_size"] != conf["moe_intermediate_size"],
        "two norm epsilons (norm_eps, layer_norm_epsilon)":
            conf["norm_eps"] != conf["layer_norm_epsilon"],
        "multi-token prediction modules (they only draft, and the engine "
        "refuses speculation over a recurrent state)":
            conf["num_nextn_predict_layers"] != 0,
        "more logits of a prefill than the last (num_logits_to_keep)":
            conf["num_logits_to_keep"] != 1,
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(f"the program's Nemotron-H path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    held = conf["n_routed_experts"]
    return NemotronHConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        pattern=pattern, d_model=conf["hidden_size"],
        norm_eps=float(conf["norm_eps"]),
        mamba_num_heads=conf["mamba_num_heads"],
        mamba_head_dim=conf["mamba_head_dim"],
        ssm_state_size=conf["ssm_state_size"], n_groups=conf["n_groups"],
        conv_kernel=conf["conv_kernel"], chunk_size=conf["chunk_size"],
        time_step_min=float(conf["time_step_min"]),
        time_step_max=float(conf["time_step_max"]),
        time_step_floor=float(conf["time_step_floor"]),
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        n_routed_experts=_router_width(conf), experts_held=held,
        first_expert=held * conf.get("expert_parallel_rank", 0),
        num_experts_per_tok=conf["num_experts_per_tok"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        moe_latent_size=conf["moe_latent_size"],
        moe_shared_expert_intermediate_size=conf[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        norm_topk_prob=bool(conf["norm_topk_prob"]))


def layer_params(conf: Dict[str, Any]) -> Dict[str, float]:
    """The matrix-multiplication parameters ONE token touches in a layer
    of each kind on this share: of a token's `num_experts_per_tok` chosen
    experts, the held share of the router's width falls here."""
    d = conf["hidden_size"]
    inner = conf["mamba_num_heads"] * conf["mamba_head_dim"]
    conv = inner + 2 * conf["n_groups"] * conf["ssm_state_size"]
    attn = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    lat, wide = conf["moe_latent_size"], conf["moe_intermediate_size"]
    here = conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / _router_width(conf)
    return {
        "M": d * (inner + conv + conf["mamba_num_heads"]) + inner * d,
        "*": 2 * d * attn + 2 * d * kv,
        "E": d * _router_width(conf) + 2 * d * lat
        + here * 2 * lat * wide
        + 2 * d * conf["moe_shared_expert_intermediate_size"],
    }


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    pattern = conf["hybrid_override_pattern"]
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "head_dim": conf["head_dim"], "d_model": conf["hidden_size"],
            "vocab": conf["vocab_size"],
            "matmul_params": int(sum(per[k] for k in pattern)
                                 + conf["vocab_size"] * conf["hidden_size"]),
            # for this family's own readers
            "expert_layers": pattern.count("E"),
            "experts_held": conf["n_routed_experts"]}
