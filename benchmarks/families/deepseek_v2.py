"""DeepSeek-V2 (`conf["family"] == "deepseek_v2"`; DeepSeek-V2 236B-A21B):
from the published keys of a `model_type` deepseek_v2 config.json to the
program's `models/deepseek_v2.py`: latent attention with a low-rank query
and rotary positions under YaRN in every layer, a dense SwiGLU in the
first `first_k_dense_replace` layers and the group-routed expert layer in
the others. The contract of this file is in `README.md` beside it.

The expert layers are one share of an expert-parallel deployment:
`n_routed_experts` counts the experts HELD HERE, `expert_parallel_size`
the chips that share a layer (the router's width is their product, and
`n_group` divides THAT), and `expert_parallel_rank` (0 when absent) which
run of experts this share holds."""
from typing import Any, Dict

MODULE = "ray_tpu.models.deepseek_v2"
INIT = "deepseek_v2_init"
LOSS = "deepseek_v2_loss"
PARTITION_SPECS = "deepseek_v2_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
LANES = 128             # the cache row is padded to whole tiles of lanes
# a dense first layer and two expert layers; 16 experts in 4 groups of 4,
# the best 2 groups, 3 a token, 4 held; blocks shorter than the
# rehearsal's 16-token prompt, in the prompt form and the feed-forward
TOY = {"hidden_size": 64, "num_hidden_layers": 3, "intermediate_size": 96,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16,
       "moe_intermediate_size": 32, "n_routed_experts": 4,
       "expert_parallel_size": 4, "n_group": 4, "topk_group": 2,
       "num_experts_per_tok": 3, "vocab_size": 512,
       "max_position_embeddings": 128, "mla_prefill_block": 8,
       "ffn_token_block": 8,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                        "mscale": 0.707, "mscale_all_dim": 0.707,
                        "original_max_position_embeddings": 16,
                        "type": "yarn"}}


def _router_width(conf: Dict[str, Any]) -> int:
    return conf["n_routed_experts"] * conf.get("expert_parallel_size", 1)


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.deepseek_v2 import DeepseekV2Config

    yarn = conf["rope_scaling"]
    refusals = {
        "activation other than silu, or scores other than softmax":
            (conf["hidden_act"], conf["scoring_func"])
            != ("silu", "softmax"),
        "choice of experts other than group_limited_greedy":
            conf["topk_method"] != "group_limited_greedy",
        "renormalised expert weights (norm_topk_prob)":
            bool(conf["norm_topk_prob"]),
        "rotary scaling other than yarn":
            yarn is None or yarn["type"] != "yarn",
        "full-rank query projection (q_lora_rank null)":
            conf["q_lora_rank"] is None,
        "bias in the attention projections": bool(conf["attention_bias"]),
        "dense layers among the expert layers (moe_layer_freq)":
            conf["moe_layer_freq"] != 1,
        "grouped keys and values in the latent layers":
            conf["num_key_value_heads"] != conf["num_attention_heads"],
        "tied head": bool(conf["tie_word_embeddings"]),
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(
                f"the program's DeepSeek-V2 path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    held = conf["n_routed_experts"]
    return DeepseekV2Config(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        first_dense=conf["first_k_dense_replace"],
        d_ff=conf["intermediate_size"],
        num_heads=conf["num_attention_heads"],
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        attn_block=conf["mla_prefill_block"],
        rope_theta=float(conf["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_max=yarn["original_max_position_embeddings"],
        rope_beta_fast=float(yarn["beta_fast"]),
        rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=float(yarn["mscale"]),
        rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
        n_routed_experts=_router_width(conf), experts_held=held,
        first_expert=held * conf.get("expert_parallel_rank", 0),
        num_experts_per_tok=conf["num_experts_per_tok"],
        n_group=conf["n_group"], topk_group=conf["topk_group"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        ffn_block=conf["ffn_token_block"])


def layer_params(conf: Dict[str, Any]) -> Dict[str, float]:
    """The matrix-multiplication parameters ONE token touches in a part
    of each kind on this share: latent attention (A), the dense
    feed-forward part, and the expert layer (E), where of a token's
    `num_experts_per_tok` chosen experts the held share of the router's
    width falls here; `expert` is one routed expert, `router` the gate."""
    d = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    q = heads * (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"])
    rank = conf["kv_lora_rank"]
    kvb = heads * (conf["qk_nope_head_dim"] + conf["v_head_dim"])
    expert = 3 * d * conf["moe_intermediate_size"]
    here = conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / _router_width(conf)
    router = d * _router_width(conf)
    return {
        "A": d * conf["q_lora_rank"] + conf["q_lora_rank"] * q
        + d * (rank + conf["qk_rope_head_dim"]) + rank * kvb
        + heads * conf["v_head_dim"] * d,
        "dense": 3 * d * conf["intermediate_size"],
        "expert": expert, "router": router,
        "shared": conf["n_shared_experts"] * expert,
        "E": router + here * expert + conf["n_shared_experts"] * expert,
    }


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers = conf["num_hidden_layers"]
    dense = conf["first_k_dense_replace"]
    sparse = layers - dense
    d = conf["hidden_size"]
    head = conf["vocab_size"] * d
    # the two norms of the low-rank paths, the two of a layer, the last
    norms = layers * (conf["q_lora_rank"] + conf["kv_lora_rank"] + 2 * d) + d
    # what every token of a decode tick reads, in the weights' type (the
    # routers apart: they are float32); NOT the embedding (a row a slot)
    always = (layers * per["A"] + dense * per["dense"]
              + sparse * per["shared"] + norms + head)
    row = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
            "d_model": d, "vocab": conf["vocab_size"],
            "matmul_params": int(layers * per["A"] + dense * per["dense"]
                                 + sparse * per["E"] + head),
            # for this family's own readers and harness/deepseek_v2_cost.py
            "expert_layers": sparse,
            "experts_held": conf["n_routed_experts"],
            "always_params": int(always),
            "router_params": int(sparse * per["router"]),
            "expert_params": int(per["expert"]),
            "held_params": int(always + sparse * per["router"] + head
                               + sparse * conf["n_routed_experts"]
                               * per["expert"]),
            # a cache row as the slab holds it: padded to whole tiles
            "row_per_token": layers * (-(-row // LANES) * LANES),
            "row_unpadded": row,
            "value_dim": conf["v_head_dim"],
            "latent_rank": conf["kv_lora_rank"]}
