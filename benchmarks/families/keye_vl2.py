"""Keye-VL-2.0 (`conf["family"] == "keye_vl2"`; Keye-VL-2.0-30B-A3B), its
language model: from the published keys of a `model_type` KeyeVL2
config.json to the program's `models/keye_vl2.py`: grouped-query attention
in every layer, per-head RMSNorms on queries and keys, whose queries
attend only the `sa_config.topk` rows a learned indexer picks (ONE index
key a token), and in every layer `num_experts` SwiGLU experts under a
softmax router, `num_experts_per_tok` a token, all of them held here. The
vision tower has no key in the published language-model config and is not
served: token ids in, logits out. The contract of this file is in
`README.md` beside it."""
from typing import Any, Dict

MODULE = "ray_tpu.models.keye_vl2"
INIT = "keye_vl2_init"
LOSS = "keye_vl2_loss"
PARTITION_SPECS = "keye_vl2_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
WEIGHT_BYTES = 2        # bf16, the cache's type too
# three layers; the best 12 rows of an indexer of 16 heads of 8 (with
# fewer heads a score is often exactly 0, ReLU after ReLU, and the ties the
# prompt form keeps the tick does not), crossed by the rehearsal's
# 16-token prompts and their answers; 4 heads over 2, a head of keys a
# call; 8 experts, 3 a token; every block shorter than the 16-token prompt
TOY = {"hidden_size": 64, "num_hidden_layers": 3, "max_window_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 3,
       "vocab_size": 512, "max_position_embeddings": 128,
       "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                        "type": "default"},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 16,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                     "q_chunk_size": 8, "topk": 12},
       "indexer_rope_dim": 4, "dsa_index_block": 16,
       "attention_head_group": 2, "ffn_token_block": 8}


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    sa, rope = conf["sa_config"], conf["rope_scaling"] or {}
    refusals = {
        "bias in the attention projections": bool(conf["attention_bias"]),
        "sliding window": bool(conf["use_sliding_window"])
        or conf["sliding_window"] is not None,
        "dense layers among the experts (decoder_sparse_step, "
        "mlp_only_layers)": conf["decoder_sparse_step"] != 1
        or bool(conf["mlp_only_layers"]),
        "tied head": bool(conf["tie_word_embeddings"]),
        "activation other than silu": conf["hidden_act"] != "silu",
        "expert weights that are not renormalised (norm_topk_prob false)":
            not conf["norm_topk_prob"],
        "experts held elsewhere (num_local_experts is not num_experts)":
            conf["num_local_experts"] != conf["num_experts"],
        "rotary scaling other than the default multimodal sections over "
        "half a head": rope.get("rope_type", "default") != "default"
        or sum(rope.get("mrope_section", [conf["head_dim"] // 2]))
        != conf["head_dim"] // 2,
        "more than one index key a token (indexer_num_kv_heads)":
            sa["indexer_num_kv_heads"] != 1,
        "prompt form whose blocks are not square (q_chunk_size is not "
        "kv_chunk_size)": sa["q_chunk_size"] != sa["kv_chunk_size"],
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(f"the program's Keye-VL-2.0 path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    return KeyeVL2Config(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], rope_theta=float(conf["rope_theta"]),
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_rope_dim=conf["indexer_rope_dim"], index_topk=sa["topk"],
        num_experts=conf["num_experts"],
        experts_held=conf["num_local_experts"], first_expert=0,
        num_experts_per_tok=conf["num_experts_per_tok"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        attn_block=sa["q_chunk_size"], index_block=conf["dsa_index_block"],
        head_group=conf["attention_head_group"],
        ffn_block=conf["ffn_token_block"])


def layer_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The matrix-multiplication parameters of one layer's parts: its
    attention (A), its indexer, the router, ONE expert."""
    d, sa = conf["hidden_size"], conf["sa_config"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return {"A": 2 * d * q + 2 * d * kv,
            "index": d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                          + sa["indexer_head_dim"]
                          + sa["indexer_num_heads"]),
            "router": d * conf["num_experts"],
            "expert": 3 * d * conf["moe_intermediate_size"]}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers, sa = conf["num_hidden_layers"], conf["sa_config"]
    d = conf["hidden_size"]
    head = conf["vocab_size"] * d
    # what every token of a decode tick reads, in the weights' type (the
    # routers apart: they are float32; NOT the embedding: a row a slot)
    always = layers * (per["A"] + per["index"]) + head
    held = conf["num_local_experts"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": conf["head_dim"], "d_model": d,
            "vocab": conf["vocab_size"],
            "matmul_params": int(always + layers * (
                per["router"] + conf["num_experts_per_tok"]
                * per["expert"])),
            # for the readers that are there, harness/dsa_cost.py and
            # harness/gqa_dsa_cost.py
            "kv_heads": conf["num_key_value_heads"],
            "expert_layers": layers, "experts_held": held,
            "always_params": int(always),
            "router_params": int(layers * per["router"]),
            "expert_params": int(per["expert"]),
            "held_params": int(always + head + layers * (
                per["router"] + held * per["expert"])),
            "full_layers": layers, "sliding_layers": 0,
            "value_dim": conf["head_dim"],
            "index_heads": sa["indexer_num_heads"],
            "index_dim": sa["indexer_head_dim"],
            "index_keep": sa["topk"],
            # a token's rows as the slab holds them, in numbers: its keys
            # and values of every head of them, its index key; no ring
            "row_full": 2 * conf["num_key_value_heads"] * conf["head_dim"],
            "row_index": sa["indexer_head_dim"], "row_ring": 0,
            # how a prompt goes through the program
            "index_block": conf["dsa_index_block"],
            "attn_block": sa["q_chunk_size"],
            "head_group": conf["attention_head_group"]}
