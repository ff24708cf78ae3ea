"""dots3-note (`conf["family"] == "dots3_note"`; dots3-note-prev
288B-A17B), its language model: from the published keys of a `model_type`
dots3_note config.json to the program's `models/dots3_note.py`: latent
attention in every layer at one of two geometries (`layer_types`): a
full layer's, whose queries attend only the `index_topk` rows a learned
indexer picks, and a sliding layer's (`swa_*`), whose queries see the
last `sliding_window_size` positions; a headwise output gate on both; a
dense SwiGLU in the first `first_k_dense_replace` layers and the
sigmoid-routed expert layer in the others. The contract of this file is
in `README.md` beside it.

The expert layers are one share of an expert-parallel deployment:
`n_routed_experts` counts the experts HELD HERE, `expert_parallel_size`
the chips that share a layer (the router's width is their product), and
`expert_parallel_rank` (0 when absent) which run of experts this share
holds."""
from typing import Any, Dict

MODULE = "ray_tpu.models.dots3_note"
INIT = "dots3_note_init"
LOSS = "dots3_note_loss"
PARTITION_SPECS = "dots3_note_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
LANES = 128             # cache rows and rings are whole tiles
# a dense full layer, then a period: full, sliding, sliding, sliding; a
# window of 9 and the best 12 rows, both crossed by the rehearsal's
# prompts; 16 experts, 3 a token, 4 held; every block shorter than the
# rehearsal's 16-token prompt
TOY = {"hidden_size": 64, "num_hidden_layers": 5, "intermediate_size": 96,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16,
       "index_n_heads": 8, "index_head_dim": 16, "index_topk": 12,
       "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
       "swa_q_lora_rank": 24, "swa_kv_lora_rank": 40,
       "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
       "swa_v_head_dim": 16, "sliding_window_size": 9,
       "moe_intermediate_size": 32, "n_routed_experts": 4,
       "expert_parallel_size": 4, "num_experts_per_tok": 3,
       "vocab_size": 512, "max_position_embeddings": 128,
       "dsa_prefill_block": 8, "dsa_index_block": 16,
       "attention_head_group": 2, "ffn_token_block": 8,
       "cache_row_tile": 4}


def _router_width(conf: Dict[str, Any]) -> int:
    return conf["n_routed_experts"] * conf.get("expert_parallel_size", 1)


def _full(conf: Dict[str, Any]) -> list:
    return [int(kind == "full_attention") for kind in conf["layer_types"]]


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.dots3_note import Dots3NoteConfig

    kinds = set(conf["layer_types"])
    refusals = {
        "activation other than silu, or scores other than sigmoid":
            (conf["hidden_act"], conf["scoring_func"])
            != ("silu", "sigmoid"),
        "choice of experts other than noaux_tc":
            conf["topk_method"] != "noaux_tc",
        "rotary scaling": conf["rope_scaling"] is not None,
        "bias in the attention projections": bool(conf["attention_bias"]),
        "output gate other than headwise":
            {conf["attention_gate_type"], conf["swa_attention_gate_type"]}
            != {"headwise"},
        "layer kind other than full_attention and sliding_attention":
            not kinds <= {"full_attention", "sliding_attention"},
        "dense layers among the expert layers (moe_layer_freq)":
            conf["moe_layer_freq"] != 1,
        "grouped keys and values in the latent layers":
            conf["num_key_value_heads"] != conf["num_attention_heads"]
            or conf["swa_num_key_value_heads"]
            != conf["swa_num_attention_heads"],
        "tied head": bool(conf["tie_word_embeddings"]),
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(
                f"the program's dots3-note path has no {what}")
    if len(conf["layer_types"]) != conf["num_hidden_layers"]:
        raise ValueError("layer_types has not one entry a layer")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    held = conf["n_routed_experts"]
    return Dots3NoteConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        full_layout=tuple(_full(conf)),
        first_dense=conf["first_k_dense_replace"],
        d_ff=conf["intermediate_size"],
        lora_rescale=bool(conf["apply_mla_qkv_lora_rescale"]),
        num_heads=conf["num_attention_heads"],
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_theta=float(conf["rope_theta"]),
        index_heads=conf["index_n_heads"],
        index_dim=conf["index_head_dim"], index_topk=conf["index_topk"],
        swa_num_heads=conf["swa_num_attention_heads"],
        swa_q_lora_rank=conf["swa_q_lora_rank"],
        swa_kv_lora_rank=conf["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=conf["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=conf["swa_qk_rope_head_dim"],
        swa_v_head_dim=conf["swa_v_head_dim"],
        swa_rope_theta=float(conf["swa_rope_theta"]),
        window=conf["sliding_window_size"],
        n_routed_experts=_router_width(conf), experts_held=held,
        first_expert=held * conf.get("expert_parallel_rank", 0),
        num_experts_per_tok=conf["num_experts_per_tok"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        attn_block=conf["dsa_prefill_block"],
        index_block=conf["dsa_index_block"],
        head_group=conf["attention_head_group"],
        ffn_block=conf["ffn_token_block"],
        row_tile=conf.get("cache_row_tile", LANES))


def _attention(d: int, heads: int, q_rank: int, kv_rank: int, d_n: int,
               d_r: int, d_v: int) -> int:
    """One layer's latent attention with its gate."""
    return (d * q_rank + q_rank * heads * (d_n + d_r)
            + d * (kv_rank + d_r) + kv_rank * heads * (d_n + d_v)
            + d * heads + heads * d_v * d)


def layer_params(conf: Dict[str, Any]) -> Dict[str, float]:
    """The matrix-multiplication parameters ONE token touches in a part
    of each kind on this share: a full layer's attention with its indexer
    (F) and a sliding layer's (S), the dense feed-forward part, and the
    expert layer (E), where of a token's `num_experts_per_tok` chosen
    experts the held share of the router's width falls here."""
    d = conf["hidden_size"]
    index = (conf["q_lora_rank"] * conf["index_n_heads"]
             * conf["index_head_dim"] + d * conf["index_head_dim"]
             + d * conf["index_n_heads"])
    expert = 3 * d * conf["moe_intermediate_size"]
    here = conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / _router_width(conf)
    router = d * _router_width(conf)
    shared = conf["n_shared_experts"] * expert
    return {
        "F": _attention(d, conf["num_attention_heads"], conf["q_lora_rank"],
                        conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                        conf["qk_rope_head_dim"], conf["v_head_dim"]) + index,
        "index": index,
        "S": _attention(d, conf["swa_num_attention_heads"],
                        conf["swa_q_lora_rank"], conf["swa_kv_lora_rank"],
                        conf["swa_qk_nope_head_dim"],
                        conf["swa_qk_rope_head_dim"], conf["swa_v_head_dim"]),
        "dense": 3 * d * conf["intermediate_size"],
        "expert": expert, "router": router, "shared": shared,
        "E": router + here * expert + shared,
    }


def _tiles(n: int, tile: int = LANES) -> int:
    return -(-n // tile) * tile


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers = conf["num_hidden_layers"]
    full = sum(_full(conf))
    sliding = layers - full
    dense = conf["first_k_dense_replace"]
    sparse = layers - dense
    d = conf["hidden_size"]
    head = conf["vocab_size"] * d
    attention = full * per["F"] + sliding * per["S"]
    # what every token of a decode tick reads, in the weights' type (the
    # routers apart: they are float32); NOT the embedding (a row a slot)
    always = attention + dense * per["dense"] + sparse * per["shared"] + head
    window = conf["sliding_window_size"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
            "d_model": d, "vocab": conf["vocab_size"],
            "matmul_params": int(attention + dense * per["dense"]
                                 + sparse * per["E"] + head),
            # for this family's own readers and harness/dsa_cost.py
            "expert_layers": sparse,
            "experts_held": conf["n_routed_experts"],
            "always_params": int(always),
            "router_params": int(sparse * per["router"]),
            "expert_params": int(per["expert"]),
            "held_params": int(always + sparse * per["router"] + head
                               + sparse * conf["n_routed_experts"]
                               * per["expert"]),
            "full_layers": full, "sliding_layers": sliding,
            "value_dim": conf["v_head_dim"],
            "index_heads": conf["index_n_heads"],
            "index_dim": conf["index_head_dim"],
            "index_keep": conf["index_topk"],
            "window": window,
            # a token's rows as the slab holds them, in numbers
            "row_full": _tiles(conf["kv_lora_rank"]
                               + conf["qk_rope_head_dim"]),
            "row_index": conf["index_head_dim"],
            "row_ring": _tiles(conf["swa_kv_lora_rank"]
                               + conf["swa_qk_rope_head_dim"]),
            "ring_rows": _tiles(window, conf.get("cache_row_tile", LANES)),
            # how a prompt goes through the program
            "index_block": conf["dsa_index_block"],
            "attn_block": conf["dsa_prefill_block"],
            "head_group": conf["attention_head_group"]}
