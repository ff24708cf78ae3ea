"""SmallThinker (`conf["family"] == "smallthinker"`; SmallThinker-21BA3B
and its 4BA0.6B sibling): from the published keys of a `model_name`
smallthinker config.json to the program's `models/smallthinker.py`:
grouped-query attention in every layer, `sliding_window_layout[i]` saying
whether layer i sees only `sliding_window_size` positions and
`rope_layout[i]` whether its queries and keys take rotary positions, an
expert layer of `moe_num_primary_experts` ReGLU experts in every layer,
whose router reads the layer's input ahead of the attention. The contract
of this file is in `README.md` beside it."""
from typing import Any, Dict

MODULE = "ray_tpu.models.smallthinker"
INIT = "smallthinker_init"
LOSS = "smallthinker_loss"
PARTITION_SPECS = "smallthinker_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
WEIGHT_BYTES = 2        # bf16, the cache's type too
# one period and a layer (G W W W G); a window of 4 and blocks of 4, so
# that the rehearsal's 8- and 16-token prompts cross the window in the
# prefill (the band masks part of a block and hides one whole) and wrap
# the ring in the decode; 8 experts, 3 a token; feed-forward blocks
# shorter than the 16-token prompt
TOY = {"hidden_size": 64, "num_hidden_layers": 5,
       "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
       "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
       "moe_num_active_primary_experts": 3, "vocab_size": 512,
       "max_position_embeddings": 128, "sliding_window_size": 4,
       "sliding_window_layout": [0, 1, 1, 1, 0],
       "rope_layout": [0, 1, 1, 1, 0],
       "attn_prefill_block": 4, "ffn_token_block": 8}


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    layers = conf["num_hidden_layers"]
    refusals = {
        "tied head": bool(conf["tie_word_embeddings"]),
        "bias in a projection": bool(conf.get("attention_bias", False)),
        "router without the softmax over the chosen "
        "(moe_primary_router_apply_softmax false)":
            not conf["moe_primary_router_apply_softmax"],
        "expert weights that are not renormalised (norm_topk_prob false)":
            not conf["norm_topk_prob"],
        "rotary scaling": conf["rope_scaling"] is not None,
        "layout whose length is not num_hidden_layers":
            len(conf["sliding_window_layout"]) != layers
            or len(conf["rope_layout"]) != layers,
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(
                f"the program's SmallThinker path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    return SmallThinkerConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=layers, d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        window_layout=tuple(int(x) for x in conf["sliding_window_layout"]),
        rope_layout=tuple(int(x) for x in conf["rope_layout"]),
        window=conf["sliding_window_size"],
        rope_theta=float(conf["rope_theta"]),
        attn_block=conf["attn_prefill_block"],
        num_experts=conf["moe_num_primary_experts"],
        num_experts_per_tok=conf["moe_num_active_primary_experts"],
        moe_intermediate_size=conf["moe_ffn_hidden_size"],
        ffn_block=conf["ffn_token_block"])


def layer_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The matrix-multiplication parameters of one layer's parts: its
    attention (A), the router, ONE expert."""
    d = conf["hidden_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return {"A": 2 * d * q + 2 * d * kv,
            "router": d * conf["moe_num_primary_experts"],
            "expert": 3 * d * conf["moe_ffn_hidden_size"]}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers = conf["num_hidden_layers"]
    d = conf["hidden_size"]
    head = conf["vocab_size"] * d
    windowed = sum(int(x) for x in conf["sliding_window_layout"])
    active = conf["moe_num_active_primary_experts"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": conf["head_dim"], "d_model": d,
            "vocab": conf["vocab_size"],
            "matmul_params": int(layers * (per["A"] + per["router"]
                                           + active * per["expert"])
                                 + head),
            # for this family's own readers and
            # harness/smallthinker_cost.py
            "kv_heads": conf["num_key_value_heads"],
            "expert_layers": layers,
            "experts_held": conf["moe_num_primary_experts"],
            "expert_params": int(per["expert"]),
            "router_params": int(layers * per["router"]),
            # what every token of a decode tick reads, in the weights'
            # type: the attention, the norms, the head (the routers
            # apart: they are float32; NOT the embedding: a row a slot)
            "always_params": int(layers * (per["A"] + 2 * d) + d + head),
            "window": conf["sliding_window_size"],
            "layers_window": windowed,
            "layers_global": layers - windowed,
            # keys and values of one token in one layer, as the slab
            # holds them
            "row_bytes": 2 * conf["num_key_value_heads"]
            * conf["head_dim"] * WEIGHT_BYTES}
