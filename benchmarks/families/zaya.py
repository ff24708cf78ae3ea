"""ZAYA1 (`conf["family"] == "zaya"`; Zyphra ZAYA1-8B): from the
published keys of a `model_type` zaya config.json to the program's
`models/zaya.py`: every layer (`layer_types` "hybrid") an attention
sublayer in a compressed latent behind two carried convolutions (CCA,
`cca_time0` and `cca_time1` taps, `num_attention_heads` over
`num_key_value_heads` heads of `head_dim`, rotary over
`partial_rotary_factor` of a head) and an expert sublayer of `num_experts`
SwiGLU experts under an MLP router `router_hidden_size` wide, ONE choice a
token, which may be no expert; residual-scaled sums, a tied head. The
contract of this file is in `README.md` beside it."""
from typing import Any, Dict

MODULE = "ray_tpu.models.zaya"
INIT = "zaya_init"
LOSS = "zaya_loss"
PARTITION_SPECS = "zaya_partition_specs"
TRAIN_KERNELS = ()      # the family is only served
WEIGHT_BYTES = 2        # bf16, the cache's and the state's type too
ROUTER_BYTES = 4        # the router's matrices, float32
# two layers, so that the router's state carries from one to the next; 4
# query heads over 2 of 16, rotary over 8; 4 experts and the skip; blocks
# of 8 tokens, so that the rehearsal's 16-token prompts walk two
TOY = {"hidden_size": 64, "num_hidden_layers": 2,
       "layer_types": ["hybrid", "hybrid"], "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16,
       "moe_intermediate_size": 32, "num_experts": 4,
       "router_hidden_size": 16, "vocab_size": 512,
       "max_position_embeddings": 128, "ffn_token_block": 8}


def _rope(conf: Dict[str, Any]) -> Dict[str, Any]:
    return conf["rope_parameters"]["hybrid"]


def config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.zaya import ZayaConfig

    rope = _rope(conf)
    rotary = conf["head_dim"] * float(rope["partial_rotary_factor"])
    refusals = {
        "bias in the attention projections": bool(conf["attention_bias"]),
        "bias on the head": bool(conf["lm_head_bias"]),
        "untied head": not conf["tie_word_embeddings"],
        "activation other than silu": conf["hidden_act"] != "silu",
        "layer other than hybrid (an attention sublayer, then an expert "
        "sublayer), one entry a layer":
            list(conf["layer_types"])
            != ["hybrid"] * conf["num_hidden_layers"],
        "sliding window": conf["sliding_window"] is not None,
        "more than one expert a token": conf["num_experts_per_tok"] != 1,
        "rotary scaling other than the default":
            rope.get("rope_type", "default") != "default",
        "rotary share of a head that differs between the two places the "
        "file states it": float(conf["partial_rotary_factor"])
            != float(rope["partial_rotary_factor"]),
        "rotary part that is no even number of channels":
            rotary != int(rotary) or int(rotary) % 2 != 0,
    }
    for what, hit in refusals.items():
        if hit:
            raise ValueError(f"the program's ZAYA1 path has no {what}")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    return ZayaConfig(
        vocab_size=conf["vocab_size"], max_seq_len=int(max_seq_len),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        norm_eps=float(conf["rms_norm_eps"]),
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], rotary_dim=int(rotary),
        rope_theta=float(rope["rope_theta"]),
        cca_time0=conf["cca_time0"], cca_time1=conf["cca_time1"],
        num_experts=conf["num_experts"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        router_hidden_size=conf["router_hidden_size"],
        ffn_block=conf["ffn_token_block"])


def layer_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """The parameters of one layer's parts: its attention's matrices (A:
    the in-projection to both latents and both half-values, the grouped
    convolution, the out-projection), what else the attention holds
    (`A_rest`: the depthwise taps, both convolutions' biases, tau), the
    router's matrices (float32; `router_rest`: its norm, gamma, bias),
    ONE expert, and the layer's two norms and eight residual vectors."""
    d, hd = conf["hidden_size"], conf["head_dim"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    lat, r = (heads + kv) * hd, conf["router_hidden_size"]
    return {"A": d * (lat + kv * hd) + (heads + kv) * conf["cca_time1"]
            * hd * hd + heads * hd * d,
            "A_rest": conf["cca_time0"] * lat + 2 * lat + kv,
            "router": d * r + 2 * r * r + r * (conf["num_experts"] + 1),
            "router_rest": r + 1 + conf["num_experts"] + 1,
            "expert": 3 * d * conf["moe_intermediate_size"],
            "vectors": 10 * d}


def shape(conf: Dict[str, Any]) -> Dict[str, Any]:
    per = layer_params(conf)
    layers, d = conf["num_hidden_layers"], conf["hidden_size"]
    hd, kv = conf["head_dim"], conf["num_key_value_heads"]
    heads, held = conf["num_attention_heads"], conf["num_experts"]
    head = conf["vocab_size"] * d
    lat = (heads + kv) * hd
    return {"layers": layers, "heads": heads, "head_dim": hd, "d_model": d,
            "vocab": conf["vocab_size"],
            # what ONE token's matrix products touch: its one expert
            "matmul_params": int(head + layers * (
                per["A"] + per["router"] + per["expert"])),
            # for the readers: harness/zaya_cost.py and the expert
            # layers' that are there
            "kv_heads": kv, "expert_layers": layers, "experts_held": held,
            "expert_params": int(per["expert"]),
            # a layer's weights every tick reads whatever is routed, in
            # bytes as served; the head once (the tied embedding: a tick
            # gathers a row a slot besides)
            "layer_bytes": int(WEIGHT_BYTES * (
                per["A"] + per["A_rest"] + per["vectors"])
                + ROUTER_BYTES * (per["router"] + per["router_rest"])),
            "head_bytes": int(WEIGHT_BYTES * (head + d)),
            "held_params": int(head + d + layers * (
                sum(per.values()) - per["expert"]
                + held * per["expert"])),
            # a token's keys and values over all layers, a slot's state
            # over all layers, in bytes as served
            "row_bytes": int(WEIGHT_BYTES * layers * 2 * kv * hd),
            "state_bytes": int(WEIGHT_BYTES * layers * (
                (conf["cca_time0"] - 1 + conf["cca_time1"] - 1) * lat
                + kv * hd // 2))}
