"""What a DeepSeek-V2 share holds, and the operations and bytes the
mathematics of its three hot parts needs, from the sizes its family's
`shape()` gives and from what a run met. Each is counted ONCE and at the
width it is served in, so a share computed from these numbers is a lower
reading and cannot pass 100% by over-counting.

  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline. Counted once a tick: the weights every token reads
    (`always_params` in bf16: latent attention, the dense part, every
    expert layer's shared experts, the norms, the head; the routers
    apart, float32. NOT the embedding: a tick gathers a row a slot); an
    expert's three matrices for each held expert that got a row
    (`moe_experts_hit`, summed over the expert layers); a cache row of
    every layer AS THE SLAB HOLDS IT, 640 numbers padded from 576, for
    each row a live slot holds (`live_rows`). Dead slots, unread experts
    and the rows past a slot's position count nothing. The absorbed
    attention's own operations (`tick_mla_flops`) are 218 for each byte
    of a row at 128 heads: its share of the compute peak stands beside
    the share of the memory roofline in `tick_bytes_roofline.tput`.
  the prompt's attention (`mla_prefill_flops`): one call of the kernel
    `mla_prefill_t<T>`, one layer's attention over a prompt of T tokens:
    q . k over d_n + d_r and p . v over d_v for the T (T + 1) / 2 pairs
    of the causal half alone. The blocks on the diagonal compute their
    upper halves too, and the kernel's padding rows: neither is counted.
  the prompt's experts (`moe_prefill_flops`, `moe_prefill_bytes`): the
    grouped products of the expert layers over a prompt: three products
    of D x I a token-expert pair that fell on a held expert, and each
    expert that got a row read once a LAYER (the program reads it once a
    block of `ffn_token_block` tokens: not counted), with the pair's row
    read and its result written at the model's width.
"""
from __future__ import annotations

from typing import Any, Dict

WEIGHT_BYTES = 2     # bf16
ROUTER_BYTES = 4     # float32


def held_bytes(shape: Dict[str, Any]) -> int:
    """Every parameter this share holds, as served."""
    return (WEIGHT_BYTES * (shape["held_params"] - shape["router_params"])
            + ROUTER_BYTES * shape["router_params"])


def slot_bytes(shape: Dict[str, Any], max_seq_len: int) -> int:
    """What a slot owns of the slab: `max_seq_len` padded rows a layer."""
    return WEIGHT_BYTES * max_seq_len * shape["row_per_token"]


def expert_bytes(shape: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return WEIGHT_BYTES * shape["expert_params"]


def tick_bytes(shape: Dict[str, Any], experts_hit: float,
               live_rows: float) -> float:
    """The least one decode tick moves (module docstring)."""
    return (WEIGHT_BYTES * shape["always_params"]
            + ROUTER_BYTES * shape["router_params"]
            + experts_hit * expert_bytes(shape)
            + WEIGHT_BYTES * live_rows * shape["row_per_token"])


def tick_mla_flops(shape: Dict[str, Any], live_rows: float) -> float:
    """The absorbed attention of one tick: for each cache row a live slot
    holds and each layer, every head's score against the row (rank + d_r
    wide) and its weighted sum of the row's latent (rank wide)."""
    per_row = shape["heads"] * 2 * (shape["row_unpadded"]
                                    + shape["latent_rank"])
    return shape["layers"] * per_row * live_rows


def mla_prefill_flops(shape: Dict[str, Any], tokens: int) -> float:
    """ONE layer's attention over a prompt, the causal half alone."""
    pairs = tokens * (tokens + 1) / 2.0
    return shape["heads"] * 2.0 * pairs * (shape["head_dim"]
                                           + shape["value_dim"])


def moe_prefill_flops(shape: Dict[str, Any], pairs_held: float) -> float:
    """The grouped products over a prompt: a multiply-add a parameter of
    the expert for each token-expert pair that fell on a held expert."""
    return 2.0 * pairs_held * shape["expert_params"]


def moe_prefill_bytes(shape: Dict[str, Any], pairs_held: float,
                      experts_hit: float) -> float:
    """Each expert that got a row once, each pair's row in and out."""
    return (experts_hit * expert_bytes(shape)
            + 2.0 * WEIGHT_BYTES * pairs_held * shape["d_model"])
