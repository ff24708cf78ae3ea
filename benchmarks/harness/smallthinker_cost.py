"""What a SmallThinker share holds, and the operations and bytes the
mathematics of its hot parts needs, from the sizes its family's `shape()`
gives and from what a run met. Each is counted ONCE and at the width it
is served in, so a share computed from these numbers is a lower reading
and cannot pass 100% by over-counting.

  the slab (`slot_bytes`, `slab_rows`): a slot owns `max_seq_len` rows of
    keys and values in each global layer and `min(window, max_seq_len)`
    in each window layer, `row_bytes` a row.
  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline. Counted once a tick: the weights every token reads
    (`always_params` in bf16: the attention, the norms, the head; the
    routers apart, float32. NOT the embedding: a tick gathers a row a
    slot); an expert's three matrices for each expert that got a row
    (`moe_experts_hit`, summed over the layers); for each live slot its
    `position` rows of every global layer (`live_rows`) and `min(position,
    window)` rows of every window layer (`live_rows_window`). Dead slots,
    unread experts and the rows past a slot's position count nothing.
  the prompt's attention (`gqa_prefill_flops`): one call of the kernel
    `gqa_prefill_w<W>_t<T>`, one layer's attention over a prompt of T
    tokens: q . k and p . v over `head_dim` for every query head and
    every VISIBLE pair (`visible_pairs`): the causal half for `w0`, the
    band for a window. What the kernel computes and masks in the blocks
    the diagonal or the band's edge crosses, and its padding rows, are
    not counted.
"""
from __future__ import annotations

from typing import Any, Dict

WEIGHT_BYTES = 2     # bf16
ROUTER_BYTES = 4     # float32


def held_bytes(shape: Dict[str, Any]) -> int:
    """Every parameter held, as served: what a tick always reads, the
    embedding (as large as the head), every expert, the routers."""
    experts = shape["expert_layers"] * shape["experts_held"] \
        * shape["expert_params"]
    return (WEIGHT_BYTES * (shape["always_params"]
                            + shape["vocab"] * shape["d_model"] + experts)
            + ROUTER_BYTES * shape["router_params"])


def slab_rows(shape: Dict[str, Any], max_seq_len: int) -> int:
    """The rows of keys and values a slot owns, over all layers: a window
    layer keeps `min(window, max_seq_len)`."""
    return (shape["layers_global"] * max_seq_len
            + shape["layers_window"] * min(shape["window"], max_seq_len))


def slot_bytes(shape: Dict[str, Any], max_seq_len: int) -> int:
    return shape["row_bytes"] * slab_rows(shape, max_seq_len)


def expert_bytes(shape: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return WEIGHT_BYTES * shape["expert_params"]


def live_rows_read(shape: Dict[str, Any], live_rows: float,
                   live_rows_window: float) -> float:
    """The rows of the slab the live slots of a tick need, all layers."""
    return (shape["layers_global"] * live_rows
            + shape["layers_window"] * live_rows_window)


def tick_bytes(shape: Dict[str, Any], experts_hit: float, live_rows: float,
               live_rows_window: float) -> float:
    """The least one decode tick moves (module docstring)."""
    return (WEIGHT_BYTES * shape["always_params"]
            + ROUTER_BYTES * shape["router_params"]
            + experts_hit * expert_bytes(shape)
            + shape["row_bytes"] * live_rows_read(shape, live_rows,
                                                  live_rows_window))


def visible_pairs(tokens: int, window: int) -> float:
    """The (query, key) pairs one head's attention over a prompt sees:
    the causal half, or with a window (0: none) the band, query i seeing
    keys `i - window < j <= i`."""
    if not window or tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * float(window)


def gqa_prefill_flops(shape: Dict[str, Any], tokens: int, window: int
                      ) -> float:
    """ONE layer's attention over a prompt, the visible pairs alone."""
    return shape["heads"] * 4.0 * shape["head_dim"] \
        * visible_pairs(tokens, window)
