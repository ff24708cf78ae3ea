"""The bytes the mathematics of one decode tick of a Kimi-Linear share
needs, from the sizes its family's `shape()` gives and from what the tick
met, and what the share holds. No kernel of this family is hand-written:
the unit reckoned here is the whole `_tick` program, and its share of the
roofline is of the memory bound alone (a tick of 128 tokens is 0.3 TFLOP,
1.4 ms at 197 TFLOP/s, under a tenth of what its bytes take).

Counted ONCE a tick, each at the width it is served in (bf16 weights and
rows, float32 state):
  - the weights every token reads (`always_params`: the mixers, the dense
    feed-forward part, every expert layer's router and shared expert, the
    norms, the head). NOT the embedding (a tick gathers a row a slot);
  - an expert's three matrices (`expert_params`) for each held expert
    that got a row (`moe_experts_hit`, summed over the expert layers);
  - a live slot's recurrence states (`state_per_slot` numbers), read and
    written (NOT its convolution tails: three rows read, one written);
  - a latent row of every latent layer (`row_per_token` numbers: the
    padding to whole lane tiles is the program's) for each cache row a
    live slot holds (`live_rows`).
Dead slots, unread experts and padding count nothing, so a share computed
from these bytes is a lower reading and cannot pass 100% by over-counting.
"""
from __future__ import annotations

from typing import Any, Dict

WEIGHT_BYTES = 2     # bf16
STATE_BYTES = 4      # float32


def held_bytes(shape: Dict[str, Any]) -> int:
    """Every parameter this share holds, as served."""
    return WEIGHT_BYTES * shape["held_params"]


def slot_bytes(shape: Dict[str, Any], max_seq_len: int) -> int:
    """What a slot owns of the slab, as the mathematics sizes it: states,
    convolution tails and `max_seq_len` latent rows."""
    return (STATE_BYTES * shape["state_per_slot"]
            + WEIGHT_BYTES * (shape["tail_per_slot"]
                              + max_seq_len * shape["row_per_token"]))


def tick_bytes(shape: Dict[str, Any], experts_hit: float,
               live_slots: float, live_rows: float) -> float:
    """The least one decode tick moves (module docstring)."""
    return (WEIGHT_BYTES * (shape["always_params"]
                            + experts_hit * shape["expert_params"])
            + 2.0 * STATE_BYTES * live_slots * shape["state_per_slot"]
            + WEIGHT_BYTES * live_rows * shape["row_per_token"])
