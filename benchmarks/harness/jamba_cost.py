"""What the hot parts of a Jamba model move and compute, from the sizes
its family's `shape()` gives and from what a run met. Each is counted
ONCE and at the width it is served in, so a share computed from these
numbers is a lower reading and cannot pass 100% by over-counting.

  the selective scan (`scan_bytes`, `scan_elementwise_ops`): ONE Mamba
    layer's recurrence over a prompt of T tokens, as one pass would do
    it: per token and channel u' and z (bf16) and dt (float32) read and y
    (bf16) written, per token B and C (float32, `d_state` each) read; A
    `[d_state, d_inner]` and D `[d_inner]` (float32) read once, the
    state `[d_state, d_inner]` (float32) read once and written once. The
    program walks a prompt in blocks of `token_block` tokens and the
    kernel `selective_scan_t<T>` is called once a block and layer
    (`scan_calls`): A, D and the state of every block after the first,
    and the steps that pad a ragged last block, are not counted and read
    as lost time. The work is elementwise on the vector unit: per token,
    channel and state a product for the decay's exponent, the
    exponential, the decay times the state, the input's product with
    B, the sum, the product with C and its sum (7), and per token and
    channel the input's products with dt and D, the gate's SiLU (4) and
    the gate's product (8).
  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline. Counted once a tick: every parameter as served
    (bf16; `float32_params` of them float32: A_log, D, dt's bias), the
    tied embedding ONCE (as the head; a tick gathers a row a slot
    besides); for each live slot its state (`state_bytes`: the
    recurrence states in float32 and the convolutions' tails) read AND
    written; for each live slot its `position` rows of keys and values
    (`live_rows`, `row_bytes` a row over the attention layers). The
    program reads every slot's state and every row of the slab whatever
    is live: dead ones count nothing here and read as lost time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List

SCAN_KERNEL = re.compile(r"selective_scan_t(\d+)")
WEIGHT_BYTES = 2     # bf16: weights, activations, keys and values
F32_BYTES = 4        # dt, B, C, A, D and the recurrence state


def scan_events(obs: Dict[str, Any]) -> Dict[int, List[float]]:
    """{prompt length: [events, seconds]} of the kernel's events that
    start in the traced window (the kernel carries the PROMPT's length in
    its name); empty without a trace or the kernel."""
    out: Dict[int, List[float]] = {}
    for name, events in ((obs.get("trace") or {}).get("ops") or {}).items():
        m = SCAN_KERNEL.search(name)
        if m:
            kind = out.setdefault(int(m.group(1)), [0, 0.0])
            kind[0] += len(events)
            kind[1] += sum(d for _n, _s, d in events) / 1e9
    return out


def scan_calls(shape: Dict[str, Any], tokens: int) -> int:
    """The kernel's calls for ONE layer over a prompt of `tokens`."""
    return -(-tokens // min(shape["token_block"], tokens))


def scan_bytes(shape: Dict[str, Any], tokens: int) -> float:
    """ONE layer's scan over a prompt (module docstring)."""
    c, n = shape["d_inner"], shape["d_state"]
    per_token = c * (3 * WEIGHT_BYTES + F32_BYTES) + 2 * n * F32_BYTES
    return float(tokens * per_token
                 + F32_BYTES * (n * c + c)          # A and D
                 + 2 * F32_BYTES * n * c)           # the state, in and out


def scan_elementwise_ops(shape: Dict[str, Any], tokens: int) -> float:
    """ONE layer's scan over a prompt: the vector unit's operations, an
    exponential counted as one."""
    c, n = shape["d_inner"], shape["d_state"]
    return float(tokens * c * (7 * n + 8))


def held_bytes(shape: Dict[str, Any]) -> int:
    """Every parameter held, as served."""
    return (WEIGHT_BYTES * shape["params"]
            + (F32_BYTES - WEIGHT_BYTES) * shape["float32_params"])


def tick_bytes(shape: Dict[str, Any], live_slots: float, live_rows: float
               ) -> float:
    """The least one decode tick moves (module docstring)."""
    return (held_bytes(shape)
            + 2.0 * live_slots * shape["state_bytes"]
            + live_rows * shape["row_bytes"])
