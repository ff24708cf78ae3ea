"""What the hot parts of a model with a learned sparse-attention indexer
compute and move, from the sizes its family's `shape()` gives and from
what a run met. Each is counted ONCE, at the width it is served in and
for the pairs the MATHEMATICS needs, whatever computes it, so a share
computed from these numbers is a lower reading and cannot pass 100% by
over-counting.

  the index score (`index_flops`, `index_bytes`): ONE full layer's
    indexer over a prompt of T tokens: for each of the T (T + 1) / 2
    visible (query, row) pairs, `index_heads` products over `index_dim`
    (the ReLU, the head weights and their sum run on the vector unit and
    are not counted); q_I, the index keys and the head weights read once,
    one float32 score a visible pair written. The program scores a block
    of `index_block` queries a call of `dsa_index_t<T>` (`index_calls` a
    layer), whole tiles up to the diagonal, padding rows too: not
    counted.
  attention over the selected pairs (`selected_flops`, `selected_bytes`):
    ONE full layer's attention over a prompt: q . k over `head_dim` and p
    . v over `value_dim`, every head, for the `min(t + 1, index_topk)`
    rows query t attends ALONE (`selected_pairs`). The first prompt form
    (`mla_selected_t<T>`: `selected_calls` a layer, one a group of heads)
    computes every visible block and masks: what it computes beyond the
    selected pairs reads as lost time, which is the point of the share.
    Bytes: queries in, outputs out, per-head keys and values of the
    prompt once.
  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline. Counted once a tick: the weights every token reads
    (`always_params` in bf16, the routers apart, float32; NOT the
    embedding: a tick gathers a row a slot); an expert's three matrices
    for each held expert that got a row; and of the slab, a full layer:
    the index key of every VISIBLE row (the indexer has to score them)
    and the latent row of every SELECTED row; a sliding layer: the ring
    rows inside the window. The program scores every row of the slab
    whatever is visible: the rest reads as lost time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List

WEIGHT_BYTES = 2     # bf16: weights, activations, cache rows
F32_BYTES = 4        # routers, index scores, head weights
KERNELS = {"index": re.compile(r"dsa_index_t(\d+)"),
           "select": re.compile(r"dsa_select_t(\d+)"),
           "selected": re.compile(r"mla_selected_t(\d+)")}


def kernel_events(obs: Dict[str, Any], kind: str) -> Dict[int, List[float]]:
    """{prompt length: [events, seconds]} of the events of one of
    `KERNELS` that start in the traced window (each carries the PROMPT's
    length in its name); empty without a trace or the kernel."""
    out: Dict[int, List[float]] = {}
    for name, events in ((obs.get("trace") or {}).get("ops") or {}).items():
        m = KERNELS[kind].search(name)
        if m:
            met = out.setdefault(int(m.group(1)), [0, 0.0])
            met[0] += len(events)
            met[1] += sum(d for _n, _s, d in events) / 1e9
    return out


def _padded(tokens: int, block: int) -> int:
    return -(-tokens // block) * block


def index_calls(shape: Dict[str, Any], tokens: int) -> int:
    """The index kernel's calls for ONE layer over a prompt: the program
    pads a prompt to whole blocks of the selected form, and to whole
    blocks of queries where it is longer than one."""
    rows = _padded(tokens, shape["attn_block"])
    if rows <= shape["index_block"]:
        return 1
    return _padded(tokens, shape["index_block"]) // shape["index_block"]


def selected_calls(shape: Dict[str, Any]) -> int:
    """The selected form's calls for ONE layer: one a group of heads."""
    return -(-shape["heads"] // shape["head_group"])


def visible_pairs(tokens: int) -> float:
    return tokens * (tokens + 1) / 2.0


def selected_pairs(tokens: int, topk: int) -> float:
    """sum over queries t of min(t + 1, topk)."""
    low = min(tokens, topk)
    return low * (low + 1) / 2.0 + (tokens - low) * float(topk)


def index_flops(shape: Dict[str, Any], tokens: int) -> float:
    return 2.0 * shape["index_heads"] * shape["index_dim"] \
        * visible_pairs(tokens)


def index_bytes(shape: Dict[str, Any], tokens: int) -> float:
    heads, dim = shape["index_heads"], shape["index_dim"]
    return (tokens * (heads * dim + dim) * WEIGHT_BYTES
            + tokens * heads * F32_BYTES
            + visible_pairs(tokens) * F32_BYTES)


def selected_flops(shape: Dict[str, Any], tokens: int) -> float:
    return shape["heads"] * 2.0 * (shape["head_dim"] + shape["value_dim"]) \
        * selected_pairs(tokens, shape["index_keep"])


def selected_bytes(shape: Dict[str, Any], tokens: int) -> float:
    per_token = shape["heads"] * 2 * (shape["head_dim"] + shape["value_dim"])
    return float(tokens * per_token * WEIGHT_BYTES)


def expert_bytes(shape: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return WEIGHT_BYTES * shape["expert_params"]


def tick_bytes(shape: Dict[str, Any], experts_hit: float,
               rows_visible: float, rows_selected: float,
               ring_rows_read: float) -> float:
    """The least one decode tick moves (module docstring). The three row
    counts are sums over the slots for ONE layer of their kind, as the
    program's tick counters give them."""
    full = shape["full_layers"] * WEIGHT_BYTES * (
        rows_visible * shape["row_index"]
        + rows_selected * shape["row_full"])
    rings = shape["sliding_layers"] * WEIGHT_BYTES * ring_rows_read \
        * shape["row_ring"]
    return (WEIGHT_BYTES * shape["always_params"]
            + F32_BYTES * shape["router_params"]
            + experts_hit * expert_bytes(shape) + full + rings)
