"""What attention of GROUPED-QUERY heads under a learned selection
computes and moves, beside `harness/dsa_cost.py` (the indexer's score, the
selected pairs and the tick's bytes are that module's, and serve this
form unchanged from the sizes its family's `shape()` gives). Counted
ONCE, at the width it is served in and for the pairs the MATHEMATICS
needs, so a share computed from these numbers is a lower reading and
cannot pass 100% by over-counting.

  attention over the selected pairs (`selected_flops`, `selected_bytes`):
    ONE layer's attention over a prompt: q . k over `head_dim` and p . v
    over `value_dim`, every QUERY head, for the `min(t + 1, index_keep)`
    rows query t attends alone (`dsa_cost.selected_pairs`). The first
    prompt form (`gqa_selected_t<T>`: `dsa_cost.selected_calls` a layer,
    `head_group` query heads a call) computes every visible block and
    masks: what it computes beyond the selected pairs reads as lost time,
    which is the point of the share. Bytes: every query head's queries in
    and outputs out, and the keys and values of the `kv_heads` heads they
    share read ONCE (not once a query head).
  the kernels of a prefill (`inside`): the events of the index, select
    and selected kernels that START inside a `_prefill_paged` program
    event that itself starts in the traced window, so that a prefill the
    window's edge cuts gives neither its kernels nor its own time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

from benchmarks.harness import dsa_cost
from benchmarks.harness.readers import program_events

WEIGHT_BYTES = dsa_cost.WEIGHT_BYTES
KERNELS = dict(dsa_cost.KERNELS,
               selected=re.compile(r"gqa_selected_t(\d+)"))


def kernel_events(obs: Dict[str, Any], kind: str,
                  spans: List[Tuple[float, float]] | None = None
                  ) -> Dict[int, List[float]]:
    """{prompt length: [events, seconds]} of the events of one of
    `KERNELS` that start in the traced window (each carries the PROMPT's
    length in its name) and, where `spans` [(start, end)] is given,
    inside one of them; empty without a trace or the kernel."""
    out: Dict[int, List[float]] = {}
    for name, events in ((obs.get("trace") or {}).get("ops") or {}).items():
        m = KERNELS[kind].search(name)
        if not m:
            continue
        for _n, start, took in events:
            if spans is None or any(a <= start < b for a, b in spans):
                met = out.setdefault(int(m.group(1)), [0, 0.0])
                met[0] += 1
                met[1] += took / 1e9
    return out


def prefill_spans(obs: Dict[str, Any]) -> List[Tuple[float, float]]:
    """(start, end) of the `_prefill_paged` events of the traced window."""
    return [(s, s + d) for _n, s, d in program_events(obs, "_prefill_paged")]


def selected_flops(shape: Dict[str, Any], tokens: int) -> float:
    return shape["heads"] * 2.0 * (shape["head_dim"] + shape["value_dim"]) \
        * dsa_cost.selected_pairs(tokens, shape["index_keep"])


def selected_bytes(shape: Dict[str, Any], tokens: int) -> float:
    per_token = (shape["heads"] + shape["kv_heads"]) \
        * (shape["head_dim"] + shape["value_dim"])
    return float(tokens * per_token * WEIGHT_BYTES)
