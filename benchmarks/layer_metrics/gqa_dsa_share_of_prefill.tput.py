"""What of a prefill the sparse attention is, where grouped-query heads
attend under the selection: the summed device time of the `dsa_index_t<T>`,
`dsa_select_t<T>` and `gqa_selected_t<T>` events (score, select, attend
under the mask) over that of the `_prefill_paged` program's events that
start in the traced window, in per cent, COUNTING ONLY the kernel events
that start inside one of those prefill events
(`harness/gqa_dsa_cost.prefill_spans`): a prefill that began before the
window gives the trace its kernels and not its own time, which would read
over 100 (the ledger's PR 48 line reads `dsa_share_of_prefill.tput` 124.3
so), and here gives neither. None against a program without the kernels,
or a backend without Mosaic."""
from benchmarks.harness.common import log
from benchmarks.harness.gqa_dsa_cost import (KERNELS, kernel_events,
                                             prefill_spans)


def read(obs):
    spans = prefill_spans(obs)
    took = {kind: sum(s for _n, s in kernel_events(obs, kind, spans).values())
            for kind in KERNELS}
    prefill_s = sum(b - a for a, b in spans) / 1e9
    if not took["selected"] or not prefill_s:
        return None
    log("gqa_dsa_share_of_prefill.tput: " + ", ".join(
        f"{kind} {s:.3f} s" for kind, s in took.items())
        + f" of {prefill_s:.3f} s of {len(spans)} prefills")
    return 100.0 * sum(took.values()) / prefill_s
