"""What of a prefill the sparse attention is: the summed device time of
the `dsa_index_t<T>`, `dsa_select_t<T>` and `mla_selected_t<T>` events
(score, select, attend under the selection) that start in the traced
window over that of the `_prefill_paged` program's events in it, in per
cent. A prefill the window's edge cuts gives its kernel events and its
own time to different sides by at most one prefill's worth: a reading
over many prefills. None against a program without the kernels, or a
backend without Mosaic."""
from benchmarks.harness.common import log
from benchmarks.harness.dsa_cost import KERNELS, kernel_events
from benchmarks.harness.readers import program_events


def read(obs):
    took = {kind: sum(s for _n, s in kernel_events(obs, kind).values())
            for kind in KERNELS}
    prefill_s = sum(d for _n, _s, d in program_events(
        obs, "_prefill_paged")) / 1e9
    if not sum(took.values()) or not prefill_s:
        return None
    log("dsa_share_of_prefill.tput: " + ", ".join(
        f"{kind} {s:.3f} s" for kind, s in took.items())
        + f" of {prefill_s:.3f} s of prefill")
    return 100.0 * sum(took.values()) / prefill_s
