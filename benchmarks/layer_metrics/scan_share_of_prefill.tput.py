"""What of a prefill the selective scan is: the summed device time of the
`selective_scan_t<T>` events that start in the traced window over that of
the `_prefill_paged` program's events in it, in per cent. A prefill the
window's edge cuts gives its kernel events and its own time to different
sides by at most one prefill's worth: a reading over many prefills. None
against a program without the kernel, or a backend without Mosaic."""
from benchmarks.harness.jamba_cost import scan_events
from benchmarks.harness.readers import program_events


def read(obs):
    kernel_s = sum(took for _n, took in scan_events(obs).values())
    prefill_s = sum(d for _n, _s, d in program_events(
        obs, "_prefill_paged")) / 1e9
    if not kernel_s or not prefill_s:
        return None
    return 100.0 * kernel_s / prefill_s
