"""The chunked Mamba-2 scan over the prompts against its roofline: the
least time the chunked form's operations and bytes need at the
configuration file's chunk (`harness/granite_hybrid_cost.scan_flops` over
the bf16 peak, `scan_bytes` over the peak bandwidth, the larger: at the
published sizes the bytes, 52 KB a token and layer against 8.45 MFLOP,
by a half), over the summed device time of the operations
the program names `ssd_scan` inside the whole `_prefill_paged` events of
the traced window. The same work is read whatever implements the scan,
XLA's fusions or a kernel, found by the scope's name; the elementwise
decay terms are counted nowhere, so the share reads LOW and cannot pass
100% by over-counting. None without a device trace, against a program
that names no such scope, or in a cell whose family's `shape()` lacks the
scan's sizes."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.granite_hybrid_cost import (prefill_scope_seconds,
                                                    scan_bytes, scan_flops)
from benchmarks.harness.roofline import least_seconds


def read(obs):
    shape = model_shape(obs["cell"]["conf"])
    if "scan_chunk" not in shape:
        return None
    met = prefill_scope_seconds(obs, "ssd_scan")
    if met is None:
        return None
    took, _whole, tokens, prompts = met
    least, bound = least_seconds(scan_flops(shape, tokens),
                                 scan_bytes(shape, tokens, prompts),
                                 obs["cell"]["peaks"])
    log(f"ssd_scan_roofline.tput: {scan_flops(shape, tokens) / 1e9:.1f} "
        f"GFLOP over {tokens:.0f} tokens need {1e3 * least:.3f} ms "
        f"({bound}), the scan took {1e3 * took:.2f} ms")
    return 100.0 * least / took
