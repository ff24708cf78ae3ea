"""Seconds of set-up Python spent tracing functions and lowering them to
modules: `trace_s + lower_s` over set-up's records whose spans lie in no
other's, the `unattributed` record (`jax.eval_shape`, a `.lower()` alone)
included. A warm cache saves none of it, and it grows with depth and with
unrolled kernels. Logs the largest functions traced INSIDE another's
trace (`inner`: an inner `jit`, a `jnp` function), whose seconds the
outer's hold."""
from benchmarks.harness import setup_clock
from benchmarks.harness.common import log


def read(obs):
    parts = setup_clock.split(obs)
    if parts is None:
        return None
    records = setup_clock.outermost(parts[0])
    inner = sorted((e for r in records for e in r.get("inner", ())),
                   key=lambda e: -e["trace_s"])[:5]
    log("trace_lower_s.setup: trace "
        f"{sum(r['trace_s'] for r in records):.3f} s, lowering "
        f"{sum(r['lower_s'] for r in records):.3f} s; the largest traced "
        "inside another: " + (", ".join(
            f"{e['name']} in {e['parent']} {e['trace_s']:.3f} s x{e['n']}"
            for e in inner) or "none"))
    return sum(r["trace_s"] + r["lower_s"] for r in records)
