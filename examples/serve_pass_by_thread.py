"""Where a pass of the serving process goes, BY THREAD: the benchmark's own
command for a serving cell, run with clocks laid round it in this process
only (no file of the benchmark or the program is edited). Every thread of
the process holds ONE interpreter lock, so what bounds the engine's pass
at many streams is the sum of the Python a tick's tokens cost over all
threads; this is the table PERF.md section 5 holds for `zaya1-rollouts`
(PR 57) and what ROADMAP S15 is read from.

    chiprun --chips 1 -- python3 examples/serve_pass_by_thread.py \\
        [--root DIR] [--parts 1] [--toy 1] -- \\
        --workload zaya1-rollouts --seed 1300000057 --seconds 51 --trace 0

Over the window of offered load (the harness's "run" command to its
client) it reads
  * the CPU clock of every thread at both ends (`pthread_getcpuclockid`;
    it ticks in 10 ms there, so read sums over a window), grouped by name:
    the engine's loop (`cb-engine`), the gateway's asyncio thread
    (`gateway-http`), its workers (`gateway-generate*`), the process;
  * the loop ring's clocks over the window's passes: all of them, the
    full ones (85% of the slots live) and the steady full ones (no
    admission): `dispatch_ms`, `readback_ms`, `emit_ms`, `admit_ms`,
    `total_ms`;
  * the gateway's totals (`sse_tokens`, `sse_decoded_tokens` where the
    program has them);
  * with `--parts 1`, a clock pair (wall and thread CPU) round each part
    of a frame on the asyncio thread: the codec's decode, the payload,
    its json, `resp.write`, `_client_gone`. A pair costs some 12 us on the
    chip's host (`clock_pair_us` in the output: take it off each call) and
    slows the cell by a tenth: take the other numbers from a run without.
The result goes to `chiprun_out/pass_by_thread_<workload>_s<seed>.json` and,
as one `PASS_BY_THREAD {...}` line, to stderr; the benchmark's own last
line stays the last line of stdout. `--root DIR` runs another checkout's
program and benchmark (the parent unpacked under `_checkout/`); `--toy 1`
walks it at the rehearsal's toy size on the CPU and gives no times worth
reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_KEYS = ("dispatch_ms", "readback_ms", "emit_ms", "admit_ms",
             "total_ms", "live", "handed", "handovers")


def thread_cpu() -> dict:
    """CPU seconds of every live thread, summed by name group."""
    out: dict = {}
    for t in threading.enumerate():
        if t.ident is None:
            continue
        try:
            s = time.clock_gettime(time.pthread_getcpuclockid(t.ident))
        except (OSError, ValueError):   # the thread ended meanwhile
            continue
        name = ("gateway-generate*" if t.name.startswith("gateway-generate")
                else t.name)
        group = out.setdefault(name, {"threads": 0, "cpu_s": 0.0})
        group["threads"] += 1
        group["cpu_s"] += s
    out["PROCESS"] = {"threads": threading.active_count(),
                      "cpu_s": time.process_time()}
    return out


def clock_pair_us(n: int = 20000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        w0, c0 = time.perf_counter(), time.thread_time()
        _ = time.perf_counter() - w0, time.thread_time() - c0
    return (time.perf_counter() - t0) / n * 1e6


class Clocks:
    """What the run leaves: `out` is the result, `parts` the frame's."""

    def __init__(self) -> None:
        self.out: dict = {}
        self.parts: dict = {}       # name -> [calls, wall_s, cpu_s, units]
        self.gateways: list = []

    def timed(self, name, fn, units=None):
        def wrapper(*a, **k):
            if threading.current_thread().name != "gateway-http":
                return fn(*a, **k)
            w0, c0 = time.perf_counter(), time.thread_time()
            try:
                return fn(*a, **k)
            finally:
                self.add(name, time.perf_counter() - w0,
                         time.thread_time() - c0,
                         units(*a, **k) if units else 0)
        return wrapper

    def add(self, name, wall, cpu, units=0) -> None:
        acc = self.parts.setdefault(name, [0, 0.0, 0.0, 0])
        acc[0] += 1
        acc[1] += wall
        acc[2] += cpu
        acc[3] += units

    def lay(self, parts: bool) -> None:
        """Lay the clocks round the program and the harness."""
        import aiohttp.web
        from benchmarks.harness import serve_cell
        from ray_tpu.models import engine as eng
        from ray_tpu.serve import gateway as gw

        clocks = self
        init = gw.GatewayServer.__init__

        def remember(server, *a, **k):
            clocks.gateways.append(server)
            init(server, *a, **k)
        gw.GatewayServer.__init__ = remember

        if parts:
            self.out["clock_pair_us"] = clock_pair_us()
            gw.ByteCodec.decode = self.timed(
                "decode", gw.ByteCodec.decode, lambda _c, toks: len(toks))
            gw.GatewayServer._completion_payload = self.timed(
                "payload", gw.GatewayServer._completion_payload)
            gw._sse_frame = self.timed("sse_frame_json", gw._sse_frame)
            gw.GatewayServer._client_gone = self.timed(
                "client_gone", gw.GatewayServer._client_gone)
            write = aiohttp.web.StreamResponse.write

            async def timed_write(resp, data):
                w0, c0 = time.perf_counter(), time.thread_time()
                try:
                    return await write(resp, data)
                finally:
                    clocks.add("resp_write", time.perf_counter() - w0,
                               time.thread_time() - c0, len(data))
            aiohttp.web.StreamResponse.write = timed_write

        ask = serve_cell.Client.ask

        def timed_ask(client, cmd):
            if cmd.get("cmd") != "run":
                return ask(client, cmd)
            clocks.parts.clear()        # the window alone
            before, w0, t0 = thread_cpu(), time.perf_counter(), time.time()
            try:
                return ask(client, cmd)
            finally:
                after = thread_cpu()
                clocks.out["window"] = {
                    "t0": t0, "wall_s": time.perf_counter() - w0,
                    "seconds": float(cmd["seconds"]),
                    "frame_parts": {
                        k: dict(zip(("calls", "wall_s", "cpu_s", "units"),
                                    v)) for k, v in clocks.parts.items()},
                    "thread_cpu_s": {
                        n: {"threads": g["threads"], "cpu_s": g["cpu_s"]
                            - before.get(n, {"cpu_s": 0.0})["cpu_s"]}
                        for n, g in after.items()}}
        serve_cell.Client.ask = timed_ask

        stop = eng.ContinuousBatchingEngine.stop

        def stop_and_write(engine, *a, **k):
            # the harness stops the engine on its way out, whatever
            # happened: the last point this process is sure to reach
            clocks.out["handover"] = engine.kv_stats().get("handover")
            clocks.finish()
            return stop(engine, *a, **k)
        eng.ContinuousBatchingEngine.stop = stop_and_write

    def finish(self) -> None:
        from ray_tpu.observability import requests as reqtrace

        win = self.out.get("window")
        if win:
            recs = [r for r in reqtrace.store().loop_records()
                    if win["t0"] <= r["ts"] <= win["t0"] + win["wall_s"]]
            full = [r for r in recs if r["live"] >= 0.85 * r["max_batch"]]
            steady = [r for r in full if not r["admissions"]]

            # a pass that decoded and admitted nothing, full or not (a
            # cell whose ticks never fill: `gpt2-chat`)
            decoding = [r for r in recs
                        if r["live"] >= 1 and not r["admissions"]]

            def means(rs):
                # a parent without the hand-over has no such counters
                return {k: sum(r.get(k, 0) for r in rs) / len(rs)
                        for k in LOOP_KEYS} if rs else {}

            def share(rs, cond):
                return sum(map(cond, rs)) / len(rs) if rs else None

            reads = sorted(r["readback_ms"] for r in decoding)
            self.out["loop"] = {
                "passes": len(recs), "full": len(full),
                "steady_full": len(steady), "mean_all": means(recs),
                "mean_full": means(full), "mean_steady_full": means(steady),
                "steady": len(decoding), "mean_steady": means(decoding),
                # the hand-over as the ring shows it: ONE sink call a
                # steady pass, for every token the tick made
                "steady_one_handover": share(
                    decoding, lambda r: r.get("handovers") == 1),
                "steady_all_handed": share(
                    decoding, lambda r: r.get("handed")
                    == r["live"] - r["discarded"]),
                "steady_readback_ms": {
                    q: reads[int(q * (len(reads) - 1))]
                    for q in (0.5, 0.95)} if reads else {}}
            phases = [p for t in reqtrace.store().slowest(10 ** 6)
                      for p in (t.get("phases") or [])
                      if p["phase"] == "sse_flush"]
            self.out["sse_flush_kept"] = {
                k: sum(p.get(k, 0) for p in phases)
                for k in ("dur_ms", "writes", "tokens", "decoded")}
        for server in self.gateways:
            stats = server.stats()
            self.out["gateway_stats"] = {
                k: stats.get(k) for k in (
                    "completed", "streamed", "tokens_out", "sse_tokens",
                    "sse_decoded_tokens")}
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        path = os.path.join(HERE, "chiprun_out", "pass_by_thread_{}_s{}.json"
                            .format(self.out["workload"], self.out["seed"]))
        with open(path, "w") as f:
            json.dump(self.out, f, indent=1)
        sys.stderr.write("PASS_BY_THREAD " + json.dumps(self.out) + "\n")


def main() -> None:
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--parts", type=int, choices=(0, 1), default=0)
    ap.add_argument("--toy", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv[:cut])
    bench_argv = argv[cut + 1:]
    root = os.path.abspath(args.root)
    os.chdir(root)
    sys.path.insert(0, root)

    def value(flag):
        return bench_argv[bench_argv.index(flag) + 1]

    clocks = Clocks()
    clocks.out.update(root=root, parts=bool(args.parts),
                      workload=value("--workload"), seed=value("--seed"))
    if args.toy:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("RAY_TPU_PALLAS_INTERPRET", "1")
    clocks.lay(bool(args.parts))
    if args.toy:
        sys.argv = ["rehearse.py"] + bench_argv
        sys.path.insert(0, os.path.join(root, "tests", "yardstick"))
        import rehearse
        rehearse.main()
    else:
        from benchmarks import run
        run.main(bench_argv)


if __name__ == "__main__":
    main()
