#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the
chip. `python chip_smoke.py`, no arguments, from the root of a checkout.

It drives both main paths once, through the entry points a user calls, at
the full width of GPT-2 125M (`GPT2Config.small()`), weights from a seed,
on every chip it finds:

- phase `train`: JaxTrainer(mode="spmd") -> TrainStep -> models/gpt2 ->
  the flash-attention and fused-CE Pallas kernels, per-chip batch
  32 x 1024, no remat, the compile step plus five more on one fixed batch;
- phase `serve`: HTTP -> GatewayServer -> DisaggRouter -> PrefillServer +
  DecodeServer -> ContinuousBatchingEngine + PagedKVCache, eight
  requests, each body checked token for token against a plain
  ContinuousBatchingEngine.generate on the same device kind.

A chip belongs to one process at a time, so this parent imports no JAX and
runs the phases as child processes one after another. With four chips or
more the serve replicas are actors with num_tpus=1, one chip each, and the
child that hosts the gateway and the router is held to the CPU.

It fails (exit code not 0, the reason on the last line, no result) when
JAX finds no TPU, when a phase raises, fails an assertion or runs out of
time. On success the last stdout line is the result, one JSON object with
exactly `ok` and `device` (platform, kind, count as JAX reports them). What
the phases recorded goes to the line before it and to
chiprun_out/chip_smoke.json. Wall times there are for budgeting chip calls
and are no metric.
"""
from __future__ import annotations

import argparse
import functools
import http.client
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
SEED = 0
# the driver allows 1200 s for the whole script, compilation included
PHASE_TIMEOUT_S = {"train": 540.0, "serve": 600.0}
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "fused_ce_fwd", "fused_ce_dx", "fused_ce_dw")


def _check(cond: bool, message: str) -> None:
    """An assertion that survives `python -O`."""
    if not cond:
        raise AssertionError(message)


def _device_facts() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs), "jax": jax.__version__}
    print(f"chip_smoke: platform={facts['platform']} "
          f"device_kind={facts['kind']} count={facts['count']} "
          f"jax={facts['jax']}", flush=True)
    return facts


def _require_tpu() -> Dict[str, Any]:
    facts = _device_facts()
    _check(facts["platform"] == "tpu",
           f"JAX found platform {facts['platform']!r} "
           f"({facts['kind']}), not 'tpu': no chip, no smoke")
    _check(os.environ.get("RAY_TPU_PALLAS_INTERPRET", "0") != "1",
           "RAY_TPU_PALLAS_INTERPRET is set: the kernels would run in "
           "the interpreter, not through Mosaic")
    return facts


def _seeded_params(cfg: Any, seed: int) -> Any:
    import jax

    from ray_tpu.models.gpt2 import gpt2_init

    return gpt2_init(cfg, jax.random.PRNGKey(seed))


# ------------------------------------------------------------ phase: train


def custom_calls(hlo_text: str) -> List[Dict[str, Any]]:
    """The Mosaic custom calls of a compiled TPU program: kernel name
    (the pallas_call's `name`, which the op metadata carries) and the
    dimensions of the first operand."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # op_name="jit(step)/jvp(flash_fwd)/pallas_call" on one device,
        # ".../jvp()/shard_map/flash_fwd/pallas_call" under shard_map:
        # the kernel is the identifier before pallas_call
        op_name = re.search(r'op_name="([^"]*)"', line)
        ids = re.findall(r"[A-Za-z_]\w*", op_name.group(1) if op_name
                         else "")
        kernel = (ids[ids.index("pallas_call") - 1]
                  if "pallas_call" in ids[1:] else "?")
        dims = re.search(r"operand_layout_constraints=\{\w+\[([\d,]*)\]",
                         line)
        calls.append({
            "kernel": kernel,
            "operand0": [int(d) for d in dims.group(1).split(",") if d]
            if dims else [],
            # the line without the serialized kernel body
            "hlo": line.split("backend_config=")[0].strip()})
    return calls


def train_phase(cfg: Any, *, per_chip_batch: int = 32, seq: int = 1024,
                steps: int = 6, interpret: bool = False,
                storage: Optional[str] = None,
                hlo_dump: Optional[str] = None) -> Dict[str, Any]:
    """JaxTrainer -> TrainStep -> gpt2_loss on every local device.
    `interpret` runs the kernels in the Pallas interpreter (CPU tests);
    the chip run leaves it off and also reads the compiled HLO, whose
    custom-call lines go to `hlo_dump` as evidence."""
    import contextlib

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.gpt2 import gpt2_loss, gpt2_partition_specs
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import JaxTrainer, RunConfig, TrainStep

    seen: Dict[str, Any] = {}

    def train_fn(_config: Dict[str, Any]) -> None:
        devices = jax.devices()
        mesh = make_mesh(MeshConfig(dp=-1), devices=devices)
        step = TrainStep(
            lambda p, b: gpt2_loss(p, b["tokens"], b["targets"], cfg),
            optax.adamw(3e-4, weight_decay=0.1), mesh,
            gpt2_partition_specs(cfg))
        rng = np.random.default_rng(SEED)
        tokens = rng.integers(
            0, cfg.vocab_size, (per_chip_batch * len(devices), seq + 1),
            dtype=np.int32)
        batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
        state = step.init_state(_seeded_params(cfg, SEED))
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            loss = float(metrics["loss"])  # waits for the device
            wall = time.perf_counter() - t0
            print(f"chip_smoke: train step {i} loss {loss:.4f} "
                  f"({wall:.1f} s wall)", flush=True)
            train.report({"step": i, "loss": loss})
        seen["n_devices"] = len(devices)
        seen["mesh"] = {k: int(v) for k, v in mesh.shape.items() if v > 1}
        if step.compiled is not None:
            seen["hlo"] = step.compiled.as_text()
            mem = step.compiled.memory_analysis()
            seen["step_bytes"] = {
                k: int(getattr(mem, f"{k}_size_in_bytes"))
                for k in ("temp", "argument", "output", "alias")
            } if mem is not None else None

    dispatch.reset_kernel_choices()
    with contextlib.ExitStack() as stack:
        if interpret:
            stack.enter_context(dispatch.pallas_interpret())
        if storage is None:
            storage = stack.enter_context(tempfile.TemporaryDirectory())
        result = JaxTrainer(
            train_fn, mode="spmd",
            run_config=RunConfig(name="chip_smoke",
                                 storage_path=storage)).fit()
    if result.error is not None:
        raise result.error

    n_dev = seen["n_devices"]
    history = result.metrics_history
    losses = [float(m["loss"]) for m in history]
    _check(len(losses) == steps, f"{len(losses)} reports for {steps} steps")
    _check(all(np.isfinite(losses)), f"loss is not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall on a fixed batch: {losses}")

    # neither kernel may have given way to its reference, and on several
    # chips each must have been cut into per-chip pieces
    choices = {c["op"]: c for c in dispatch.kernel_choices()}
    for op in ("flash_attention", "linear_cross_entropy"):
        c = choices.get(op)
        _check(c is not None, f"{op} was never traced")
        _check(c["choice"] == "pallas",
               f"{op} took the {c['choice']} path: {c['reason']}")
        _check(c["shards"] == n_dev,
               f"{op} runs in {c['shards']} piece(s) on {n_dev} device(s)")

    calls: List[Dict[str, Any]] = []
    if not interpret:
        _check("hlo" in seen, "TrainStep kept no compiled step")
        calls = custom_calls(seen["hlo"])
        if hlo_dump:
            with open(hlo_dump, "w") as f:
                f.write("\n".join(c["hlo"] for c in calls) + "\n")
        # the flash kernels read [batch, seq, heads * head_dim] as it
        # lies (two heads of 64 a 128-lane block); the fused CE its rows
        rows = {"flash": per_chip_batch, "fused": per_chip_batch * seq}
        for kernel in TRAIN_KERNELS:
            mine = [c for c in calls if c["kernel"] == kernel]
            want = cfg.num_layers if kernel.startswith("flash") else 1
            _check(len(mine) == want,
                   f"{len(mine)} Mosaic custom call(s) named {kernel} in "
                   f"the compiled step, expected {want}; it has "
                   f"{sorted(c['kernel'] for c in calls)}")
            for c in mine:
                # per-chip batch, not the global one: the partitioner did
                # not gather the kernel's operands onto every chip
                _check(c["operand0"][:1] == [rows[kernel[:5]]],
                       f"{kernel} operand {c['operand0']} does not start "
                       f"with the per-chip {rows[kernel[:5]]}")

    mem = jax.devices()[0].memory_stats() or {}
    from ray_tpu.util.compile_cache import compile_cache_counts

    return {
        "ok": True, "devices": n_dev, "mesh": seen["mesh"],
        "per_chip_batch": per_chip_batch, "seq": seq, "steps": steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        # with what each entry point chose from the shape (flash
        # attention: each kernel's blocks and the share of the square
        # they visit; under 1.0, the causal skipping engaged)
        "kernels": [{k: v for k, v in c.items() if k != "reason"}
                    for c in choices.values()],
        "custom_calls": {k: sum(1 for c in calls if c["kernel"] == k)
                         for k in TRAIN_KERNELS} if calls else None,
        # set-up time, from the trainer's own step record
        "setup_s": round(float(history[0].get("compile_ms", 0.0)) / 1e3, 1),
        "compile_cache": compile_cache_counts(),
        # what the compiled step needs on each device (XLA's own
        # accounting: temporaries, arguments, outputs, donated aliases),
        # and what the allocator saw
        "step_bytes": seen.get("step_bytes"),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "bytes_limit": mem.get("bytes_limit"),
    }


# ------------------------------------------------------------ phase: serve


def _request_plan(cfg: Any, prompt_lens: Sequence[int], shared_prefix: int
                  ) -> List[Dict[str, Any]]:
    """Eight requests in four stages. Stages run one after another, the
    requests of a stage together. The shared-prefix pair (A, then B) and
    the streamed repeat of D each run alone, after the request whose
    cached blocks they reuse has finished: the oracle replays the same
    order against its own prefix cache, so every prompt meets the same
    cached prefix, hence the same prefill program, on both sides."""
    import numpy as np

    rng = np.random.default_rng(SEED + 1)
    short, mid, long_ = prompt_lens

    def toks(n: int) -> List[int]:
        return [int(t) for t in rng.integers(1, cfg.vocab_size, n)]

    prefix = toks(shared_prefix)
    a = prefix + toks(long_ - shared_prefix)
    b = prefix + toks(long_ - shared_prefix)
    d = toks(mid)
    return [
        {"name": "A", "stage": 0, "prompt": a},
        {"name": "B", "stage": 1, "prompt": b},
        {"name": "C", "stage": 2, "prompt": toks(short)},
        {"name": "D", "stage": 2, "prompt": d},
        {"name": "E", "stage": 2, "prompt": toks(short)},
        {"name": "F", "stage": 2, "prompt": toks(mid)},
        {"name": "G", "stage": 2, "prompt": toks(long_)},
        {"name": "D-stream", "stage": 3, "prompt": d, "stream": True},
    ]


def _post(host: str, port: int, body: Dict[str, Any], timeout: float
          ) -> Dict[str, Any]:
    """One /v1/completions call; returns status and the generated text
    (for a stream: the concatenated deltas)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200 or not body.get("stream"):
            payload = resp.read()
            text = (json.loads(payload)["choices"][0]["text"]
                    if resp.status == 200 else payload.decode()[:300])
            return {"status": resp.status, "text": text}
        parts, done = [], False
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                done = True
                break
            parts.append(json.loads(data)["choices"][0].get("text", ""))
        return {"status": resp.status, "text": "".join(parts),
                "done": done}
    finally:
        conn.close()


class Oracle:
    """A plain ContinuousBatchingEngine.generate over the plan's prompts,
    in plan order: greedy decoding, the repo's own oracle. In-process on
    one chip; an actor with num_tpus=1 on a four-chip host, so that it
    runs on the same device kind as the replicas."""

    def __init__(self, params: Any, cfg: Any, max_batch: int):
        from ray_tpu.models.engine import ContinuousBatchingEngine
        from ray_tpu.serve.disagg import device_record, own_params

        self.engine = ContinuousBatchingEngine(own_params(params), cfg,
                                               max_batch=max_batch)
        self.device = device_record()

    def run(self, prompts: List[List[int]], max_new: int
            ) -> Dict[str, Any]:
        from ray_tpu.serve.disagg import runtime_record

        try:
            tokens = [[int(t) for t in self.engine.generate(p, max_new)]
                      for p in prompts]
        finally:
            self.engine.stop()
        return {"tokens": tokens, "device": self.device,
                "runtime": runtime_record()}


def serve_phase(cfg: Any, *, actors: bool, max_new: int = 32,
                prompt_lens: Sequence[int] = (24, 96, 224),
                shared_prefix: int = 64, max_batch: int = 8,
                expect_platform: str = "tpu",
                request_timeout_s: float = 240.0) -> Dict[str, Any]:
    """HTTP -> gateway -> router -> prefill + decode replicas -> engine.
    actors=False: one prefill and one decode replica as in-process
    objects of this process, which holds the chip. actors=True: two of
    each as actors with num_tpus=1, one per chip, and this process (the
    gateway, the router, the driver) must be off the TPU."""
    import ray_tpu
    from ray_tpu.serve.disagg import (DecodeServer, DisaggRouter,
                                      PrefillServer, _call)
    from ray_tpu.serve.gateway import GatewayServer

    plan = _request_plan(cfg, prompt_lens, shared_prefix)
    _check(len({len(r["prompt"]) for r in plan}) == 3
           and all(16 <= len(r["prompt"]) <= 256 for r in plan),
           "the plan needs three distinct prompt lengths in [16, 256]")
    prompts = [r["prompt"] for r in plan]
    params = functools.partial(_seeded_params, cfg, SEED)
    chip = {"num_tpus": 1} if actors else {}

    def make(cls, *args, **kw):
        if not actors:
            return cls(*args, **kw)
        return ray_tpu.remote(cls).options(
            max_concurrency=8, **chip).remote(*args, **kw)

    # the chunk plane the KV crosses between the tiers is the cluster's
    # object store, so the phase runs inside a cluster either way
    ray_tpu.init(num_cpus=2)
    gateway = None
    replicas: List[Any] = []
    try:
        store = ray_tpu._private.worker.global_worker.store.implementation
        if actors:
            _check(ray_tpu.cluster_resources().get("TPU", 0) >= 4,
                   f"the cluster counts "
                   f"{ray_tpu.cluster_resources().get('TPU', 0)} chips: "
                   "four one-chip replicas cannot be placed")
            import jax

            _check(jax.default_backend() == "cpu",
                   "the driver of actor replicas must stay off the TPU, "
                   f"it is on {jax.default_backend()!r}")
        else:
            params = params()  # one copy for every in-process object

        # oracle first: it also fills the compile cache the replicas share
        oracle = make(Oracle, params, cfg, max_batch)
        want = _call(oracle, "run", prompts, max_new)
        if actors:
            ray_tpu.kill(oracle)  # gives its chip back
        _check(want["device"]["platform"] == expect_platform,
               f"the oracle ran on {want['device']}")

        n_each = 2 if actors else 1
        prefill = [make(PrefillServer, params, cfg) for _ in range(n_each)]
        decode = [make(DecodeServer, params, cfg, max_batch=max_batch)
                  for _ in range(n_each)]
        replicas = prefill + decode
        router = DisaggRouter(decode=decode, prefill=prefill,
                              max_queue_depth=len(plan))
        gateway = GatewayServer(router, model="gpt2",
                                vocab_size=cfg.vocab_size,
                                max_tokens_cap=max_new,
                                request_timeout_s=request_timeout_s)
        host, port = gateway.ready()

        got: Dict[str, Dict[str, Any]] = {}

        def send(req: Dict[str, Any]) -> None:
            body = {"model": "gpt2", "prompt": req["prompt"],
                    "max_tokens": max_new, "stream": bool(req.get("stream"))}
            try:
                got[req["name"]] = _post(host, port, body,
                                         request_timeout_s)
            except Exception as e:  # noqa: BLE001 - reported per request
                got[req["name"]] = {"status": None,
                                    "text": f"{type(e).__name__}: {e}"}

        for stage in sorted({r["stage"] for r in plan}):
            threads = [threading.Thread(target=send, args=(r,))
                       for r in plan if r["stage"] == stage]
            for t in threads:
                t.start()
            for t in threads:
                t.join(request_timeout_s + 30.0)
            _check(not any(t.is_alive() for t in threads),
                   f"a stage {stage} request is still running after "
                   f"{request_timeout_s + 30.0:.0f} s")

        for req, expect in zip(plan, want["tokens"]):
            r = got[req["name"]]
            _check(r["status"] == 200,
                   f"request {req['name']}: status {r['status']}: "
                   f"{r['text']}")
            tokens = [int(t) for t in r["text"].split()]
            _check(len(expect) == max_new and tokens == expect,
                   f"request {req['name']} ({len(req['prompt'])} prompt "
                   f"tokens): gateway {tokens} != oracle {expect}")
        _check(got["D-stream"].get("done") is True,
               "the stream did not end with [DONE]")
        _check(got["D-stream"]["text"] == got["D"]["text"],
               "the stream's concatenated deltas differ from the "
               "non-streamed body of the same prompt")

        rstats = router.stats()
        _check(rstats["completed"] == len(plan) and rstats["shed"] == 0,
               f"router completed {rstats['completed']} of {len(plan)}, "
               f"shed {rstats['shed']}")
        stats = [_call(r, "stats") for r in replicas]
        reused = sum(s.get("reused_tokens", 0) for s in stats)
        _check(reused >= shared_prefix,
               f"the shared-prefix pair reused {reused} tokens, expected "
               f"at least {shared_prefix}")
        devices = [d for tier in rstats["replica_devices"].values()
                   for d in tier.values()]
        _check(len(devices) == len(replicas) and all(
            d and d["platform"] == expect_platform for d in devices),
            f"not every replica computes on {expect_platform!r}: {devices}")
        if actors:
            _check(len({d["visible_chips"] for d in devices}) == 4
                   and len({d["pid"] for d in devices}) == 4
                   and all(d["local_devices"] == 1 for d in devices),
                   f"four replicas are not on four chips, one process "
                   f"each: {devices}")
        runtimes = [want["runtime"]] + [s["runtime"] for s in stats]
        if not actors:
            runtimes = runtimes[:1]  # one process: one set of counters
        peaks = [r["peak_bytes_in_use"] for r in runtimes
                 if r["peak_bytes_in_use"] is not None]
        return {
            "ok": True, "actors": actors, "requests": len(plan),
            "prompt_lens": sorted({len(p) for p in prompts}),
            "max_tokens": max_new, "reused_tokens": reused,
            "replicas": [{k: d[k] for k in ("platform", "device_kind",
                                            "device_id", "visible_chips",
                                            "pid")} for d in devices],
            "router": {k: rstats[k] for k in ("completed", "shed",
                                              "dispatched")},
            # set-up time: compile (or cache fetch) seconds, every process
            "setup_s": round(sum(r["compile_cache"]["compile_s"]
                                 for r in runtimes), 1),
            "compile_cache": {
                k: sum(r["compile_cache"][k] for r in runtimes)
                for k in ("hits", "misses", "compiles")},
            "peak_bytes_in_use": max(peaks) if peaks else None,
            "object_store": store,
        }
    finally:
        if gateway is not None:
            gateway.stop()
        for r in replicas:
            if actors:
                ray_tpu.kill(r)
            elif hasattr(r, "stop"):
                r.stop()
        ray_tpu.shutdown()


# ------------------------------------------------------- children, parent


def _child(phase: str, record_path: str, actors: bool) -> None:
    """One phase in its own process: the only owner of the chips while it
    lives (or, hosting actor replicas, an owner of none)."""
    # a phase that runs out of time is asked to stop before it is killed,
    # so that the cluster it started stops its workers
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.path.insert(0, ROOT)
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.util.compile_cache import enable_compile_cache

    try:
        cache_dir = enable_compile_cache()
        cfg = GPT2Config.small()
        if phase == "train":
            facts = _require_tpu()
            record = train_phase(cfg, hlo_dump=os.path.join(
                OUT_DIR, "chip_smoke_custom_calls.txt"))
        else:
            # hosting actor replicas, this is the CPU-held driver's view
            facts = _device_facts() if actors else _require_tpu()
            record = serve_phase(cfg, actors=actors)
            for d in record["replicas"]:
                print(f"chip_smoke: replica {d}", flush=True)
        record.update(device=facts, cache_dir=cache_dir)
    except BaseException as e:
        # the parent puts the reason on its last line; the traceback
        # goes where tracebacks go
        record = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        raise
    finally:
        with open(record_path, "w") as f:
            json.dump(record, f)


def _run_phase(phase: str, actors: bool = False) -> Dict[str, Any]:
    """Run one phase as a child and return its record; raises when the
    child fails, leaves no record, or outlives its time."""
    record_path = os.path.join(OUT_DIR, f"chip_smoke_{phase}.json")
    if os.path.exists(record_path):
        os.unlink(record_path)  # nothing read here is from an earlier run
    env = dict(os.environ)
    if actors:
        # the driver of actor replicas must hold no chip
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--record", record_path] + (["--actors"] if actors else [])
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, start_new_session=True)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S[phase])
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=30.0)
        except subprocess.TimeoutExpired:
            pass
        raise RuntimeError(
            f"phase {phase} ran out of time "
            f"({PHASE_TIMEOUT_S[phase]:.0f} s)") from None
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    record: Dict[str, Any] = {}
    if os.path.exists(record_path):
        with open(record_path) as f:
            record = json.load(f)
    if rc != 0 or record.get("ok") is not True:
        raise RuntimeError(
            f"phase {phase} failed with exit code {rc}: "
            f"{record.get('error', 'it left no record')}")
    record["wall_s"] = round(time.monotonic() - t0, 1)
    return record


def result_record(device: Dict[str, Any]) -> Dict[str, Any]:
    """What the last stdout line says once every phase has passed: these
    keys and no others. `device` is the train child's view, the process
    that held every chip."""
    return {"ok": True,
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=sorted(PHASE_TIMEOUT_S),
                    help=argparse.SUPPRESS)  # set by the parent only
    ap.add_argument("--record", help=argparse.SUPPRESS)
    ap.add_argument("--actors", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        _child(args.phase, args.record, args.actors)
        return 0

    try:
        _check(os.path.isdir(os.path.join(ROOT, "ray_tpu")),
               f"no ray_tpu package beside {os.path.abspath(__file__)}")
        os.makedirs(OUT_DIR, exist_ok=True)
        train = _run_phase("train")
        device = train.pop("device")
        # four one-chip replicas need four chips; below that the replicas
        # share the serve child's process and its chip
        serve = _run_phase("serve", actors=device["count"] >= 4)
        serve_device = serve.pop("device")
    except Exception as e:  # noqa: BLE001 - every failure ends the run
        reason = f"chip_smoke FAILED: {type(e).__name__}: {e}"
        print(reason, file=sys.stderr, flush=True)
        print(reason, flush=True)
        return 1
    cache_dir = train.pop("cache_dir")
    serve.pop("cache_dir")
    result = result_record(device)
    summary = {
        **result,
        "jax": device["jax"],
        "phases": {"train": train, "serve": serve},
        "serve_driver_platform": serve_device["platform"],
        "cache_dir": cache_dir,
        "object_store": serve["object_store"],
        "claim": None,
    }
    line = json.dumps(summary)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    print(f"chip_smoke: record {line}", flush=True)
    # the last line is the result and nothing but the result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
