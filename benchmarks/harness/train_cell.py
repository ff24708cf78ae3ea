"""A training cell: the trainer's normal entry (`JaxTrainer` -> `TrainStep`)
on every chip of the cell, a fresh seeded batch every step from a host-side
input pipeline, each step ended by `block_until_ready` on its loss."""
from __future__ import annotations

import math
import os
import queue
import threading
import time
from typing import Any, Dict

import numpy as np

from . import common, reference
from .configs import (family, init_params, model_shape, program_config,
                      train_flops_per_token, train_program)


class BatchPipeline:
    """The host-side input pipeline: a thread that draws the next token
    batch from the seed and keeps a few ready."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 depth: int = 4):
        self._rng = np.random.default_rng([int(seed), 0xDA7A])
        self._shape = (batch, seq + 1)
        self._vocab = vocab
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="bench-input")
        self._thread.start()

    def _fill(self) -> None:
        while not self._stop.is_set():
            tokens = self._rng.integers(0, self._vocab, self._shape,
                                        dtype=np.int32)
            item = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next(self) -> Dict[str, np.ndarray]:
        return self._q.get(timeout=60.0)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def run_train(run: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import optax

    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import JaxTrainer, RunConfig, TrainStep
    from ray_tpu.util.compile_cache import (compile_cache_counts,
                                            enable_compile_cache)

    conf, traffic = run["conf"], run["traffic"]
    device = common.require_devices(run)
    cache_dir = enable_compile_cache()
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    cfg = program_config(conf, seq)
    opt = traffic["optimizer"]
    loss_fn, specs = train_program(conf, cfg, bool(traffic["remat"]))
    seen: Dict[str, Any] = {"steps": [], "waits": [], "losses": [],
                            "spans": []}
    window = common.TracedWindow(run) if run["trace"] else None

    def train_fn(_config: Dict[str, Any]) -> None:
        devices = jax.devices()[:run["cell"]["chips"]]
        mesh = make_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
        step = TrainStep(
            loss_fn,
            optax.adamw(float(opt["lr"]),
                        weight_decay=float(opt["weight_decay"])),
            mesh, specs)
        pipe = BatchPipeline(run["seed"], batch * len(devices), seq,
                             cfg.vocab_size)
        try:
            params = init_params(conf, cfg, run["seed"])
            first = pipe.next()
            common.mark(run, "params")
            # the reference's loss on the first batch, before the
            # state is donated to the step
            n_ref = int(traffic["reference_sequences"])
            seen["ref_loss"] = reference.mean_loss(
                conf, params, first["tokens"][:n_ref],
                first["targets"][:n_ref])
            seen["program_loss"] = float(jax.jit(loss_fn)(
                params, {k: v[:n_ref] for k, v in first.items()}))
            common.mark(run, "reference")
            state = step.init_state(params)
            # warm-up: the first call compiles (or fetches); one more
            # makes sure nothing is left to trace
            for b in (first, pipe.next()):
                state, m = step(state, b)
                seen["losses"].append(float(m["loss"]))
            common.mark(run, "warm_steps")
            seen["compiled"] = step.compiled
            seen["cache_at_setup"] = compile_cache_counts()
            seen["setup_s"] = time.perf_counter() - run["t_start"]
            tracer = None
            if window is not None:
                tracer = threading.Thread(
                    target=_trace_later, args=(window, seen), daemon=True)
                tracer.start()
            t_begin = time.perf_counter()
            t_end = t_begin + run["seconds"]
            while time.perf_counter() < t_end:
                w0 = time.time()
                t0 = time.perf_counter()
                b = pipe.next()
                t1 = time.perf_counter()
                state, m = step(state, b)
                t2 = time.perf_counter()
                loss = jax.block_until_ready(m["loss"])
                t3 = time.perf_counter()
                seen["waits"].append(t1 - t0)
                seen["steps"].append(t3 - t0)
                seen["losses"].append(float(loss))
                seen["spans"] += [("data_wait", w0, w0 + (t1 - t0)),
                                  ("step_dispatch", w0 + (t1 - t0),
                                   w0 + (t2 - t0)),
                                  ("loss_readback", w0 + (t2 - t0),
                                   w0 + (t3 - t0))]
            seen["elapsed"] = time.perf_counter() - t_begin
            seen["cache_at_end"] = compile_cache_counts()
            if tracer is not None:
                tracer.join(timeout=120.0)
                if tracer.is_alive():
                    raise RuntimeError("the profiler did not stop")
        finally:
            pipe.close()
        seen["n_devices"] = len(devices)

    dispatch.reset_kernel_choices()
    storage = os.path.join(common.REPO_ROOT, ".bench_trainer")
    trainer = JaxTrainer(train_fn, mode="spmd", run_config=RunConfig(
        name="bench", storage_path=storage))
    result = trainer.fit()
    if result.error is not None:
        raise result.error
    if "trace_error" in seen:
        raise seen["trace_error"]

    n_dev = seen["n_devices"]
    tokens_per_step = batch * n_dev * seq
    steps = seen["steps"]
    if not steps:
        raise RuntimeError("no step finished inside the window")
    # over all the steps and all the time of the window, per chip
    tokens_per_s = tokens_per_step * len(steps) / seen["elapsed"] / n_dev

    # correctness, outside the window: the kernels ran (no silent
    # reference path), every loss is finite, and the program's loss on
    # the reference's sequences agrees with the float32 reference
    tol = traffic["tolerances"]
    choices = {c["op"]: c for c in dispatch.kernel_choices()}
    kernels_ok = run["rehearsal"] or all(
        choices.get(op, {}).get("choice") == "pallas"
        for op in family(conf).train_kernels)
    finite = all(math.isfinite(x) for x in seen["losses"])
    loss_gap = abs(seen["program_loss"] - seen["ref_loss"])
    compiles_in_window = (seen["cache_at_end"]["compiles"]
                          - seen["cache_at_setup"]["compiles"])
    correct = (kernels_ok and finite and loss_gap <= tol["loss_abs"]
               and compiles_in_window == 0)

    memory_extra = 0
    step_bytes = None
    if seen.get("compiled") is not None:
        mem = seen["compiled"].memory_analysis()
        if mem is not None:
            step_bytes = {k: int(getattr(mem, f"{k}_size_in_bytes"))
                          for k in ("temp", "argument", "output", "alias")}
            # the allocator's peak leaves out a program's temporaries on
            # this backend (PERF.md section 5); XLA's own accounting of
            # the step is arguments + outputs - donated aliases + temps
            memory_extra = (step_bytes["temp"] + step_bytes["argument"]
                            + step_bytes["output"] - step_bytes["alias"])

    obs: Dict[str, Any] = {
        "trace": None, "host": [],
        "train": {"step_s": steps, "data_wait_s": seen["waits"],
                  "tokens_per_s": tokens_per_s},
        "counters": {
            "cache_misses_setup": seen["cache_at_setup"]["misses"],
            "compiles_in_window": compiles_in_window},
        "cell": {"conf": conf, "traffic": traffic, "peaks": run["peaks"],
                 "chips": n_dev, "batch": batch, "seq": seq,
                 **{k: v for k, v in model_shape(conf).items()
                    if k != "matmul_params"},
                 "train_flops_per_token": train_flops_per_token(conf, seq)},
    }
    if window is not None:
        obs["trace"] = window.reduce(seen["spans"])
        obs["host"] = obs["trace"]["gaps"]
    common.write_record(run, {
        "workload": run["cell"]["name"], "seed": run["seed"],
        "steps": len(steps), "elapsed_s": seen["elapsed"],
        "tokens_per_s_per_chip": tokens_per_s,
        "setup_s": seen["setup_s"], "cache_dir": cache_dir,
        "marks": run.get("marks"),
        "cache_at_setup": seen["cache_at_setup"],
        "cache_at_end": seen["cache_at_end"],
        "loss_first": seen["losses"][0], "loss_last": seen["losses"][-1],
        "ref_loss": seen["ref_loss"], "program_loss": seen["program_loss"],
        "loss_gap": loss_gap, "kernels": list(choices.values()),
        "step_bytes": step_bytes,
        "allocator_peak": common.memory_peak_bytes(),
        "correct": correct,
        "programs_in_trace": sorted((obs["trace"] or {}).get(
            "programs", {})),
    })
    return common.assemble(
        run, obs,
        end_to_end={"train_tokens_per_s": tokens_per_s,
                    "setup_s": seen["setup_s"]},
        correct=correct, attempted=len(steps), failed=0, device=device,
        memory_extra=memory_extra)


def _trace_later(window: common.TracedWindow, seen: Dict[str, Any]) -> None:
    try:
        time.sleep(window.delay_s)
        window.record()
    except BaseException as e:  # noqa: BLE001 - raised by the main thread
        seen["trace_error"] = e
