"""Sampler actors: the Sebulba half of the Podracer split.

Each sampler is one actor process wrapping a
``ContinuousBatchingEngine`` behind a ``WeightSync``: the engine
decodes rollouts continuously while the sync thread prefetches each
newly published version's chunks and hot-swaps BETWEEN decode ticks —
the framework keeps the sampler fresh; generation never restarts,
in-flight requests keep their KV caches and continue under the new
weights from the next tick on.

A rollout is a small host-side dict::

    {"prompt": int32[...], "completion": int32[...],
     "scores": float32[...],             # per-token logprobs
     "weights_version": int,             # serving when it COMPLETED
     "weights_version_start": int,       # serving when it was submitted
     "sampler": str, "ts": float}

A swap landing mid-rollout means mixed provenance: start != end tags
it (a PPO-style consumer should drop or re-weight those; plain
distillation does not care).

Completed rollouts are pushed to the :class:`RolloutBuffer`; a full
buffer REJECTS the overflow and the sampler pauses generation (holding
the rejected rollouts for retry) — backpressure propagates to the
engine instead of growing an unbounded queue.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.util.telemetry import Pusher, emit

from .metrics import online_metrics


def default_prompt_fn(vocab_size: int, min_len: int = 2,
                      max_len: int = 8) -> Callable:
    """Random-token prompt generator bounded to the model's vocab (the
    one default both RolloutSampler and OnlineTrainer use)."""

    def prompt_fn(rng: np.random.Generator) -> List[int]:
        n = int(rng.integers(min_len, max_len + 1))
        return rng.integers(1, max(2, int(vocab_size)),
                            size=n).tolist()

    return prompt_fn


class RolloutSampler:
    """Actor body for one sampler (spawn via :func:`spawn_samplers` or
    ``ray_tpu.remote(RolloutSampler).remote(...)``).

    `model_factory()` runs inside the actor and returns
    ``(template_params, config)`` — the template's shardings/dtypes are
    the sampler's serving layout (reshard-on-fetch), `config` is any
    family the engine knows (GPT2Config, LlamaConfig)."""

    def __init__(self, sampler_id: str, weights_name: str,
                 model_factory: Callable[[], Any], buffer: Any, *,
                 max_new_tokens: int = 16,
                 eos_token: Optional[int] = None,
                 min_version: int = 1,
                 wait_timeout_s: float = 120.0,
                 max_batch: int = 2,
                 prompt_fn: Optional[Callable] = None,
                 seed: int = 0,
                 poll_interval_s: float = 0.05,
                 prefetch: bool = True):
        from ray_tpu import weights as wts
        from ray_tpu.models.engine import ContinuousBatchingEngine

        self.sampler_id = sampler_id
        self.weights_name = weights_name
        self.buffer = buffer
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = eos_token
        self._rng = np.random.default_rng(seed)
        template, self.config = model_factory()
        self.prompt_fn = prompt_fn or default_prompt_fn(
            getattr(self.config, "vocab_size", 256))
        # the learner publishes the first version before samplers spawn;
        # wait for it rather than serving uninitialized weights
        self._sub = wts.WeightSubscriber(weights_name)
        version = self._sub.wait_for_version(min_version,
                                             timeout=wait_timeout_s)
        params = self._sub.fetch(version=version, like=template)
        self.engine = ContinuousBatchingEngine(
            params, self.config, max_batch=max_batch,
            params_version=version)
        self.sync = wts.WeightSync(
            self.engine, weights_name, template=params,
            consumer=sampler_id, poll_interval_s=poll_interval_s,
            subscriber=self._sub, prefetch=prefetch)
        self.rollouts = 0
        self.rollout_tokens = 0
        self.backpressure_waits = 0
        self._seen_version = version
        # staleness high-water mark, probed at every rollout boundary —
        # the loop's freshness invariant (<= 1) is asserted from this
        self.max_staleness: Optional[int] = None
        self._held: List[Dict[str, Any]] = []  # rejected, awaiting retry
        self.run_error: Optional[str] = None  # why the loop died, if it did
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pusher = Pusher("online", f"sampler/{self.sampler_id}")

    # ---------------------------------------------------------- lifecycle

    def start(self) -> bool:
        """Begin the rollout loop on a background thread (the actor's
        RPC loop stays free for status()/stop())."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"sampler-{self.sampler_id}")
            self._thread.start()
        return True

    def stop(self) -> Dict[str, Any]:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        self.sync.stop()  # closes the shared subscriber too
        self.engine.stop()
        st = self.status()
        self._push_telemetry(force=True)
        return st

    # --------------------------------------------------------------- loop

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                if self._held:
                    # backpressure: the buffer rejected these — retry
                    # before generating anything new. Telemetry still
                    # pushes: the learner's publication gate reads
                    # serving_version from these snapshots, and a
                    # frozen one would defer publishes on stale data
                    if not self._flush():
                        self.backpressure_waits += 1
                        self._push_telemetry()
                        self._stop.wait(0.02)
                        continue
                seen = self._seen_version
                self._held.append(self._rollout_one())
                self._flush()
                # a hot swap is what the learner's publication gate
                # waits to read: its snapshot goes out at once
                self._push_telemetry(force=self._seen_version != seen)
            except Exception as e:  # noqa: BLE001 — a dead rollout
                # thread must be VISIBLE: record the cause, push a
                # final snapshot, and stop (a healthy-looking actor
                # with a silently-dead loop would hang the learner in
                # data_wait forever)
                self.run_error = f"{type(e).__name__}: {e}"
                self._push_telemetry(force=True)
                return

    def _rollout_one(self) -> Dict[str, Any]:
        prompt = list(self.prompt_fn(self._rng))
        version_start = self.engine.params_version
        stream = self.engine.stream(prompt, self.max_new_tokens,
                                    self.eos_token)
        completion = list(stream)
        scores = stream.scores
        version = self.engine.params_version
        self.rollouts += 1
        self.rollout_tokens += len(completion)
        m = online_metrics()
        m["rollouts"].inc(1, tags={"sampler": self.sampler_id})
        m["rollout_tokens"].inc(len(completion),
                                tags={"sampler": self.sampler_id})
        emit("online", {"kind": "rollout", "sampler": self.sampler_id,
                        "tokens": len(completion),
                        "weights_version": version})
        if version is not None and version != self._seen_version:
            # the sync thread swapped while we decoded: mark it in the
            # online lane (the weights lane has the fabric-side marker)
            emit("online", {"kind": "swap", "sampler": self.sampler_id,
                            "from_version": self._seen_version,
                            "to_version": version})
            self._seen_version = version
        return {"prompt": np.asarray(prompt, np.int32),
                "completion": np.asarray(completion, np.int32),
                "scores": np.asarray(scores, np.float32),
                "weights_version": version,
                "weights_version_start": version_start,
                "sampler": self.sampler_id, "ts": time.time()}

    def _flush(self) -> bool:
        """Push held rollouts to the buffer; True when all landed."""
        import ray_tpu

        if not self._held:
            return True
        accepted = ray_tpu.get(
            self.buffer.put.remote(list(self._held)), timeout=60.0)
        del self._held[:accepted]
        return not self._held

    # ---------------------------------------------------------- telemetry

    def status(self) -> Dict[str, Any]:
        sync = self.sync.status()
        # the sync loop samples staleness every poll cycle; fold its
        # high-water mark into ours
        for st in (sync["staleness_versions"],
                   sync["max_staleness_versions"]):
            if st is not None:
                self.max_staleness = st if self.max_staleness is None \
                    else max(self.max_staleness, st)
        return {
            "role": "sampler", "sampler": self.sampler_id,
            "weights_name": self.weights_name,
            "rollouts": self.rollouts,
            "rollout_tokens": self.rollout_tokens,
            "held": len(self._held),
            "backpressure_waits": self.backpressure_waits,
            "run_error": self.run_error,
            "max_staleness_versions": self.max_staleness,
            "serving_version": sync["serving_version"],
            "latest_version": sync["latest_version"],
            "staleness_versions": sync["staleness_versions"],
            "registry_reachable": sync["registry_reachable"],
            "swap_count": sync["swap_count"],
            "prefetch_bytes": sync["prefetch_bytes"],
            "rpc_bytes": sync["rpc_bytes"],
            "shm_bytes": sync["shm_bytes"],
            "fetched_bytes": sync["fetched_bytes"],
        }

    def _push_telemetry(self, force: bool = False) -> None:
        self._pusher.push(self.status, force=force)


def spawn_samplers(num_samplers: int, weights_name: str,
                   model_factory: Callable[[], Any], buffer: Any, *,
                   name_prefix: str = "sampler",
                   **sampler_kwargs) -> List[Any]:
    """Spawn N sampler actors (one process each) against one weight set
    and one buffer; returns the actor handles. Each gets a distinct
    sampler id and rng seed."""
    import ray_tpu

    base_seed = int(sampler_kwargs.pop("seed", 0))
    actor_cls = ray_tpu.remote(RolloutSampler)
    return [actor_cls.remote(
        f"{name_prefix}-{i}", weights_name, model_factory, buffer,
        seed=base_seed + i, **sampler_kwargs)
        for i in range(num_samplers)]
