"""OnlineTrainer: the closed loop — learner gang + sampler actors +
rollout buffer, wired through the live weight fabric.

The in-tree example workload is online distillation: the learner (a
``JaxTrainer`` spmd gang reusing ``TrainStep``/gang formation) trains
the model to imitate the completions its OWN samplers generate through
the continuous-batching engine, and publishes refreshed weights every
``publish_every`` steps via ``train.report(publish_weights=...,
weights_delta=True)`` — delta publication ships only the leaves the
optimizer moved, subscriber prefetch pulls them while the engines still
decode the old version, and the hot swap lands between decode ticks. A
positional-embedding freeze (``frozen_leaves``) is both common
distillation practice and what makes the delta path visibly cheaper
than a full publish.

The loop's invariant: sampler staleness stays <= 1 version (each
sampler tracks its high-water mark; ``online_status()`` aggregates it)
while the learner steps continuously — rollout generation, ingestion,
and weight refresh all overlap the device step.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.util.telemetry import Pusher, emit

from .buffer import RolloutBuffer, from_rollouts
from .sampler import spawn_samplers

_ONLINE_AXES = ("dp", "fsdp", "tp")


@dataclass
class OnlineConfig:
    """Knobs of the online loop (tiny defaults — production runs scale
    num_samplers / batch_size / num_steps, not the structure)."""

    num_samplers: int = 2
    num_steps: int = 16
    batch_size: int = 8
    publish_every: int = 2          # learner steps between publishes
    delta: bool = True              # delta-publish refreshed weights
    # staleness gate: defer a due publish while any sampler still
    # serves an older version than the last one published — the
    # learner keeps stepping at full speed, only the publication
    # cadence adapts, and sampler staleness stays <= 1 by
    # construction. 0 disables the gate; max_publish_skips bounds the
    # deferral so a dead sampler cannot silence publication forever.
    gate_on_staleness: bool = True
    max_publish_skips: int = 50
    buffer_capacity: int = 64
    max_new_tokens: int = 12
    max_prompt_len: int = 8
    sampler_max_batch: int = 2
    sampler_prefetch: bool = True
    learning_rate: float = 1e-3
    weights_name: str = "online"
    # leaves (top-level param keys) excluded from the optimizer — frozen
    # leaves never change, so delta publication skips them
    frozen_leaves: tuple = ("wpe",)
    seed: int = 0


@dataclass
class OnlineResult:
    """What fit() hands back: the learner's Result plus the loop's own
    accounting (per-sampler stats incl. the staleness high-water mark,
    buffer totals, the registry's final listing)."""

    metrics: Dict[str, Any] = field(default_factory=dict)
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    sampler_stats: List[Dict[str, Any]] = field(default_factory=list)
    buffer_stats: Dict[str, Any] = field(default_factory=dict)
    weight_versions: Dict[str, Any] = field(default_factory=dict)
    max_staleness_versions: Optional[int] = None
    error: Optional[BaseException] = None


def _pad_batch(rollouts: List[Dict[str, Any]], seq_len: int
               ) -> Dict[str, np.ndarray]:
    """Collate rollouts into fixed-shape LM arrays (runs on the
    prefetch thread): tokens = prompt + completion padded to seq_len,
    targets = next token, mask = 1 on completion predictions only (the
    distillation objective imitates the SAMPLED tokens, not the
    prompt)."""
    n = len(rollouts)
    tokens = np.zeros((n, seq_len), np.int32)
    mask = np.zeros((n, seq_len - 1), np.float32)
    versions = np.zeros(n, np.int64)
    for i, r in enumerate(rollouts):
        seq = np.concatenate([r["prompt"], r["completion"]])[:seq_len]
        tokens[i, :len(seq)] = seq
        p = len(r["prompt"])
        # predictions at positions p-1 .. len(seq)-2 produce the
        # completion tokens — that is the imitation region
        mask[i, p - 1:len(seq) - 1] = 1.0
        versions[i] = int(r.get("weights_version") or 0)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
            "mask": mask, "versions": versions}


def next_publish_version(name: str) -> int:
    """The version a NEW publisher of `name` should start at:
    publication numbering continues after whatever the registry
    already holds, so a second trainer (or a restarted one) against a
    live weights name never collides with an existing version. Shared
    by OnlineTrainer's initial full publish and the per-tenant
    TenantLoraTrainer (online/lora.py)."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        raise RuntimeError("ray_tpu.init() must be called before "
                           "publishing weights")
    return int(w.conductor.call("weights_latest_version", name,
                                timeout=10.0) or 0) + 1


def _distill_loss_fn(model_config) -> Callable:
    """Masked next-token CE over the completion region — the online
    distillation objective (sequence-level: imitate the sampler's
    greedy tokens)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import gpt2_hidden

    def loss_fn(params, batch):
        x = gpt2_hidden(params, batch["tokens"], model_config)
        logits = jnp.dot(x, params["wte"].T,
                         preferred_element_type=jnp.float32)
        logits = logits[..., :model_config.vocab_size]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                 axis=-1)[..., 0]
        mask = batch["mask"]
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    return loss_fn


def _learner_mesh():
    """All local devices on the canonical (dp, fsdp, tp) axes — dp
    carries the data, the model axes collapse to 1 so the GPT-2 spec
    tree reads as replicated."""
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs), 1, 1), _ONLINE_AXES)


class OnlineTrainer:
    """Compose a learner gang with N samplers over one weight-fabric
    name and run the online-distillation loop end to end."""

    def __init__(self, model_config: Any = None, *,
                 config: Optional[OnlineConfig] = None,
                 run_config: Any = None,
                 optimizer: Any = None,
                 prompt_fn: Optional[Callable] = None,
                 loss_fn: Optional[Callable] = None):
        if model_config is None:
            import dataclasses

            import jax.numpy as jnp

            from ray_tpu.models.gpt2 import GPT2Config

            model_config = dataclasses.replace(GPT2Config.tiny(),
                                               dtype=jnp.float32)
        self.model_config = model_config
        self.config = config or OnlineConfig()
        self.run_config = run_config
        self.optimizer = optimizer
        self.prompt_fn = prompt_fn
        self.loss_fn = loss_fn

    # ------------------------------------------------------------ pieces

    def _make_optimizer(self):
        if self.optimizer is not None:
            return self.optimizer
        import jax
        import optax

        frozen = tuple(self.config.frozen_leaves)

        def label_fn(params):
            # NB optax.masked would pass the masked-out RAW GRADIENT
            # through to apply_updates — multi_transform + set_to_zero
            # is what actually freezes a leaf (bit-identical across
            # steps, which is what lets delta publication skip it)
            return {k: jax.tree.map(
                lambda _: "freeze" if k in frozen else "train", v)
                for k, v in params.items()}

        return optax.multi_transform(
            {"train": optax.adam(self.config.learning_rate),
             "freeze": optax.set_to_zero()}, label_fn)

    def _seq_len(self) -> int:
        return min(self.model_config.max_seq_len,
                   self.config.max_prompt_len
                   + self.config.max_new_tokens)

    def _model_factory(self):
        """Serializable factory the sampler actors run: template params
        (the sampler's serving layout — single-process default device)
        + the model config."""
        model_config = self.model_config
        seed = self.config.seed

        def factory():
            import jax

            from ray_tpu.models.gpt2 import gpt2_init

            return (gpt2_init(model_config, jax.random.PRNGKey(seed)),
                    model_config)

        return factory

    def _default_prompt_fn(self):
        from .sampler import default_prompt_fn

        return default_prompt_fn(self.model_config.vocab_size,
                                 max_len=self.config.max_prompt_len)

    # --------------------------------------------------------------- fit

    def fit(self) -> OnlineResult:
        import jax

        import ray_tpu
        from ray_tpu import weights as wts
        from ray_tpu.models.gpt2 import gpt2_init
        from ray_tpu.train import JaxTrainer, RunConfig

        cfg = self.config
        model_config = self.model_config
        # the starting point both sides share — published FULL before
        # any sampler exists, so samplers boot onto it. Numbered after
        # whatever the registry already holds under this name (a second
        # fit() against a live cluster must not collide with v1).
        start_version = next_publish_version(cfg.weights_name)
        initial = gpt2_init(model_config, jax.random.PRNGKey(cfg.seed))
        wts.publish(initial, name=cfg.weights_name,
                    version=start_version)
        buffer = ray_tpu.remote(RolloutBuffer).remote(
            cfg.buffer_capacity, name=cfg.weights_name)
        samplers = spawn_samplers(
            cfg.num_samplers, cfg.weights_name, self._model_factory(),
            buffer,
            max_new_tokens=cfg.max_new_tokens,
            max_batch=cfg.sampler_max_batch,
            min_version=start_version,
            prompt_fn=self.prompt_fn or self._default_prompt_fn(),
            prefetch=cfg.sampler_prefetch,
            seed=cfg.seed)
        out = OnlineResult()
        try:
            ray_tpu.get([s.start.remote() for s in samplers],
                        timeout=300.0)
            stream = from_rollouts(
                buffer, batch_size=cfg.batch_size,
                collate_fn=lambda rs, _T=self._seq_len():
                    _pad_batch(rs, _T))
            trainer = JaxTrainer(
                self._train_fn(start_version),
                datasets={"rollouts": stream},
                run_config=self.run_config
                or RunConfig(name=f"online/{cfg.weights_name}"))
            result = trainer.fit()
            out.metrics = result.metrics
            out.metrics_history = result.metrics_history
            out.error = result.error
        finally:
            for s in samplers:
                try:
                    out.sampler_stats.append(ray_tpu.get(
                        s.stop.remote(), timeout=60.0))
                except Exception:  # noqa: BLE001 — sampler died
                    pass
                try:
                    ray_tpu.kill(s)
                except Exception:  # noqa: BLE001
                    pass
            try:
                out.buffer_stats = ray_tpu.get(buffer.stats.remote(),
                                               timeout=30.0)
            except Exception:  # noqa: BLE001
                pass
            try:
                ray_tpu.kill(buffer)
            except Exception:  # noqa: BLE001
                pass
        stale = [s.get("max_staleness_versions")
                 for s in out.sampler_stats
                 if s.get("max_staleness_versions") is not None]
        out.max_staleness_versions = max(stale) if stale else None
        try:
            from ray_tpu.util import state

            out.weight_versions = state.weight_versions(cfg.weights_name)
        except Exception:  # noqa: BLE001 — cluster already down
            pass
        return out

    def _train_fn(self, start_version: int = 1) -> Callable:
        """The learner body (runs under JaxTrainer's session): TrainStep
        over the local mesh, batches pulled from the rollout shard with
        the pull accounted as flight-recorder data_wait, weights
        delta-published every K steps."""
        cfg = self.config
        model_config = self.model_config
        optimizer = self._make_optimizer()
        loss_fn = self.loss_fn or _distill_loss_fn(model_config)
        weights_name = cfg.weights_name

        def train_fn(_tcfg):
            import jax
            from jax.sharding import PartitionSpec as P

            from ray_tpu import train
            from ray_tpu.models.gpt2 import (gpt2_init,
                                             gpt2_partition_specs)
            from ray_tpu.train.trainer import TrainStep

            mesh = _learner_mesh()
            step_fn = TrainStep(
                lambda p, b: loss_fn(p, b), optimizer, mesh,
                gpt2_partition_specs(model_config),
                data_spec=P(("dp", "fsdp")))
            params = gpt2_init(model_config,
                               jax.random.PRNGKey(cfg.seed))
            state = step_fn.init_state(params)
            shard = train.get_dataset_shard("rollouts")
            batches = shard.iter_batches()
            timer = train.get_step_timer()
            ingested_rollouts = 0
            ingested_tokens = 0
            # the initial full publish went out before the samplers
            # spawned; the learner numbers its publications after it
            published = start_version
            publish_due = False
            publish_skips = 0
            ctx = train.get_context()
            for s in range(1, cfg.num_steps + 1):
                with timer.phase("data_wait"):
                    batch = next(batches)
                versions = batch.pop("versions")
                ingested_rollouts += int(versions.shape[0])
                ingested_tokens += int(batch["mask"].sum())
                state, aux = step_fn(state, batch)
                loss = float(aux["loss"])
                _learner_telemetry(
                    ctx, kind="ingest", step=s,
                    rollouts=int(versions.shape[0]),
                    min_version=int(versions.min()),
                    max_version=int(versions.max()))
                metrics = {"step": s, "loss": loss,
                           "ingested_rollouts": ingested_rollouts,
                           "ingested_tokens": ingested_tokens}
                publish_due = publish_due or s % cfg.publish_every == 0
                gated = (publish_due and cfg.gate_on_staleness
                         and publish_skips < cfg.max_publish_skips
                         and not _samplers_caught_up(published,
                                                     weights_name))
                if publish_due and not gated:
                    # versions number PUBLICATIONS consecutively (v1 =
                    # the initial publish), so the staleness gauge
                    # counts publications-behind and the <= 1 invariant
                    # is meaningful; delta ships only the moved leaves
                    train.report(metrics,
                                 publish_weights=state["params"],
                                 weights_name=weights_name,
                                 weights_delta=cfg.delta,
                                 weights_version=published + 1)
                    published += 1
                    publish_due = False
                    publish_skips = 0
                    _learner_telemetry(ctx, kind="publish", step=s,
                                       version=published,
                                       delta=cfg.delta)
                else:
                    if gated:
                        publish_skips += 1
                    train.report(metrics)
                _learner_stats(ctx, steps=s, last_loss=loss,
                               ingested_rollouts=ingested_rollouts,
                               ingested_tokens=ingested_tokens,
                               published_version=published,
                               publish_skips=publish_skips)

        return train_fn


def _samplers_caught_up(last_version: int, weights_name: str,
                        max_age_s: float = 10.0) -> bool:
    """Every live sampler of THIS loop serves `last_version` (or
    newer) — the publication gate's predicate. Only snapshots for this
    weights_name count, and only recent ones from loops still running
    (another loop's samplers — or a dead/errored one's frozen
    snapshot — must not gate this learner). Unreachable conductor or
    no sampler telemetry reads as caught up (the gate must never
    deadlock the learner)."""
    import time

    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        return True
    try:
        st = w.conductor.call("get_status", "online", timeout=5.0)
    except Exception:  # noqa: BLE001 — conductor mid-restart
        return True
    now = time.time()
    for s in (st.get("samplers") or {}).values():
        if s.get("weights_name") != weights_name:
            continue
        if s.get("run_error") or now - s.get("ts", now) > max_age_s:
            continue
        v = s.get("serving_version")
        if v is not None and v < last_version:
            return False
    return True


def _learner_stats(ctx, **stats) -> None:
    from .metrics import online_metrics

    prev = getattr(ctx, "_online_ingested", 0)
    cur = stats.get("ingested_rollouts", prev)
    if cur > prev:
        online_metrics()["ingested_rollouts"].inc(
            cur - prev, tags={"run": ctx.run_id})
    ctx._online_ingested = cur
    Pusher("online", f"learner/{ctx.run_id}").push(
        dict(stats, role="learner", run_id=ctx.run_id), force=True)


def _learner_telemetry(ctx, **event) -> None:
    emit("online", dict(event, run_id=ctx.run_id))
