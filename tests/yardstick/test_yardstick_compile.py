"""Compile rehearsal: each cell's programs at their real size, compiled
for a described (not attached) v5e chip, so that what the chip's compiler
would refuse is refused here, at no chip time. Nothing runs, so nothing
here is a time or a rate. All in one file and inside fixtures, as the
on-chip-measurement guide asks: only the worker that is given this file
loads the TPU's library."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a described device's programs cannot be read back from the
    # persistent cache: keep them out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cell(name):
    from benchmarks.harness import traffic
    from benchmarks.harness.configs import load_config, program_config

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[name]
    conf = load_config(cell["config"])
    mix = traffic.load_json("traffic", cell["traffic"])
    window = mix["max_seq_len"] if mix["kind"] == "serve" else mix["seq"]
    return conf, mix, program_config(conf, window)


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _param_shapes(conf, cfg, sharding):
    import jax

    from benchmarks.harness.configs import init_params

    return _shapes(jax.eval_shape(lambda: init_params(conf, cfg, 0)),
                   sharding)


def _total_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


@pytest.mark.parametrize("cell", ["mistral-chat", "mistral-summarize",
                                  "gpt2-chat"])
def test_tick_compiles_for_the_chip(cell, one_chip):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import engine
    from ray_tpu.models.generate import _model_fns

    conf, mix, cfg = _cell(cell)
    params = _param_shapes(conf, cfg, one_chip)
    batch = int(mix["max_batch"])
    cache = _shapes(jax.eval_shape(
        lambda: _model_fns(cfg)[1](cfg, batch)), one_chip)
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    compiled = engine._tick.lower(params, cfg, cache, vec, vec).compile()
    # beside the program: the paged pool, as large as the slab again
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert _total_bytes(compiled) + slab < HBM_BYTES


@pytest.mark.parametrize("cell", ["mistral-chat", "mistral-summarize",
                                  "gpt2-chat"])
def test_longest_prefill_compiles_for_the_chip(cell, one_chip):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import traffic
    from ray_tpu.models import engine
    from ray_tpu.models.generate import _model_fns

    conf, mix, cfg = _cell(cell)
    params = _param_shapes(conf, cfg, one_chip)
    longest = traffic.prompt_lengths(mix)[-1]
    batch = int(mix["max_batch"])
    cache = jax.eval_shape(lambda: _model_fns(cfg)[1](cfg, batch))
    heads_dim = cache[0]["k"].shape[2:]
    dtype = cache[0]["k"].dtype
    suffix = jax.ShapeDtypeStruct((1, longest), jnp.int32,
                                  sharding=one_chip)
    empty = jax.ShapeDtypeStruct((len(cache), 0) + heads_dim, dtype,
                                 sharding=one_chip)
    compiled = engine._prefill_paged.lower(
        params, suffix, cfg, empty, empty).compile()
    # beside the program: the decode slab and the pool of the same size
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert _total_bytes(compiled) + 2 * slab < HBM_BYTES


def test_train_step_compiles_for_the_chip(topo, one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt2 import gpt2_loss, gpt2_partition_specs
    from ray_tpu.train import TrainStep

    conf, mix, cfg = _cell("gpt2-train-b32")
    # the trainer's own step, on a mesh of the one described chip
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1),
                ("dp", "fsdp", "tp"))
    rep = NamedSharding(mesh, P())
    opt = optax.adamw(mix["optimizer"]["lr"],
                      weight_decay=mix["optimizer"]["weight_decay"])
    ts = TrainStep(
        lambda p, b: gpt2_loss(p, b["tokens"], b["targets"], cfg),
        opt, mesh, gpt2_partition_specs(cfg))
    params = _param_shapes(conf, cfg, rep)
    state = {"params": params,
             "opt_state": _shapes(jax.eval_shape(opt.init, params), rep),
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    tok = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32,
                               sharding=NamedSharding(mesh, ts.data_spec))
    # the kernels' dispatch asks jax.default_backend(), which is the CPU
    # here: steer it from the test so that the Mosaic path is what compiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(ts._step, donate_argnums=(0,)).lower(
            state, {"tokens": tok, "targets": tok}).compile()
    text = compiled.as_text()
    # both Pallas kernels are in the compiled step
    assert text.count("tpu_custom_call") >= 3 * conf["n_layer"] + 3
    assert _total_bytes(compiled) < HBM_BYTES
