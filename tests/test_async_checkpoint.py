"""Async sharded checkpointing (SURVEY.md §7.5; reference persistence
flow train/_internal/storage.py): save returns before I/O completes,
shards are written per-host with a commit marker, and restore reshards
onto a different mesh bit-exactly."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.train import async_checkpoint as ac


def _mesh(axes):
    devs = np.array(jax.devices()[:int(np.prod([n for _, n in axes]))])
    return Mesh(devs.reshape([n for _, n in axes]),
                [a for a, _ in axes])


def _sharded_state(mesh, spec_map, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, spec) in spec_map.items():
        arr = rng.standard_normal(shape).astype(np.float32)
        out[name] = jax.device_put(arr, NamedSharding(mesh, spec))
    out["step"] = jnp.int32(7)
    return out


SPECS = {
    "w_fsdp": ((16, 8), P(("dp", "fsdp"), None)),
    "w_tp": ((8, 16), P(None, "fsdp")),
    "w_rep": ((4, 4), P(None, None)),
}


def test_save_restore_roundtrip_numpy(tmp_path):
    mesh = _mesh([("dp", 2), ("fsdp", 4)])
    state = _sharded_state(mesh, SPECS)
    ckpt = ac.async_save(str(tmp_path / "ck"), state)
    ckpt.wait()
    loaded = ac.restore(str(tmp_path / "ck"))
    for k in SPECS:
        np.testing.assert_array_equal(loaded[k], np.asarray(state[k]))
    assert int(loaded["step"]) == 7


def test_restore_onto_different_mesh_bit_exact(tmp_path):
    """dp=2,fsdp=4 -> dp=8: the review done-criterion."""
    mesh_a = _mesh([("dp", 2), ("fsdp", 4)])
    state = _sharded_state(mesh_a, SPECS, seed=3)
    ac.async_save(str(tmp_path / "ck"), state).wait()

    mesh_b = _mesh([("dp", 8)])
    like = {
        "w_fsdp": jax.device_put(np.zeros((16, 8), np.float32),
                                 NamedSharding(mesh_b, P("dp", None))),
        "w_tp": jax.device_put(np.zeros((8, 16), np.float32),
                               NamedSharding(mesh_b, P(None, "dp"))),
        "w_rep": jax.device_put(np.zeros((4, 4), np.float32),
                                NamedSharding(mesh_b, P(None, None))),
        "step": jnp.int32(0),
    }
    restored = ac.restore(str(tmp_path / "ck"), like=like)
    for k in SPECS:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(state[k]))
        assert restored[k].sharding == like[k].sharding
    assert int(restored["step"]) == 7


# Round-trip property over unequal source/target mesh shapes: the
# weights fabric (ray_tpu.weights) reuses this exact reshard-on-fetch
# path, so its contract is pinned here before anything depends on it.
# Shape (16, 8) divides by every axis product below.
RESHARD_MESHES = [
    ([("dp", 2), ("fsdp", 4)], [("dp", 8)]),
    ([("dp", 8)], [("dp", 2), ("fsdp", 4)]),
    ([("dp", 2), ("fsdp", 4)], [("dp", 4), ("fsdp", 2)]),
    ([("dp", 4), ("fsdp", 2)], [("dp", 2), ("fsdp", 2)]),  # fewer devices
    ([("dp", 2), ("fsdp", 2)], [("dp", 8)]),               # more devices
]


def _axis_specs(axes):
    """A spec set exercising row-, column-, mixed- and un-sharded leaves
    for whatever axis names the mesh has."""
    names = [a for a, _ in axes]
    first = names[0]
    rest = tuple(names[1:]) or None
    return {
        "w_rows": ((16, 8), P(tuple(names), None)),
        "w_cols": ((16, 8), P(None, tuple(names))),
        "w_mixed": ((16, 8), P(first, rest)),
        "w_rep": ((16, 8), P(None, None)),
    }


@pytest.mark.parametrize("src_axes,dst_axes", RESHARD_MESHES)
@pytest.mark.parametrize("seed", [0, 1])
def test_restore_reshard_roundtrip_property(tmp_path, src_axes, dst_axes,
                                            seed):
    """For every (source mesh, target mesh) pair and every sharding
    style, save-then-restore(like=) is bit-exact and lands the
    template's sharding."""
    mesh_src = _mesh(src_axes)
    state = _sharded_state(mesh_src, _axis_specs(src_axes), seed=seed)
    d = str(tmp_path / "ck")
    ac.async_save(d, state).wait()

    mesh_dst = _mesh(dst_axes)
    like = {
        k: jax.device_put(np.zeros(shape, np.float32),
                          NamedSharding(mesh_dst, spec))
        for k, (shape, spec) in _axis_specs(dst_axes).items()}
    like["step"] = jnp.int32(0)
    restored = ac.restore(d, like=like)
    for k in _axis_specs(src_axes):
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(state[k]))
        assert restored[k].sharding == like[k].sharding
    assert int(restored["step"]) == 7


def test_restore_like_dtype_cast_template(tmp_path):
    """A template whose dtype differs from the stored one casts on
    device (the serving layout may run bf16 off an fp32 training
    checkpoint) — sharding still comes from the template."""
    mesh = _mesh([("dp", 2), ("fsdp", 4)])
    state = _sharded_state(mesh, SPECS, seed=9)
    d = str(tmp_path / "ck")
    ac.async_save(d, state).wait()

    mesh_b = _mesh([("dp", 8)])
    like = {
        "w_fsdp": jax.device_put(np.zeros((16, 8), jnp.bfloat16),
                                 NamedSharding(mesh_b, P("dp", None))),
        "w_tp": jax.device_put(np.zeros((8, 16), np.float32),
                               NamedSharding(mesh_b, P(None, "dp"))),
        "w_rep": jax.device_put(np.zeros((4, 4), np.float16),
                                NamedSharding(mesh_b, P(None, None))),
        "step": jnp.int32(0),
    }
    restored = ac.restore(d, like=like)
    assert restored["w_fsdp"].dtype == jnp.bfloat16
    assert restored["w_rep"].dtype == np.float16
    assert restored["w_tp"].dtype == np.float32  # same dtype: no cast
    for k in SPECS:
        np.testing.assert_array_equal(
            np.asarray(restored[k], dtype=np.float32),
            np.asarray(np.asarray(state[k]).astype(like[k].dtype),
                       dtype=np.float32))
        assert restored[k].sharding == like[k].sharding


def test_save_returns_before_write_completes(tmp_path):
    """report/save must not block on disk I/O (async done-criterion)."""
    mesh = _mesh([("dp", 8)])
    state = _sharded_state(mesh, {"w": ((64, 64), P("dp", None))})
    ckpter = ac.AsyncCheckpointer()
    ckpter._test_write_delay = 0.5
    t0 = time.monotonic()
    ckpt = ckpter.save(str(tmp_path / "ck"), state)
    t_return = time.monotonic() - t0
    assert t_return < 0.2, f"save() blocked {t_return:.2f}s"
    assert not ckpt.committed
    ckpt.wait()
    assert ckpt.committed
    total = time.monotonic() - t0
    assert total >= 0.5  # the write really did happen afterwards
    loaded = ac.restore(str(tmp_path / "ck"))
    np.testing.assert_array_equal(loaded["w"], np.asarray(state["w"]))


def test_donation_safety_snapshot_before_return(tmp_path):
    """Mutating (donating) the array right after save() must not corrupt
    the checkpoint — shards are snapshotted to host before returning."""
    mesh = _mesh([("dp", 8)])
    arr = jax.device_put(np.arange(800, dtype=np.float32).reshape(8, 100),
                         NamedSharding(mesh, P("dp", None)))
    ckpter = ac.AsyncCheckpointer()
    ckpter._test_write_delay = 0.3
    ckpt = ckpter.save(str(tmp_path / "ck"), {"w": arr})

    @jax.jit
    def clobber(x):
        return x * 0.0

    arr = clobber(arr)  # original buffer may be reused
    del arr
    ckpt.wait()
    loaded = ac.restore(str(tmp_path / "ck"))
    np.testing.assert_array_equal(
        loaded["w"], np.arange(800, dtype=np.float32).reshape(8, 100))


def test_torn_checkpoint_detected(tmp_path):
    mesh = _mesh([("dp", 2), ("fsdp", 4)])
    state = _sharded_state(mesh, SPECS)
    ac.async_save(str(tmp_path / "ck"), state).wait()
    os.remove(str(tmp_path / "ck" / "commit.0"))
    with pytest.raises(ValueError, match="torn"):
        ac.restore(str(tmp_path / "ck"))


def test_trainer_report_async_checkpoint_overlap(tmp_path):
    """report(checkpoint=async) returns immediately; the manager
    registers at commit time and fit()'s result sees the checkpoint."""
    from ray_tpu.train import JaxTrainer, RunConfig, report

    report_times = []

    def train_fn(cfg):
        mesh = _mesh([("dp", 8)])
        state = _sharded_state(mesh, {"w": ((16, 4), P("dp", None))})
        ckpter = ac.AsyncCheckpointer()
        ckpter._test_write_delay = 0.4
        for step in range(2):
            ck = ckpter.save(str(tmp_path / f"work_ck_{step}"), state)
            t0 = time.monotonic()
            report({"loss": 1.0 - step * 0.1, "step": step}, checkpoint=ck)
            report_times.append(time.monotonic() - t0)

    trainer = JaxTrainer(
        train_fn,
        run_config=RunConfig(name="async_ck",
                             storage_path=str(tmp_path / "exp")))
    result = trainer.fit()
    assert result.error is None
    assert max(report_times) < 0.2, report_times
    assert result.checkpoint is not None
    loaded = ac.restore(result.checkpoint.path)
    assert loaded["w"].shape == (16, 4)


def test_async_then_sync_registration_order(tmp_path):
    """An in-flight async checkpoint reported BEFORE a sync one must rank
    older (recency by report order, not commit order)."""
    from ray_tpu.train import Checkpoint, JaxTrainer, RunConfig, report
    from ray_tpu.train.checkpoint import save_pytree

    def train_fn(cfg):
        mesh = _mesh([("dp", 8)])
        state = _sharded_state(mesh, {"w": ((16, 4), P("dp", None))})
        ckpter = ac.AsyncCheckpointer()
        ckpter._test_write_delay = 0.4  # commits AFTER the sync report
        ck0 = ckpter.save(str(tmp_path / "async0"), state)
        report({"step": 0}, checkpoint=ck0)
        d = str(tmp_path / "sync1")
        save_pytree({"w": np.ones(3)}, d)
        report({"step": 1}, checkpoint=Checkpoint(d))

    result = JaxTrainer(
        train_fn,
        run_config=RunConfig(name="order",
                             storage_path=str(tmp_path / "exp"))).fit()
    assert result.error is None
    # latest must be the sync step-1 checkpoint (index 1), not the
    # late-committing async step-0 one
    assert result.checkpoint.path.endswith("checkpoint_000001")


def test_overwrite_crash_reads_torn_not_mixed(tmp_path):
    """Re-saving into the same directory invalidates the commit marker
    FIRST: a crash mid-overwrite must read as torn, never as a silent
    mix of old and new shards."""
    mesh = _mesh([("dp", 8)])
    state = _sharded_state(mesh, {"w": ((16, 4), P("dp", None))})
    d = str(tmp_path / "ck")
    ac.async_save(d, state).wait()
    # simulate a second save that died after clearing the marker
    ckpter = ac.AsyncCheckpointer()
    orig = ckpter._write_one

    def dies_after_invalidate(directory, snaps, treedef):
        import os as _os
        try:
            _os.remove(_os.path.join(directory, "commit.0"))
        except FileNotFoundError:
            pass
        raise RuntimeError("simulated crash mid-write")

    ckpter._write_one = dies_after_invalidate
    ck = ckpter.save(d, state)
    with pytest.raises(RuntimeError, match="simulated"):
        ck.wait()
    with pytest.raises(ValueError, match="torn"):
        ac.restore(d)
    # a fresh successful save into the same dir heals it
    ac.async_save(d, state).wait()
    loaded = ac.restore(d)
    np.testing.assert_array_equal(loaded["w"], np.asarray(state["w"]))
    del orig
