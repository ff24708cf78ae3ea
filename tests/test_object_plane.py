"""Object-plane maturity: spill-to-disk on eviction with restore-on-
access, and chunked streaming for cross-host fetches. Reference:
src/ray/raylet/local_object_manager.h:53 (spill),
src/ray/object_manager/pull_manager.cc (64MB chunked pull),
plasma/eviction_policy.cc (LRU)."""
from __future__ import annotations

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.object_store import LocalObjectStore


def test_put_beyond_cap_all_readable(tmp_path):
    """Objects put past the memory cap are spilled, not lost — every one
    reads back intact (round-1 done-criterion)."""
    store = LocalObjectStore(cap=1 * 1024 * 1024,
                             spill_dir=str(tmp_path / "spill"))
    arrays = {}
    for i in range(12):  # 12 x 256KB = 3MB >> 1MB cap
        oid = f"obj{i:02d}"
        arrays[oid] = np.random.default_rng(i).integers(
            0, 255, size=256 * 1024, dtype=np.uint8)
        store.put_value(oid, arrays[oid])
    st = store.stats()
    assert st["spilled_objects"] > 0, "nothing was spilled"
    assert st["bytes"] <= 1 * 1024 * 1024 * 1.1
    for oid, want in arrays.items():
        store._deserialized_cache.pop(oid, None)  # force real read path
        got = store.get_local(oid)
        np.testing.assert_array_equal(got, want)
    store.shutdown()


def test_spill_restore_survives_reeviction(tmp_path):
    store = LocalObjectStore(cap=512 * 1024, spill_dir=str(tmp_path / "s"))
    a = np.arange(100_000, dtype=np.int64)
    b = np.arange(100_000, dtype=np.float32) * 2.5
    store.put_value("a", a)
    store.put_value("b", b)  # evicts a to disk
    store._deserialized_cache.clear()
    np.testing.assert_array_equal(store.get_local("a"), a)  # restore a
    store._deserialized_cache.clear()
    np.testing.assert_array_equal(store.get_local("b"), b)
    np.testing.assert_array_equal(store.get_local("a"), a)
    store.shutdown()


def test_read_range_matches_stream(tmp_path):
    store = LocalObjectStore(cap=64 * 1024 * 1024,
                             spill_dir=str(tmp_path / "s"))
    arr = np.random.default_rng(0).standard_normal(50_000).astype(np.float64)
    store.put_value("x", arr)
    meta, total, sizes = store.stream_info("x")
    assert total == sum(sizes)
    whole = store.read_range("x", 0, total)
    assert len(whole) == total
    # reassembly in arbitrary chunk sizes agrees
    got = bytearray()
    pos = 0
    for chunk in (1000, 37, 100_000, total):
        take = min(chunk, total - pos)
        got += store.read_range("x", pos, take)
        pos += take
        if pos >= total:
            break
    assert bytes(got) == whole
    # and after spilling, identical ranges come from the file
    with store._cv:
        assert store._spill_entry_locked("x", store._entries["x"])
    assert store.read_range("x", 0, total) == whole
    store.shutdown()


def test_error_entries_not_spilled(tmp_path):
    store = LocalObjectStore(cap=1024, spill_dir=str(tmp_path / "s"))
    store.put_error("e", ray_tpu.exceptions.ObjectLostError("e", "boom"))
    store.put_value("big", np.zeros(10_000))
    with pytest.raises(ray_tpu.exceptions.ObjectLostError):
        store.get_local("e")
    store.shutdown()


@pytest.fixture
def forced_remote_cluster(monkeypatch):
    """Every process claims a distinct machine id and a tiny chunk size:
    same-box fetches exercise the full cross-host chunked protocol."""
    monkeypatch.setenv("RAY_TPU_FORCE_REMOTE_FETCH", "1")
    monkeypatch.setenv("RAY_TPU_FETCH_CHUNK", str(256 * 1024))
    import ray_tpu._private.worker as wm

    monkeypatch.setattr(wm, "_MACHINE_ID", wm._compute_machine_id())
    info = ray_tpu.init(num_cpus=2)
    yield info
    ray_tpu.shutdown()


def test_cross_host_chunked_fetch(forced_remote_cluster):
    """A multi-MB task result crosses process boundaries in 256KB chunks
    (no shm handoff, no single giant frame) and arrives intact."""
    @ray_tpu.remote
    def big():
        rng = np.random.default_rng(7)
        return rng.integers(0, 255, size=3 * 1024 * 1024, dtype=np.uint8)

    ref = big.remote()
    got = ray_tpu.get(ref, timeout=120.0)
    want = np.random.default_rng(7).integers(
        0, 255, size=3 * 1024 * 1024, dtype=np.uint8)
    np.testing.assert_array_equal(got, want)
    # PROVE the value rode the stream path: a cross-host result must not
    # arrive as a shm-name handoff (r1 review: the old test silently took
    # the shm path and never exercised chunking)
    w = ray_tpu._private.worker.global_worker
    entry = w.store._entries[ref.id]
    assert entry.shm_name is None, \
        "cross-host fetch still used a shm handoff"
    assert entry.buffers is not None


def test_cross_host_small_inline(forced_remote_cluster):
    @ray_tpu.remote
    def small():
        return {"x": np.arange(10), "s": "hello"}

    got = ray_tpu.get(small.remote(), timeout=60.0)
    np.testing.assert_array_equal(got["x"], np.arange(10))
    assert got["s"] == "hello"
