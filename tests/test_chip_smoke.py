"""chip_smoke.py off the chip: it must refuse to run without a TPU, and its
two phase functions, the chip-counting, compile-cache and kernel-choice
code it leans on, must hold on the CPU mesh at a tiny size. What only the
chip can show (Mosaic accepting the kernels, the custom calls in the
compiled step) is the script's own job, through the chip tool."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
import ray_tpu
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.ops import dispatch
from ray_tpu.ops.attention import flash_attention
from ray_tpu.util import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# tiny() has head_dim 32, which the flash kernel does not take; two heads
# of 64 keep the width and put both kernels on the traced path
TINY = dataclasses.replace(GPT2Config.tiny(), num_heads=2)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("chip_smoke FAILED") and "'cpu'" in last
    assert '"ok": true' not in p.stdout


def test_last_line_is_the_result_and_nothing_else(monkeypatch, capsys,
                                                  tmp_path):
    """With both phases passed, the last stdout line is a JSON object with
    exactly `ok` and `device` {platform, kind, count}; what the phases
    recorded is on the line before it and in chip_smoke.json."""
    facts = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "jax": jax.__version__}

    def fake_phase(phase, actors=False):
        rec = {"ok": True, "device": dict(facts), "cache_dir": "/c",
               "wall_s": 1.0}
        return dict(rec, object_store="native-arena") \
            if phase == "serve" else rec

    monkeypatch.setattr(chip_smoke, "_run_phase", fake_phase)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    record = json.loads(lines[-2].split("chip_smoke: record ", 1)[1])
    assert record == json.loads((tmp_path / "chip_smoke.json").read_text())
    assert record["claim"] is None and set(record["phases"]) == {
        "train", "serve"}


def test_train_phase_tiny_interpret(tmp_path):
    rec = chip_smoke.train_phase(TINY, per_chip_batch=2, seq=128, steps=3,
                                 interpret=True, storage=str(tmp_path))
    n = len(jax.devices())
    assert rec["ok"] and rec["devices"] == n and rec["mesh"] == {"dp": n}
    assert rec["loss_last"] < rec["loss_first"]
    assert {k["op"]: (k["choice"], k["shards"]) for k in rec["kernels"]} == {
        "flash_attention": ("pallas", n),
        "linear_cross_entropy": ("pallas", n)}


def test_train_phase_fails_when_a_kernel_gives_way(tmp_path):
    """head_dim 32 sends flash attention to its reference: the phase must
    say so, not pass."""
    with pytest.raises(AssertionError, match="flash_attention took the "
                       "reference path: head_dim 32"):
        chip_smoke.train_phase(GPT2Config.tiny(), per_chip_batch=2, seq=128,
                               steps=2, interpret=True,
                               storage=str(tmp_path))


def test_serve_phase_tiny_in_process():
    rec = chip_smoke.serve_phase(TINY, actors=False, prompt_lens=(16, 40, 80),
                                 max_batch=4, expect_platform="cpu")
    assert rec["ok"] and rec["requests"] == 8
    assert rec["prompt_lens"] == [16, 40, 80]
    assert rec["reused_tokens"] >= 64
    assert rec["router"] == {"completed": 8, "shed": 0, "dispatched": 8}
    assert [r["platform"] for r in rec["replicas"]] == ["cpu", "cpu"]
    assert rec["object_store"] in ("native-arena", "python-shm")
    assert not ray_tpu.is_initialized()


def test_custom_calls_reads_kernel_name_and_first_operand():
    hlo = '''
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop
  %flash_fwd.3 = (bf16[32,1024,768]{2,1,0:T(8,128)(2,1)}, f32[32,12,1024,8]{3,2,1,0:T(8,128)}) custom-call(%bitcast.227, %bitcast.224), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32,1024,768]{2,1,0}, bf16[32,1024,768]{2,1,0}}, metadata={op_name="jit(step)/jvp()/shard_map/flash_fwd/pallas_call" stack_frame_id=47}, backend_config={"custom_call_config":{"body":"TUzv"}}
  %custom-call.77 = f32[32768,768]{1,0:T(8,128)} custom-call(%bitcast.195, %custom-call.13), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32768,768]{1,0}, bf16[50304,768]{1,0}}, metadata={op_name="jit(step)/transpose(jvp(fused_ce_dx))/pallas_call" stack_frame_id=90}
  %custom-call.9 = f32[4]{0} custom-call(%x), custom_call_target="Sharding"
'''
    calls = chip_smoke.custom_calls(hlo)
    assert [(c["kernel"], c["operand0"]) for c in calls] == [
        ("flash_fwd", [32, 1024, 768]), ("fused_ce_dx", [32768, 768])]
    assert all("TUzv" not in c["hlo"] for c in calls)


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, the helper sets
    no directory. Unset: the fixed path under the checkout."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == \
            before["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO_ROOT, ".xla_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def test_detect_tpu_chips_from_device_nodes(tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    assert ray_tpu._detect_tpu_chips(str(tmp_path)) == 0
    vfio = tmp_path / "vfio"
    vfio.mkdir()
    for name in ("0", "1", "2", "3", "vfio"):  # vfio/vfio: control node
        (vfio / name).touch()
    assert ray_tpu._detect_tpu_chips(str(tmp_path)) == 4
    for i in range(8):  # older hosts: /dev/accel<n> wins
        (tmp_path / f"accel{i}").touch()
    assert ray_tpu._detect_tpu_chips(str(tmp_path)) == 8
    monkeypatch.setenv("RAY_TPU_CHIPS", "2")
    assert ray_tpu._detect_tpu_chips(str(tmp_path)) == 2


def test_kernel_choice_is_recorded():
    dispatch.reset_kernel_choices()
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    flash_attention(q, q, q, True)
    (rec,) = dispatch.kernel_choices("flash_attention")
    assert rec["choice"] == "reference" and "cpu" in rec["reason"]
    with dispatch.pallas_interpret():
        flash_attention(q, q, q, True)
        odd = jnp.ones((1, 100, 2, 64), jnp.float32)
        flash_attention(odd, odd, odd, True)
    by_shape = {r["shape"]: r for r in
                dispatch.kernel_choices("flash_attention")}
    assert by_shape[(1, 128, 2, 64, 128)]["choice"] == "pallas"
    assert by_shape[(1, 100, 2, 64, 100)]["choice"] == "reference"
    assert "multiples of 128" in by_shape[(1, 100, 2, 64, 100)]["reason"]
