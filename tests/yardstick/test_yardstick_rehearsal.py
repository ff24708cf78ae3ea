"""The command end to end on the CPU at a toy size, through the test-only
path of `rehearse.py`: the last line's keys are pinned for `--trace 0` and
`--trace 1`, nothing but the result reaches stdout, and the command proper
exits non-zero with no result where there is no chip or no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _env(tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(tmp, "xla_cache"))
    env.pop("BENCH_RUN", None)
    return env


def _run(script, workload, trace, tmp, cwd=ROOT, seconds="3", extra=None):
    env = _env(str(tmp))
    env.update(extra or {})
    return subprocess.run(
        [sys.executable, script, "--workload", workload, "--seed",
         "3000000019", "--seconds", seconds, "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def _metric_names(section, workload):
    return {m["name"] for m in BENCH[section]
            if workload in m.get("workloads", [workload])}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("yardstick")


@pytest.mark.parametrize("workload,trace", [
    ("gpt2-train-b32", 0), ("gpt2-train-b32", 1),
    ("mistral-chat", 0), ("mistral-chat", 1),
    ("mistral-summarize", 0), ("mistral-summarize", 1),
    ("gpt2-chat", 0), ("gpt2-chat", 1)])
def test_last_line_is_the_contracts_object(workload, trace, cache_dir):
    proc = _run(os.path.join(HERE, "rehearse.py"), workload, trace,
                cache_dir, extra={"BENCH_RUN": "something-of-the-drivers"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    # nothing but the result reaches stdout, whatever threads and
    # libraries print while the run shuts down
    lines = proc.stdout.splitlines()
    assert len(lines) == 1, proc.stdout[-2000:]
    result = json.loads(lines[0])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == want | ({"breakdown"} if trace else set())
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["device"]) == dev | (
        {"busy_s", "window_s"} if trace else set())
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    section = "per_layer" if trace else "end_to_end"
    names = _metric_names(section, workload)
    assert set(result["metrics"]) <= names
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        unit = {x["name"]: x["unit"] for x in BENCH[section]}[name]
        assert m["unit"] == unit and isinstance(m["value"], (int, float))
    if trace:
        assert 0 < result["device"]["busy_s"] \
            <= result["device"]["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in result["breakdown"].values())
        # what needs no device plane is read even here
        assert "compile_cache_misses.setup" in result["metrics"]
    else:
        # a plain run reports every end-to-end metric of its cell
        assert set(result["metrics"]) == names
        assert all(m["value"] > 0 for m in result["metrics"].values())
    if workload != "gpt2-train-b32" and trace:
        key = [n for n in names if n.startswith("compiles_in_window")][0]
        assert result["metrics"][key]["value"] == 0


def test_the_command_proper_fails_without_a_chip(cache_dir):
    proc = _run(os.path.join(ROOT, *BENCH["command"][1].split("/")),
                "gpt2-train-b32", 0, cache_dir)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not 'tpu'" in proc.stderr


def test_the_command_fails_beside_the_benchmark_alone(tmp_path, cache_dir):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no program: no result, and a code that is not 0."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(os.path.join(tmp_path, *BENCH["command"][1].split("/")),
                "mistral-chat", 0, cache_dir, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_an_unknown_workload_fails(cache_dir):
    proc = _run(os.path.join(ROOT, *BENCH["command"][1].split("/")),
                "no-such-cell", 0, cache_dir)
    assert proc.returncode != 0 and proc.stdout == ""
