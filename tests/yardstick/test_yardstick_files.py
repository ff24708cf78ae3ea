"""Every data file of the benchmark loads, every name and unit keeps to
the contract's characters and lengths, and every entry of BENCHMARK.json
resolves by name to files under its `paths`."""
import glob
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import readers, traffic  # noqa: E402
from benchmarks.harness.configs import load_config  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _files(kind, ext):
    return sorted(os.path.splitext(os.path.basename(p))[0] for p in
                  glob.glob(os.path.join(ROOT, "benchmarks", kind,
                                         "*" + ext)))


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert all(os.path.isdir(os.path.join(ROOT, p))
               for p in BENCH["paths"])
    assert any(w.startswith(BENCH["paths"][0] + "/")
               for w in BENCH["command"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry(m):
    per_layer = m in BENCH["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert want <= set(m) <= want | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if per_layer:
        moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
        # the metric it moves is reported wherever this one is
        assert set(m.get("workloads", cells)) \
            <= set(moved.get("workloads", cells))
        assert 1 <= len(m["layer"]) <= 200
        assert callable(readers.load_reader(m["name"]))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert sorted(_files("layer_metrics", ".py")) \
        == sorted(m["name"] for m in BENCH["per_layer"])


@pytest.mark.parametrize("w", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    conf = load_config(w["config"])
    mix = traffic.load_json("traffic", w["traffic"])
    assert mix["kind"] in ("train", "serve") and conf["family"]
    ends = [m for m in BENCH["end_to_end"]
            if w["name"] in m.get("workloads", [w["name"]])]
    layers = [m for m in BENCH["per_layer"]
              if w["name"] in m.get("workloads", [w["name"]])]
    assert len(ends) >= 2 and layers
    if mix["kind"] == "train":
        from ray_tpu.parallel.mesh import MeshConfig

        assert MeshConfig(**mix["mesh"]).sizes(w["chips"])
        assert mix["remat"] in (True, False)
    if mix["kind"] == "serve":
        longest = traffic.prompt_lengths(mix)[-1]
        assert longest + max(mix["output_tokens"]["values"]) \
            <= mix["max_seq_len"]
        assert mix["loop"] in ("open", "closed")
        assert set(mix["tolerances"]) >= {
            "logprob_abs", "logprob_mean_abs", "margin_abs"}
        assert mix["tolerances"]["logprob_mean_abs"] \
            < mix["tolerances"]["logprob_abs"]
        plan = traffic.plan(mix, 1, float(BENCH["run_seconds"]))
        assert plan["n"] >= 10


@pytest.mark.parametrize("c", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_entry_and_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["source"].startswith("https://")
    assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    conf = load_config(c["name"])
    assert c["file"] == f"benchmarks/configs/{c['name']}.json"
    assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
    assert len(c["reduced"]) <= 16 and all(NAME.match(k) and k in conf
                                           for k in c["reduced"])
    # no width is ever named as reduced
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   or k == "num_experts_per_tok" for k in c["reduced"])
    assert c["name"] in {w["config"] for w in BENCH["workloads"]}
    assert len({x["file"] for x in BENCH["configs"]}) \
        == len(BENCH["configs"])


def test_every_data_file_is_named_by_the_contracts_characters():
    for path in BENCH["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_traffic_and_config_file_is_used_and_loads():
    assert set(_files("traffic", ".json")) \
        == {w["traffic"] for w in BENCH["workloads"]}
    assert set(_files("configs", ".json")) \
        == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_the_published_widths_of_the_two_configurations():
    g = load_config("gpt2-124m")
    assert (g["n_layer"], g["n_embd"], g["n_head"], g["vocab_size"],
            g["n_positions"]) == (12, 768, 12, 50257, 1024)
    m = load_config("mistral-7b-v0.3-l8")
    assert (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["vocab_size"], m["rope_theta"]) \
        == (4096, 14336, 32, 8, 128, 32768, 1e6)
