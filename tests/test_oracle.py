"""Step-time oracle (ISSUE-10 acceptance surface): the roofline model's
constants table pinned to the peak-FLOPs table, predicted step-time
breakdowns for every dryrun layout, the seeded calibration fit, the
unmodeled-collective blind-spot finding, and the one-set-of-numbers
consistency check across state API / CLI / dashboard / Prometheus /
merged-timeline counter track — with a real predicted-vs-measured
residual recorded for a real (virtual-cluster) training run.

The `oracle` marker tags the scenarios; everything here is tier-1-safe
on CPU — cluster tests run on a module-scoped cluster with
log_to_driver=0 per the established fixture pattern. On CPU the
validation exercises plumbing and calibration math, not the absolute
TPU constants (the module's documented caveat)."""
from __future__ import annotations

import json
import time

import pytest

import ray_tpu
from ray_tpu.observability import flops, roofline
from ray_tpu.observability.gang import summarize_run
from ray_tpu.observability.step_timer import summarize_records

pytestmark = pytest.mark.oracle


# ------------------------------------------------- constants (property)

def test_link_constants_pin_to_peak_flops_table():
    """Every generation with a peak-FLOPs entry has ICI/DCN constants,
    and within each generation the link classes are ordered: ICI
    bandwidth above DCN bandwidth, ICI hop latency below DCN latency."""
    for gen in flops.PEAK_FLOPS_BF16:
        assert gen in roofline.LINK_CONSTANTS, \
            f"{gen} has peak FLOPs but no link constants"
        lc = roofline.LINK_CONSTANTS[gen]
        assert lc.ici_bw > lc.dcn_bw > 0, gen
        assert 0 < lc.ici_latency_s < lc.dcn_latency_s, gen
    for platform in flops.NOMINAL_PEAK_FLOPS:
        assert platform in roofline.NOMINAL_LINK_CONSTANTS, platform
        lc = roofline.NOMINAL_LINK_CONSTANTS[platform]
        assert lc.ici_bw > lc.dcn_bw > 0


def test_device_link_constants_prefix_match():
    class Fake:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    assert roofline.device_link_constants(Fake()) == \
        roofline.LINK_CONSTANTS["TPU v5 lite"]
    Fake.device_kind = "TPU v9x"  # unknown TPU: an error, not a default
    with pytest.raises(ValueError, match="TPU v9x"):
        roofline.device_link_constants(Fake())
    Fake.device_kind, Fake.platform = "cpu", "cpu"
    assert roofline.device_link_constants(Fake()) == \
        roofline.NOMINAL_LINK_CONSTANTS["cpu"]


# ------------------------------------------------------------ prediction

def test_predict_builtin_layouts_all_five():
    preds = roofline.predict_builtin_layouts(8)
    assert set(preds) == {"dcn_dp_tp", "dcn_pp_fsdp", "dp_pp", "dp_sp",
                          "dp_ep"}
    for name, p in preds.items():
        assert p["predicted_step_ms"] > 0, name
        assert p["predicted_step_ms"] == pytest.approx(
            p["device_step_ms"] + p["ici_wait_ms"] + p["dcn_wait_ms"])
        for key in ("device_step_ms", "ici_wait_ms", "dcn_wait_ms"):
            assert p[key] >= 0, (name, key)
    # layouts that declare DCN parallelism pay a DCN share; flat
    # single-slice layouts cannot
    for name in ("dcn_dp_tp", "dcn_pp_fsdp"):
        assert preds[name]["dcn_wait_ms"] > 0, name
        assert preds[name]["dcn_bytes"] > 0, name
    for name in ("dp_pp", "dp_sp", "dp_ep"):
        assert preds[name]["dcn_wait_ms"] == 0.0, name
        assert preds[name]["dcn_bytes"] == 0.0, name


def test_prediction_scales_with_bytes_and_calibration():
    from ray_tpu.analysis.collectives import CollectiveUse
    from ray_tpu.analysis.shardcheck import MeshLayout

    layout = MeshLayout({"dp": 8}, {"dp": 2}, name="t")
    links = roofline.LINK_CONSTANTS["TPU v4"]

    def pred(nbytes, cal=1.0):
        return roofline.predict_step_time(
            layout, [CollectiveUse("psum", ("dp",), nbytes)],
            1e12, 8 * 275e12, links=links, calibration=cal)

    small, big = pred(2 ** 20), pred(2 ** 26)
    assert big["dcn_wait_ms"] > small["dcn_wait_ms"]
    assert big["ici_wait_ms"] > small["ici_wait_ms"]
    assert small["device_step_ms"] == pytest.approx(
        big["device_step_ms"])  # compute term independent of comms
    doubled = pred(2 ** 20, cal=2.0)
    assert doubled["predicted_step_ms"] == pytest.approx(
        2 * small["predicted_step_ms"])
    assert doubled["calibration"] == 2.0


def test_unmodeled_collective_is_named_not_absorbed():
    """Satellite: an unmodeled primitive's byte estimate falls back to
    its raw input size AND announces itself — an INFO finding from
    check_collectives and an `unmodeled_collectives` key on the
    prediction."""
    from ray_tpu.analysis.collectives import (CollectiveUse,
                                              check_collectives)
    from ray_tpu.analysis.shardcheck import MeshLayout

    layout = MeshLayout({"dp": 4}, {"dp": 2}, name="t",
                        declared_dcn=True)
    use = CollectiveUse("pgather", ("dp",), 4096)
    assert not use.modeled()
    assert use.dcn_bytes(layout) == 4096.0  # raw-size fallback
    findings = check_collectives(layout, [use])
    unmodeled = [f for f in findings if f.rule == "unmodeled-collective"]
    assert len(unmodeled) == 1
    assert unmodeled[0].severity == "info"
    assert "pgather" in unmodeled[0].message
    pred = roofline.predict_step_time(
        layout, [use], 0.0, 1e12,
        links=roofline.LINK_CONSTANTS["TPU v4"])
    assert pred["unmodeled_collectives"] == ["pgather"]
    # a modeled psum produces no such finding
    clean = check_collectives(layout,
                              [CollectiveUse("psum", ("dp",), 4096)])
    assert not [f for f in clean if f.rule == "unmodeled-collective"]


def test_checkrep_psum_trace_stays_modeled():
    """Under check_vma a psum inside shard_map traces as
    `psum_invariant` with zero-payload `pvary` markers: the former must
    be priced like psum, the latter never collected — a plain psum trace
    must not flag the model's own core primitive as unmodeled."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ray_tpu.analysis.collectives import (abstract_mesh,
                                              check_collectives,
                                              scan_collectives)
    from ray_tpu.analysis.shardcheck import MeshLayout

    layout = MeshLayout({"dp": 8}, {"dp": 2}, name="t",
                        declared_dcn=True)
    mesh = abstract_mesh(layout)
    fn = shard_map(lambda x: x * jax.lax.psum(x, "dp"), mesh=mesh,
                   in_specs=P("dp"), out_specs=P("dp"))
    uses = scan_collectives(fn, jax.ShapeDtypeStruct((64,), "float32"))
    assert uses and all(u.modeled() for u in uses)
    assert not any(u.primitive == "pvary" for u in uses)
    findings = check_collectives(layout, uses)
    assert not [f for f in findings
                if f.rule == "unmodeled-collective"]
    # psum_invariant is priced exactly like psum (ring allreduce)
    psum_like = next(u for u in uses if u.primitive.startswith("psum"))
    assert psum_like.dcn_bytes(layout) == pytest.approx(
        2.0 * psum_like.in_bytes * (2 - 1) / 2)


def test_validate_rejects_empty_records():
    pred = {"layout": "t", "predicted_step_ms": 1.0,
            "device_step_ms": 1.0, "ici_wait_ms": 0.0,
            "dcn_wait_ms": 0.0}
    with pytest.raises(ValueError, match="no flight-recorder"):
        roofline.validate_run(pred, run_id="r", records=[])
    # records without any modeled phase must not land as a vacuous
    # calibration=1.0 "perfect fit"
    with pytest.raises(ValueError, match="no comparable phase"):
        roofline.validate_run(pred, run_id="r",
                              records=[{"step": 0, "data_wait_ms": 5.0}])


def test_validate_run_uses_lead_rank_only():
    """A multi-rank run's flattened records (one per rank per step) must
    not inflate n_steps or let a straggler rank skew the fit — the lead
    rank is the measurement, matching gang.summarize_run."""
    pred = {"layout": "t", "predicted_step_ms": 10.0,
            "device_step_ms": 10.0, "ici_wait_ms": 0.0,
            "dcn_wait_ms": 0.0}
    records = []
    for s in range(6):
        records.append({"step": s, "rank": 0, "device_step_ms": 10.0,
                        "total_ms": 11.0})
        records.append({"step": s, "rank": 1, "device_step_ms": 90.0,
                        "total_ms": 91.0})  # straggler
    val = roofline.validate_run(pred, run_id="multi", records=records)
    assert val["n_steps"] == 6
    assert val["calibration"] == pytest.approx(1.0)
    assert val["residuals"]["device_step"] == pytest.approx(1.0)


def test_pmap_wrapper_is_not_a_collective():
    """Call-like primitives wrapping a sub-jaxpr (xla_pmap carries the
    axis_name string) are priced through their BODY by the recursion —
    the wrapper itself must not appear as an unmodeled collective nor
    double-charge the whole input as comms bytes."""
    import jax

    from ray_tpu.analysis.collectives import scan_collectives

    fn = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    n = jax.local_device_count()
    uses = scan_collectives(
        fn, jax.ShapeDtypeStruct((n, 4), "float32"))
    assert uses, "the body psum must be collected"
    assert all(u.primitive not in ("xla_pmap", "pmap") for u in uses)
    assert all(u.modeled() for u in uses)


def test_cli_analyze_predict_step_time(tmp_path, capsys):
    """`ray_tpu analyze --predict-step-time` emits the predicted
    breakdown for all five dryrun layouts next to the findings — and
    plain --json keeps the historical bare findings list."""
    from ray_tpu.scripts.cli import main

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    main(["analyze", "--predict-step-time", "--json", str(clean)])
    payload = json.loads(capsys.readouterr().out)
    assert set(payload) == {"findings", "predicted_step_time"}
    preds = payload["predicted_step_time"]
    assert set(preds) == {"dcn_dp_tp", "dcn_pp_fsdp", "dp_pp", "dp_sp",
                          "dp_ep"}
    for p in preds.values():
        assert p["predicted_step_ms"] > 0
    main(["analyze", "--predict-step-time", str(clean)])
    text = capsys.readouterr().out
    assert "predicted step time per layout" in text
    assert "dcn_dp_tp" in text and "dcn " in text
    main(["analyze", "--json", str(clean)])  # no flag: bare list
    assert isinstance(json.loads(capsys.readouterr().out), list)


# ------------------------------------------------ calibration (seeded)

def test_calibration_fit_recovers_seeded_scale():
    """Seeded predicted-vs-measured residual test: measured steps are a
    noisy 1.7x of the prediction; the least-squares fit recovers the
    factor and the per-phase residual agrees."""
    import numpy as np

    rng = np.random.default_rng(7)
    predicted_ms = 12.5
    alpha = 1.7
    measured = alpha * predicted_ms * (1.0 + 0.05 * rng.standard_normal(64))
    pairs = [(predicted_ms, float(m)) for m in measured]
    fit = roofline.calibration_fit(pairs)
    assert fit == pytest.approx(alpha, rel=0.05)
    assert roofline.calibration_fit([]) == 1.0

    prediction = {"layout": "seeded", "device_step_ms": predicted_ms,
                  "ici_wait_ms": 0.0, "dcn_wait_ms": 0.0,
                  "predicted_step_ms": predicted_ms}
    records = [{"step": i, "device_step_ms": float(m),
                "total_ms": float(m) + 1.0}
               for i, m in enumerate(measured)]
    val = roofline.validate_records(prediction, records)
    assert val["n_steps"] == 64
    assert val["calibration"] == pytest.approx(alpha, rel=0.05)
    assert val["residuals"]["device_step"] == pytest.approx(alpha,
                                                            rel=0.1)
    assert val["residuals"]["total"] > val["residuals"]["device_step"]
    assert val["measured"]["summary"]["device_step"]["p99_ms"] >= \
        val["measured"]["summary"]["device_step"]["p50_ms"]


# ------------------------------------------- shared summarize (satellite)

def test_summarize_records_shape():
    records = [{"device_step_ms": float(v), "data_wait_ms": 1.0,
                "total_ms": float(v) + 1.0}
               for v in (10, 20, 30, 40, 100)]
    s = summarize_records(records)
    assert s["steps"] == 5
    dev = s["phases"]["device_step"]
    assert dev["p50_ms"] == 30.0
    assert dev["p99_ms"] == 100.0
    assert dev["mean_ms"] == pytest.approx(40.0)
    assert dev["last_ms"] == 100.0
    # trailing EMA weights the newest step but stays below the outlier
    assert dev["p50_ms"] < dev["ema_ms"] < dev["last_ms"]
    assert s["phases"]["data_wait"]["p99_ms"] == 1.0
    assert summarize_records([]) == {"steps": 0, "phases": {}}


def test_gang_phase_summary_uses_shared_summarize():
    """train_progress's aggregation carries the shared per-phase
    p50/p99/EMA summary instead of ad-hoc re-derivation."""
    steps = {s: {0: {"step": s, "rank": 0, "total_ms": 100.0 + s,
                     "device_step_ms": 90.0 + s, "data_wait_ms": 5.0}}
             for s in range(10)}
    run = summarize_run(steps, k=1.5)
    ps = run["phase_summary"]
    assert ps["device_step"]["p50_ms"] == pytest.approx(95.0, abs=1.0)
    assert ps["data_wait"]["p99_ms"] == 5.0
    expected = summarize_records(
        [steps[s][0] for s in sorted(steps)])["phases"]
    assert ps == expected


# --------------------------------------------- cluster (virtual) coverage

@pytest.fixture(scope="module")
def oracle_cluster():
    """ONE cluster for the cluster-backed oracle tests — log_to_driver
    off per the established tier-1 pattern (mirrored worker stderr
    corrupts the tier-1 dot count)."""
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 _system_config={"log_to_driver": 0})
    yield ray_tpu._private.worker.global_worker
    ray_tpu.shutdown()


def _tiny_train_fn(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import (GPT2Config, gpt2_init, gpt2_loss,
                                gpt2_partition_specs)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import TrainStep, get_step_timer, report

    mcfg = GPT2Config.tiny()
    mesh = make_mesh(MeshConfig(dp=-1))
    step = TrainStep(
        lambda p, b: gpt2_loss(p, b["tokens"], b["targets"], mcfg),
        optax.adamw(1e-3), mesh, gpt2_partition_specs(mcfg))
    state_ = step.init_state(gpt2_init(mcfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    for _ in range(3):
        with get_step_timer().phase("data_wait"):
            raw = rng.integers(0, mcfg.vocab_size, (8, 65),
                               dtype=np.int32)
            batch = {"tokens": jnp.asarray(raw[:, :-1]),
                     "targets": jnp.asarray(raw[:, 1:])}
        state_, m = step(state_, batch)
        report({"loss": float(m["loss"])})


def test_oracle_e2e_one_set_of_numbers(oracle_cluster, tmp_path, capsys):
    """Acceptance: predictions for all five dryrun layouts land on every
    surface with ONE set of numbers (state API == CLI == dashboard ==
    Prometheus == merged-timeline counter track), and a real training
    run gets a recorded predicted-vs-measured residual + fitted
    calibration, persisted to disk."""
    from ray_tpu.dashboard import _ClusterData
    from ray_tpu.scripts import cli
    from ray_tpu.train import JaxTrainer, RunConfig
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import state

    # 1. predictions for all five layouts, published to the cluster
    preds = roofline.predict_builtin_layouts(8)
    for name, p in preds.items():
        roofline.record_prediction(name, p)

    # 2. a real training run measured by the flight recorder
    result = JaxTrainer(
        _tiny_train_fn,
        run_config=RunConfig(name="oracle-accept",
                             storage_path=str(tmp_path))).fit()
    assert result.error is None
    deadline = time.monotonic() + 10.0
    run_id = None
    while time.monotonic() < deadline and run_id is None:
        for rid, run in state.train_progress().items():
            if rid.startswith("oracle-accept/") and \
                    run["steps_buffered"] >= 3:
                run_id = rid
        if run_id is None:
            time.sleep(0.2)
    assert run_id, "train records never reached the conductor"

    # 3. validate predicted-vs-measured for THAT run (CPU constants:
    # this validates plumbing + the calibration math, not TPU numbers)
    mcfg_pred = dict(preds["dcn_dp_tp"], layout="oracle-accept")
    persist = tmp_path / "oracle_validation.json"
    val = roofline.validate_run(mcfg_pred, run_id=run_id,
                                persist_path=str(persist))
    assert val["n_steps"] >= 3
    assert val["calibration"] > 0
    assert "device_step" in val["residuals"]
    on_disk = json.loads(persist.read_text())
    assert on_disk["calibration"] == pytest.approx(val["calibration"])

    # 4. one set of numbers across every surface
    st = state.oracle_status()
    assert set(st["predictions"]) == set(preds)
    assert st["totals"]["layouts"] == 5
    assert st["totals"]["validations"] >= 1
    assert st["validations"][-1]["calibration"] == pytest.approx(
        val["calibration"])
    for name, p in preds.items():
        assert st["predictions"][name]["predicted_step_ms"] == \
            pytest.approx(p["predicted_step_ms"])

    cli.main(["oracle", "--address", "ignored:0", "--json"])
    cli_payload = json.loads(capsys.readouterr().out)
    assert cli_payload["predictions"].keys() == st["predictions"].keys()
    for name in preds:
        assert cli_payload["predictions"][name]["predicted_step_ms"] == \
            pytest.approx(st["predictions"][name]["predicted_step_ms"])
    cli.main(["oracle", "--address", "ignored:0", "--events", "5"])
    text = capsys.readouterr().out
    assert "dcn_dp_tp" in text and "calibration" in text

    w = oracle_cluster
    dash = _ClusterData(w.conductor_address).oracle()
    assert dash["predictions"].keys() == st["predictions"].keys()
    assert dash["totals"]["validations"] == st["totals"]["validations"]
    assert dash["events"], "dashboard payload missing the event tail"
    json.dumps(dash)  # JSON-safe exactly as json_response applies it

    metrics_mod.flush()
    prom = state.prometheus_metrics()
    assert "ray_tpu_oracle_predicted_step_ms" in prom
    assert 'layout="dcn_dp_tp"' in prom
    assert "ray_tpu_oracle_residual_ratio" in prom
    assert 'phase="device_step"' in prom

    # 5. merged timeline: the predicted-step-time counter track + the
    # validation marker ride beside the run's train-step markers
    trace = state.timeline(str(tmp_path / "merged.json"), merged=True)
    counters = [e for e in trace if e.get("cat") == "oracle"
                and e.get("ph") == "C"]
    assert {e["name"] for e in counters} >= {
        f"predicted_step_ms:{name}" for name in preds}
    assert all(e["pid"] == "oracle" for e in counters)
    markers = [e for e in trace if e.get("cat") == "oracle"
               and e.get("ph") == "i"]
    assert any(e["args"].get("calibration") is not None
               for e in markers)
    assert any(e.get("cat") == "train_step" for e in trace)


def test_validate_run_without_records_raises(oracle_cluster):
    pred = {"layout": "missing", "predicted_step_ms": 1.0,
            "device_step_ms": 1.0, "ici_wait_ms": 0.0,
            "dcn_wait_ms": 0.0}
    with pytest.raises(ValueError, match="no flight-recorder"):
        roofline.validate_run(pred, run_id="no-such-run")
