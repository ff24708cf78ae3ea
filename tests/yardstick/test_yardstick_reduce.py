"""The yardstick's own arithmetic: the trace reducer on a synthetic trace
(overlapping events, two lines on one plane, events that straddle both
edges of the window), the roofline operations and bytes against
hand-worked numbers for the training cell's shapes, the traffic generator,
and the last line's validator."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import lastline, roofline, traffic  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402


def synthetic(second_device=False, ops=None):
    """Window [1000, 11000] ns. On the ops line: A straddles the start
    (500 ns inside), B and C overlap (union 1500 ns), D straddles the end
    (500 ns inside): 2500 ns busy. The Steps line covers everything and
    must not be counted."""
    host = ("/host:CPU", [("python3", [
        (tr.MARK_T0, 900.0, 100.0), ("other", 1200.0, 50.0),
        (tr.MARK_T1, 11000.0, 100.0)])])
    if ops is None:
        ops = [("fusion.1", 500.0, 1000.0), ("flash_fwd", 2000.0, 1000.0),
               ("fusion.2", 2500.0, 1000.0), ("flash_fwd", 10500.0, 1000.0)]
    dev = ("/device:TPU:0", [
        ("Steps", [("step 0", 0.0, 20000.0)]),
        ("XLA Modules", [("jit__tick(123)", 2000.0, 1500.0),
                         ("jit__prefill_paged(7)", 10500.0, 1000.0),
                         ("jit__prefill_paged(8)", 10400.0, 50.0),
                         ("jit__tick(123)", 400.0, 1100.0)]),
        ("XLA Ops", ops)])
    planes = [host, dev]
    if second_device:
        planes.append(("/device:TPU:1", [("XLA Ops", [
            ("fusion.9", 1000.0, 5000.0)])]))
    return planes


def reduce(planes, **kw):
    return tr.reduce_trace(planes, plane_prefix="/device:TPU:",
                           ops_line="XLA Ops",
                           modules_line="XLA Modules", **kw)


def test_window_is_between_the_markers():
    assert tr.find_window(synthetic()) == (1000.0, 11000.0)


def test_busy_is_the_union_clipped_to_the_window():
    out = reduce(synthetic())
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx(2500e-9)
    assert 0 < out["busy_s"] <= out["window_s"]


def test_busy_is_averaged_over_device_planes():
    out = reduce(synthetic(second_device=True))
    assert out["busy_s"] == pytest.approx((2500e-9 + 5000e-9) / 2)


def test_programs_are_whole_events_that_start_in_the_window():
    out = reduce(synthetic())
    # the tick that started at 400 began before the window: left out
    assert [(s, d) for _n, s, d in out["programs"]["_tick"]] \
        == [(2000.0, 1500.0)]
    # two compiled programs of one name (two prompt lengths) are one row
    assert sorted(d for _n, _s, d in out["programs"]["_prefill_paged"]) \
        == [50.0, 1000.0]
    assert sum(d for _n, _s, d in out["ops"]["flash_fwd"]) == 2000.0


def test_gaps_lie_between_programs_and_are_named_by_host_spans():
    spans = [("decode_steady", 3000.0, 9000.0),
             ("queue_reserve", 9500.0, 10400.0)]
    out = reduce(synthetic(), host_spans=spans)
    gaps = {(s, d): (p, n, h) for s, d, p, n, h in out["gaps"]}
    assert gaps[(1500.0, 500.0)] == ("_tick", "_tick", "none")
    assert gaps[(3500.0, 6900.0)] == ("_tick", "_prefill_paged",
                                      "decode_steady")
    total = sum(d for _s, d, *_ in out["gaps"])
    # modules cover 500 + 1500 + 50 + 500 ns of the 10000
    assert total == pytest.approx(7450.0)
    idle = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert idle["_tick>_prefill_paged|decode_steady"] \
        == pytest.approx(6900e-9)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_union_merges_overlaps_and_touching_events():
    assert tr.union([("a", 0.0, 10.0), ("b", 5.0, 10.0),
                     ("c", 15.0, 5.0), ("d", 30.0, 1.0)]) \
        == [(0.0, 20.0), (30.0, 31.0)]


def test_a_window_with_no_device_operation_is_an_error():
    planes = synthetic(ops=[("fusion.1", 20000.0, 10.0)])
    with pytest.raises(tr.TraceError, match="no device operation"):
        reduce(planes)


@pytest.mark.parametrize("drop", [tr.MARK_T0, tr.MARK_T1])
def test_a_missing_marker_is_an_error(drop):
    planes = synthetic()
    planes[0] = ("/host:CPU", [("python3", [
        e for e in planes[0][1][0][1] if e[0] != drop])])
    with pytest.raises(tr.TraceError, match="marker"):
        reduce(planes)


def test_no_device_plane_is_an_error():
    with pytest.raises(tr.TraceError, match="no plane"):
        reduce(synthetic()[:1])


def test_program_names():
    assert tr.program_name("jit__tick(42)") == "_tick"
    assert tr.program_name("jit_step") == "step"
    assert tr.program_name("fusion.3") == "fusion.3"


# ------------------------------------------------------------- roofline

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 8.19e11}


def test_flash_cost_of_the_training_cell_by_hand():
    # 32 sequences x 12 heads, 1,024 tokens, heads of 64, causal:
    # forward 4 T^2 d, backward 8 T^2 d, halved = 6 * 1024^2 * 64 a head
    ops, nbytes = roofline.flash_attention_cost(32 * 12, 1024, 64)
    assert ops == 384 * 6 * 1024 * 1024 * 64 == 154618822656
    # a [1024, 64] bf16 tensor is 131,072 B: 4 forward + 8 backward of
    # them, and 3 float32 row statistics of 4,096 B
    assert nbytes == 384 * (12 * 131072 + 3 * 4096) == 608698368
    least, bound = roofline.least_seconds(ops, nbytes, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(154618822656 / 197e12)


def test_flash_forward_only_and_full_mask():
    ops, nbytes = roofline.flash_attention_cost(1, 128, 64, causal=False,
                                                backward=False)
    assert ops == 4 * 128 * 128 * 64
    assert nbytes == 4 * 128 * 64 * 2 + 4 * 128


def test_fused_ce_cost_of_the_training_cell_by_hand():
    # 32,768 rows, 768 wide, 50,257 words: logits, dx and dW are
    # 2 * rows * vocab * d each
    ops, nbytes = roofline.fused_ce_cost(32 * 1024, 768, 50257)
    assert ops == 3 * 2 * 32768 * 50257 * 768 == 7588552900608
    x, w = 32768 * 768 * 2, 50257 * 768 * 2
    assert nbytes == 3 * (x + w) + 8 * 32768
    least, bound = roofline.least_seconds(ops, nbytes, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(0.038521, rel=1e-3)


@pytest.mark.parametrize("metric,names,per_event_s", [
    # one layer's attention, and one step's cross-entropy, at the peak
    ("flash_roofline", ("jvp_flash_fwd_.3", "transpose_jvp_flash_bwd_dq__.3",
                        "transpose_jvp_flash_bwd_dkv__.3"),
     154618822656 / 197e12 / 3),
    ("fused_ce_roofline", ("jvp_fused_ce_fwd_.1",
                           "transpose_jvp_fused_ce_dx__.1",
                           "transpose_jvp_fused_ce_dw__.1"),
     7588552900608 / 197e12 / 3)])
def test_a_kernels_share_is_reckoned_per_event_of_its_own(
        metric, names, per_event_s):
    """Kernels that take twice their least time read 50%, however the
    window cuts through the steps: 10 forward events, 9 of each backward
    kernel, and a step count that the reader must not need."""
    from benchmarks.harness.readers import load_reader

    ev = lambda n, k: [(n, 1e6 * i, 2e9 * per_event_s)  # noqa: E731
                       for i in range(k)]
    obs = {"trace": {"ops": {names[0]: ev(names[0], 10),
                             names[1]: ev(names[1], 9),
                             names[2]: ev(names[2], 9),
                             "fusion.7": ev("fusion.7", 50)},
                     "programs": {}},
           "cell": {"batch": 32, "seq": 1024, "heads": 12, "head_dim": 64,
                    "layers": 12, "d_model": 768, "vocab": 50257,
                    "peaks": PEAKS}}
    assert load_reader(metric)(obs) == pytest.approx(50.0)
    assert load_reader(metric)({"trace": {"ops": {}, "programs": {}},
                                "cell": obs["cell"]}) is None


def test_a_memory_bound_call_says_so():
    _least, bound = roofline.least_seconds(1e6, 1e9, PEAKS)
    assert bound == "memory"


# -------------------------------------------------------------- traffic

CHAT = {"loop": "open", "rate_rps": 10.0,
        "prompt_tokens": {"values": [128, 256, 512],
                          "weights": [0.5, 0.3, 0.2]},
        "output_tokens": {"values": [32, 64], "weights": [0.25, 0.75]}}


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.plan(CHAT, 1, 20.0)["requests"]
    b = traffic.plan(CHAT, 3_000_000_019, 20.0)["requests"]
    assert len(a) == len(b) == 200
    for key in ("prompt_len", "max_tokens"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
        assert [r[key] for r in a] != [r[key] for r in b]
    gaps = lambda rs: sorted(round(y["due_s"] - x["due_s"], 9)  # noqa: E731
                             for x, y in zip(rs, rs[1:]))
    assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"])
    assert sum(r["prompt_len"] == 128 for r in a) == 100
    assert sum(r["max_tokens"] == 64 for r in a) == 150
    assert len(gaps(a)) == 199
    assert traffic.plan(CHAT, 1, 20.0)["requests"] == a


def test_poisson_gaps_have_the_rate_and_the_spread():
    gaps = traffic.poisson_gaps(1000, 8.0)
    mean = sum(gaps) / len(gaps)
    assert mean == pytest.approx(1 / 8.0, rel=0.01)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert var ** 0.5 == pytest.approx(1 / 8.0, rel=0.05)


def test_apportion_adds_up():
    assert traffic.apportion(10, [0.35, 0.30, 0.20, 0.10, 0.05]) \
        == [4, 3, 2, 1, 0]
    assert sum(traffic.apportion(37, [1, 1, 1])) == 37


def test_closed_loop_pool_outlasts_the_window():
    mix = dict(CHAT, loop="closed", clients=8, pool_requests_per_s=16)
    plan = traffic.plan(mix, 7, 30.0)
    assert plan["n"] == 480 and all(r["due_s"] == 0 for r in
                                    plan["requests"])


def test_prompts_are_unique_and_seeded():
    a = traffic.prompt_tokens(5, 0, 64, 32768)
    assert a == traffic.prompt_tokens(5, 0, 64, 32768)
    assert a != traffic.prompt_tokens(5, 1, 64, 32768)
    assert a != traffic.prompt_tokens(2 ** 31 + 5, 0, 64, 32768)
    assert all(1 <= t < 32768 for t in a)


# ------------------------------------------------------------ last line

def good_result(traced):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1}
    out = {"correct": True, "attempted": 3, "failed": 0,
           "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
           "device": dev}
    if traced:
        dev.update(busy_s=1.0, window_s=2.0)
        out["breakdown"] = {"device_ops": [], "idle_gaps": []}
    return out


@pytest.mark.parametrize("traced", [False, True])
def test_the_contracts_object_passes(traced):
    lastline.check_result(good_result(traced), traced)


@pytest.mark.parametrize("spoil", [
    lambda r: r.update(extra=1),
    lambda r: r.pop("failed"),
    lambda r: r["device"].update(busy_s=3.0),
    lambda r: r["device"].update(busy_s=0.0),
    lambda r: r["device"].pop("window_s"),
    lambda r: r["metrics"]["setup_s"].update(value=float("nan")),
    lambda r: r["metrics"].clear(),
    lambda r: r["metrics"]["setup_s"].update(why="x"),
])
def test_a_spoiled_object_is_refused(spoil):
    result = good_result(True)
    spoil(result)
    with pytest.raises(ValueError):
        lastline.check_result(result, True)


def test_a_plain_run_carries_no_trace_keys():
    result = good_result(True)
    with pytest.raises(ValueError):
        lastline.check_result(result, False)
