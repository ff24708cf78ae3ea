"""`slab_rows_read_share.itl` (PR 41) on a hand-made loop ring: the mean
over the window's decode passes of the rows a tick's walk read, over the
slab's `max_batch` x `max_seq_len`; None over a ring without the field,
as the parent's is; and its entry in `BENCHMARK.json`."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import readers  # noqa: E402
from ray_tpu.observability import requests as reqtrace  # noqa: E402

NAME = "slab_rows_read_share.itl"
T0 = 2_000_000.0
# `mistral-chat`'s slab: 32 slots of 2,304 rows, blocks of 128
CELL = {"seconds": 2.0, "traffic": {"max_batch": 32, "max_seq_len": 2304}}


def _record(ts, live, rows_read=None):
    rec = {"engine_id": "cb-test", "ts": ts, "live": live, "max_batch": 32,
           "live_rows": 500 * live, "admissions": []}
    if rows_read is not None:
        rec["slab_rows_read"] = rows_read
    return rec


@pytest.fixture()
def store():
    reqtrace._reset_store_for_tests()
    st = reqtrace.store()
    st.record({"kind": "trace", "request_id": "r0", "ts": T0,
               "total_ms": 450.0, "outcome": "ok", "attempts": 1,
               "replayed": False, "preempts": 0, "phases": [],
               "phase_ms": {}})
    yield st
    reqtrace._reset_store_for_tests()


def _obs(store, ring):
    for rec in ring:
        store.record_loop(rec)
    return {"phases": [{}], "cell": CELL}


def test_the_share_is_the_rows_read_over_the_slab(store):
    ring = [
        _record(T0 - 5.0, 1, 32 * 2304),        # the warm-up: cut
        _record(T0 + 0.1, 0, 99999),            # nothing decoding: out
        # 29 parked slots' one block and three streams' 4 + 5 + 5
        _record(T0 + 0.2, 3, 43 * 128),
        _record(T0 + 0.3, 2, 39 * 128),
        _record(T0 + 0.4, 1),                   # a pass that landed none
        _record(T0 + 3.0, 1, 32 * 2304)]        # the drain: cut
    want = 100.0 * (43 + 39) / 2 * 128 / (32 * 2304)
    assert readers.load_reader(NAME)(_obs(store, ring)) \
        == pytest.approx(want)
    assert want == pytest.approx(7.118, abs=1e-3)


def test_a_program_that_reads_every_row_reports_its_whole_slab(store):
    ring = [_record(T0 + 0.1 * i, 3, 32 * 2304) for i in range(1, 4)]
    assert readers.load_reader(NAME)(_obs(store, ring)) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("ring", [
    [], [_record(T0 + 0.2, 3), _record(T0 + 0.3, 2)]],
    ids=["no ring", "a ring without the field"])
def test_nothing_to_read_is_none(store, ring):
    assert readers.load_reader(NAME)(_obs(store, ring)) is None


def test_the_entry_is_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "itl_p95_ms", "workloads": ["mistral-chat"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", NAME + ".py"))
