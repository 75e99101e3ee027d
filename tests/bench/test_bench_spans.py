"""The readers of the program's spans: `spans.py` on hand-made intervals,
and each tiny cell traced on the CPU reading every span metric of its
cell, with the harness's own spans inside the program's on one clock."""
from __future__ import annotations

import json
import math
import os
import time
from types import SimpleNamespace

import pytest

import tiny

import run
import spans

pytestmark = pytest.mark.timeout(900)

SPAN_METRICS = {
    "serve-tile-search": [
        "queue_wait_ms.serve", "worker_busy.serve",
        "decode_ms_per_request.serve", "lookup_ms_per_request.serve",
        "encode_ms_per_pack.serve", "predict_ms_per_call.serve"],
    "train-tile": ["batch_ms_per_step.train", "loop_ms_per_step.train",
                   "dispatch_ms_per_step.train"],
}
SPAN_METRICS["serve-whole-program"] = SPAN_METRICS["serve-tile-search"]

MS = 1_000_000          # nanoseconds


def _trace(lines, busy=None):
    return spans.build((0, 100 * MS), lines, busy)


def test_window_clipping():
    """Spans count where they end inside the window; their union is cut to
    it."""
    t = _trace([[("repro.serve.pass", -10 * MS, 10 * MS, {}),
                 ("repro.serve.pass", 50 * MS, 60 * MS, {}),
                 ("repro.serve.pass", 95 * MS, 120 * MS, {})]])
    assert [r[:2] for r in spans.ending_in_window(t, "repro.serve.pass")] \
        == [(-10 * MS, 10 * MS), (50 * MS, 60 * MS)]
    assert spans.mean_s(t, "repro.serve.pass") == pytest.approx(0.015)
    assert spans.union_s(t, "repro.serve.pass") == pytest.approx(0.025)
    assert spans.mean_s(t, "repro.serve.decode") is None


def test_self_time_of_nested_spans():
    line = [("repro.train.step", 0, 10 * MS, {"step": 0}),
            ("repro.train.batch", 1 * MS, 3 * MS, {}),
            ("repro.train.dispatch", 4 * MS, 8 * MS, {}),
            ("bench.sampler", 1 * MS, 2 * MS, {}),
            ("repro.train.step", 20 * MS, 30 * MS, {"step": 1}),
            ("repro.train.batch", 20 * MS, 26 * MS, {})]
    t = _trace([line])
    # (10 - 2 - 4) + (10 - 6) ms; the harness span takes nothing
    assert spans.self_s(t, "repro.train.step") == pytest.approx(0.008)
    assert spans.self_s(t, "repro.train.batch") == pytest.approx(0.008)
    assert spans.child_s(t, "repro.train.step", "repro.train.batch") == \
        pytest.approx(0.008)
    assert spans.mean_s(t, "repro.train.step", own=True) == \
        pytest.approx(0.004)
    stats = [r[5] for r in spans.ending_in_window(t, "repro.train.step")]
    assert stats == [{"step": 0}, {"step": 1}]


def test_a_cross_thread_queue_span_nests_with_nothing():
    """A queue-wait span recorded on the worker's line, overlapping its
    passes, takes no time from them and gives none to the idle split."""
    worker = [("repro.serve.pass", 10 * MS, 20 * MS, {}),
              ("repro.serve.queue_wait", 5 * MS, 10 * MS, {"seq": 1}),
              ("repro.serve.queue_wait", 12 * MS, 30 * MS, {"seq": 2}),
              ("repro.serve.pass", 30 * MS, 40 * MS, {})]
    t = _trace([worker], busy=[])
    assert spans.self_s(t, "repro.serve.pass") == pytest.approx(0.020)
    assert spans.mean_s(t, "repro.serve.queue_wait") == \
        pytest.approx(0.0115)
    idle = spans.idle_by_stage(t)
    assert idle["serve.pass"] == pytest.approx(0.020)
    assert idle["waiting"] == pytest.approx(0.080)
    assert "serve.queue_wait" not in idle


def test_idle_by_stage():
    """Idle device time goes to the innermost span on the worker's line,
    else to a span on another thread, else to waiting; busy time to no
    stage."""
    worker = [("repro.serve.pass", 10 * MS, 50 * MS, {}),
              ("repro.serve.lookup", 10 * MS, 20 * MS, {}),
              ("repro.serve.flush", 20 * MS, 45 * MS, {}),
              ("repro.serve.predict", 30 * MS, 40 * MS, {})]
    conn = [("repro.serve.decode", 0, 15 * MS, {}),
            ("repro.serve.decode", 60 * MS, 70 * MS, {})]
    busy = [(32 * MS, 38 * MS), (90 * MS, 110 * MS)]
    idle = spans.idle_by_stage(_trace([worker, conn], busy))
    assert idle == pytest.approx({
        "serve.lookup": 0.010, "serve.flush": 0.015, "serve.predict": 0.004,
        "serve.pass": 0.005, "serve.decode": 0.020, "waiting": 0.030})
    assert sum(idle.values()) == pytest.approx(0.100 - 0.006 - 0.010)
    assert spans.idle_by_stage(_trace([worker])) is None   # no device


def test_readers_find_nothing_without_a_profile(tmp_path):
    ctx = SimpleNamespace(trace_dir=str(tmp_path))
    for name in sorted({n for v in SPAN_METRICS.values() for n in v}):
        assert run._reader(tiny.REPO, _bench(), name)(ctx) is None


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run of each cell, and its trace, run once."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("root")))
    done = {}

    def get(workload):
        if workload not in done:
            out = run.run_cell(root, workload, 5, 1.5, 1,
                               require_tpu=False, t0=time.monotonic())
            trace = spans.load(os.path.join(root, ".bench_trace",
                                            workload))
            done[workload] = (out, trace)
        return done[workload]
    return get


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_a_traced_cell_reads_every_span_metric(traced, workload):
    out, _ = traced(workload)
    assert out["correct"], out["checks"]
    listed = {m["name"] for m in _bench()["per_layer"]
              if workload in m.get("workloads", [])
              and m["source"] == "program_counter"
              and m["name"] in SPAN_METRICS[workload]}
    assert listed == set(SPAN_METRICS[workload])
    for name in SPAN_METRICS[workload]:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)


def _inside(trace, inner: str, outer: str) -> tuple[int, int]:
    """(number of `inner` spans, how many lie inside an `outer` span on
    their own thread's line). An `inner` span ending after the last
    `outer` one is left out: the profile may stop between the two."""
    outers = trace["by_name"].get(outer, [])
    last = max((e for _, e, *_ in outers), default=0)
    rows = [r for r in trace["by_name"].get(inner, []) if r[1] <= last]
    held = sum(any(ol == line and os_ <= s and e <= oe
                   for os_, oe, _, _, ol, _ in outers)
               for s, e, _, _, line, _ in rows)
    return len(rows), held


@pytest.mark.parametrize("workload,inner,outer", [
    ("serve-tile-search", "bench.predict", "repro.serve.predict"),
    ("train-tile", "bench.sampler", "repro.train.batch")])
def test_harness_spans_lie_inside_the_programs(traced, workload, inner,
                                               outer):
    """Both kinds of span share one clock: each harness span around a call
    into the program lies inside the program's own span of that call."""
    _, trace = traced(workload)
    n, held = _inside(trace, inner, outer)
    assert n > 0 and held == n
