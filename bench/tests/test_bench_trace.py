"""The reduction from a trace to the per-layer metrics, on a synthetic
trace whose every number is known."""

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline, run  # noqa: E402
from bench import trace as tracing  # noqa: E402

PUSH = "jit(_fora_fused_impl)/jit(forward_push)/while/body/gather"
WALK = "jit(_fora_fused_impl)/vmap(jit(residual_walks))/while/body/gather"


def _meta(pid, tid, process, thread):
    return [{"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": process}},
            {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": thread}}]


def _op(pid, ts, dur, name, tf_op=None):
    args = {"tf_op": tf_op} if tf_op else {}
    return {"ph": "X", "pid": pid, "tid": 3, "ts": ts, "dur": dur,
            "name": name, "args": args}


def synthetic_events():
    """Two chips over a 200 us window with two answers.

    chip 0: while [0, 100] enclosing a push op [10, 40] and a walk op
    [50, 90]; an all-reduce [120, 150].
    chip 1: a push op [0, 60]; an all-reduce [120, 140].
    """
    ev = _meta(1, 3, "/device:TPU:0", "XLA Ops")
    ev += _meta(2, 3, "/device:TPU:1", "XLA Ops")
    ev += _meta(9, 7, "/host:CPU", "python")
    ev += [_op(1, 0, 100, "while.16"), _op(1, 10, 30, "fusion.1", PUSH),
           _op(1, 50, 40, "fusion.2", WALK), _op(1, 120, 30, "all-reduce.1"),
           _op(2, 0, 60, "fusion.1", PUSH), _op(2, 120, 20, "all-reduce.1")]
    ev += [{"ph": "X", "pid": 9, "tid": 7, "ts": ts, "dur": dur, "name": n}
           for n, ts, dur in (("bench.window", 0, 200),
                              ("bench.answer", 0, 100),
                              ("bench.answer", 110, 50),
                              ("not.ours", 0, 10))]
    return ev


@pytest.fixture
def tr(tmp_path):
    path = tmp_path / "plugins/profile/x/host.trace.json.gz"
    path.parent.mkdir(parents=True)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": synthetic_events()}, f)
    return tracing.load(tmp_path)


def test_load_finds_devices_ops_and_spans(tr):
    assert sorted(tr.ops) == [0, 1]
    assert len(tr.ops[0]) == 4 and len(tr.ops[1]) == 2
    assert [s[0] for s in tr.spans] == ["bench.window", "bench.answer",
                                        "bench.answer"]
    assert tr.window() == (0.0, 200.0)


def test_busy_union_and_idle_share(tr):
    # chip 0: [0, 100] + [120, 150] = 130 us; chip 1: [0, 60] + [120, 140]
    assert tracing.busy_s(tr, 0, 200) == pytest.approx((130 + 80) / 2 / 1e6)
    assert tracing.busy_s(tr, 50, 130) == pytest.approx((60 + 20) / 2 / 1e6)


def test_time_by_name_stack_and_collectives(tr):
    push = tracing.op_time_s(tr, tracing.in_scope("jit(forward_push)"),
                             0, 200)
    walks = tracing.op_time_s(tr, tracing.in_scope("jit(residual_walks)"),
                              0, 200)
    coll = tracing.op_time_s(tr, tracing.is_collective, 0, 200)
    assert push == pytest.approx((30 + 60) / 2 / 1e6)
    assert walks == pytest.approx((40 + 0) / 2 / 1e6)
    assert coll == pytest.approx((30 + 20) / 2 / 1e6)
    assert tracing.op_time_s(tr, tracing.in_scope("nothing"), 0, 200) is None


def test_host_time_inside_spans(tr):
    # answer 1 [0, 100]: chip 0 idle 0, chip 1 idle 40;
    # answer 2 [110, 160]: chip 0 idle 20, chip 1 idle 30
    assert tracing.host_s(tr, "bench.answer", 0, 200) == pytest.approx(
        (0 + 40) / 2 / 1e6 + (20 + 30) / 2 / 1e6)


def test_breakdown(tr):
    b = tracing.breakdown(tr, 0, 200)
    ops = dict(b["device_ops"])
    assert "while" not in ops      # a container is not an op of its own
    assert ops["jit(forward_push)/while/body/gather [fusion]"] == \
        pytest.approx((30 + 60) / 2 / 1e6)
    assert ops["all-reduce"] == pytest.approx((30 + 20) / 2 / 1e6)
    gaps = dict(b["idle_gaps"])
    # chip 0 gaps: [100, 110] none, [110, 120] answer, [150, 160] answer,
    # [160, 200] none; chip 1: [60, 100] answer, [100, 110] none,
    # [110, 120] answer, [140, 160] answer, [160, 200] none
    assert gaps["bench.answer"] == pytest.approx((20 + 70) / 2 / 1e6)
    assert gaps["between spans"] == pytest.approx((50 + 50) / 2 / 1e6)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_metric_readers_on_the_synthetic_trace(tr):
    calls = [dict(sweeps=10, batch=1, r_sum=[0.5]),
             dict(sweeps=20, batch=1, r_sum=[0.25])]
    ctx = run.Context(tr, 0.0, 200.0, 2, calls, n=3, m=3, alpha=0.2,
                      omega=10.0, chips=2,
                      peaks={"hbm_bytes_per_s": 1e9})
    read = {m: run.reader(m)(ctx) for m in (
        "push.device_s_per_answer", "push_roofline",
        "walks.device_s_per_answer", "walks_roofline",
        "device.idle_pct", "collective.device_s_per_answer",
        "executor.host_s_per_answer")}
    push_s, walk_s = 45e-6, 20e-6
    assert read["push.device_s_per_answer"] == pytest.approx(push_s / 2)
    assert read["walks.device_s_per_answer"] == pytest.approx(walk_s / 2)
    assert read["collective.device_s_per_answer"] == pytest.approx(25e-6 / 2)
    assert read["device.idle_pct"] == pytest.approx(100 * (1 - 105 / 200))
    assert read["executor.host_s_per_answer"] == pytest.approx(45e-6 / 2)
    push_bytes = 30 * roofline.push_sweep_bytes(3, 3, 1)
    assert read["push_roofline"] == pytest.approx(
        100 * push_bytes / (push_s * 2 * 1e9))
    walk_bytes = roofline.walk_bytes(0.5, 10, 0.2) + \
        roofline.walk_bytes(0.25, 10, 0.2)
    assert read["walks_roofline"] == pytest.approx(
        100 * walk_bytes / (walk_s * 2 * 1e9))


def test_a_reader_that_finds_nothing_returns_nothing():
    ctx = run.Context(tracing.Trace(), 0.0, 1.0, 1, [], n=3, m=3, alpha=0.2,
                      omega=10.0, chips=1, peaks={"hbm_bytes_per_s": 1e9})
    for metric in ("push_roofline", "walks_roofline",
                   "device.idle_pct", "collective.device_s_per_answer",
                   "executor.host_s_per_answer"):
        assert run.reader(metric)(ctx) is None
