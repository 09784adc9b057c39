"""The readers of the program's ``fora.*`` scopes, on synthetic traces whose
every number is known: one named as the program names its ops now, and
the same trace named as it was before the scopes existed.

The scopes only add parts to each op's name stack, so every reader that
matches the nested jits' names reads the same on both, and the scope
readers read nothing from the older trace.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench.tests.test_bench_trace import _meta, _op  # noqa: E402

TOP = "jit(_fora_fused_impl)/"
WALKS = "vmap(jit(residual_walks))/"
# each op's name stack, with the scopes as the program writes them; the
# older program's stack is the same with the scopes taken out
PUSH = TOP + "fora.push/jit(forward_push)/while/body/gather"
PUSH_PSUM = TOP + "fora.push/jit(forward_push)/while/body/psum"
STARTS = TOP + WALKS + "fora.walk_starts/jit(searchsorted)/while/body/gather"
STEPS = TOP + WALKS + "fora.walk_steps/while/body/closed_call/gather"
ENDPOINT_PSUM = TOP + "fora.walk_steps/psum"
OLD_READERS = ("push.device_s_per_answer", "push_roofline",
               "walks.device_s_per_answer", "walks_roofline",
               "device.idle_pct", "collective.device_s_per_answer",
               "executor.host_s_per_answer")
NEW_READERS = ("push.scoped_device_s_per_answer",
               "walks.start_device_s_per_answer",
               "walks.step_device_s_per_answer")


def unscoped(stack: str) -> str:
    """The name stack the program wrote before it had the scopes."""
    for scope in ("fora.push/", "fora.walk_starts/", "fora.walk_steps/"):
        stack = stack.replace(scope, "")
    return stack


def events(name=lambda stack: stack):
    """Two chips over a 300 us window with three answers.

    chip 0: a while [0, 120] enclosing push ops [10, 50] and [60, 70]; a
    push psum [70, 80]; walk starts [130, 150]; walk steps [150, 230];
    the endpoint psum [230, 240].
    chip 1: push [0, 40]; its psum [40, 60]; walk starts [130, 140]; walk
    steps [140, 250]; the endpoint psum [250, 255].
    """
    ev = _meta(1, 3, "/device:TPU:0", "XLA Ops")
    ev += _meta(2, 3, "/device:TPU:1", "XLA Ops")
    ev += _meta(9, 7, "/host:CPU", "python")
    ev += [_op(1, 0, 120, "while.3", name(TOP + "fora.push/while")),
           _op(1, 10, 40, "fusion.1", name(PUSH)),
           _op(1, 60, 10, "fusion.1", name(PUSH)),
           _op(1, 70, 10, "all-reduce.1", name(PUSH_PSUM)),
           _op(1, 130, 20, "fusion.5", name(STARTS)),
           _op(1, 150, 80, "fusion.7", name(STEPS)),
           _op(1, 230, 10, "all-reduce.2", name(ENDPOINT_PSUM)),
           _op(2, 0, 40, "fusion.1", name(PUSH)),
           _op(2, 40, 20, "all-reduce.1", name(PUSH_PSUM)),
           _op(2, 130, 10, "fusion.5", name(STARTS)),
           _op(2, 140, 110, "fusion.7", name(STEPS)),
           _op(2, 250, 5, "all-reduce.2", name(ENDPOINT_PSUM))]
    ev += [{"ph": "X", "pid": 9, "tid": 7, "ts": ts, "dur": dur, "name": n}
           for n, ts, dur in (("bench.window", 0, 300),
                              ("bench.answer", 0, 125),
                              ("bench.answer", 125, 135),
                              ("bench.answer", 260, 30))]
    return ev


def context(tr):
    calls = [dict(sweeps=12, batch=1, r_sum=[0.5]),
             dict(sweeps=20, batch=1, r_sum=[0.25]),
             dict(sweeps=30, batch=1, r_sum=[0.125])]
    return run.Context(tr, 0.0, 300.0, 3, calls, n=5, m=7, alpha=0.2,
                       omega=10.0, chips=2, peaks={"hbm_bytes_per_s": 1e9})


@pytest.fixture
def scoped():
    return context(tracing.from_events(events()))


@pytest.fixture
def before():
    return context(tracing.from_events(events(unscoped)))


def read(ctx, metric):
    return run.reader(metric)(ctx)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_scope_reader_finds_its_layer(scoped, metric):
    # chip 0: push [0, 120] (the while encloses its ops, psum included),
    # starts 20, steps 80 + 10; chip 1: push 40 + 20, starts 10, steps
    # 110 + 5 — each the mean over chips, over 3 answers
    want = {"push.scoped_device_s_per_answer": (120 + 60) / 2,
            "walks.start_device_s_per_answer": (20 + 10) / 2,
            "walks.step_device_s_per_answer": (90 + 115) / 2}[metric]
    assert read(scoped, metric) == pytest.approx(want / 3 / 1e6)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_scope_reader_reads_nothing_before_the_scopes(before, metric):
    assert read(before, metric) is None


@pytest.mark.parametrize("metric", OLD_READERS)
def test_the_older_readers_read_as_before(scoped, before, metric):
    value = read(scoped, metric)
    assert value is not None
    assert value == read(before, metric)


def test_the_older_readers_known_values(scoped):
    # the old push reader matches jit(forward_push): chip 0 [10, 50],
    # [60, 80]; chip 1 [0, 60]; the while carries no jit name
    assert read(scoped, "push.device_s_per_answer") == pytest.approx(
        (60 + 60) / 2 / 3 / 1e6)
    # walks: residual_walks' ops, not the endpoint psum outside it
    assert read(scoped, "walks.device_s_per_answer") == pytest.approx(
        (100 + 120) / 2 / 3 / 1e6)
    assert read(scoped, "collective.device_s_per_answer") == pytest.approx(
        (20 + 25) / 2 / 3 / 1e6)


def test_push_and_walks_add_up_to_the_older_layers(scoped):
    """What the scopes split, the older readers held whole: the walk
    scopes inside ``jit(residual_walks)`` add up to it."""
    starts = read(scoped, "walks.start_device_s_per_answer")
    steps = read(scoped, "walks.step_device_s_per_answer")
    endpoint_psum = (10 + 5) / 2 / 3 / 1e6
    assert starts + steps - endpoint_psum == pytest.approx(
        read(scoped, "walks.device_s_per_answer"))
