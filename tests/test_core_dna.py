"""Unit + property tests for the D&A core (paper Algorithms 1-2, Lemmas 1-2)."""

from __future__ import annotations

import math

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional dev dep (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.core import (BoundReport, DeviceAllocator, InfeasibleDeadline,
                        RuntimeStats, SimulatedTimeSource, build_slot_plan,
                        cochran_sample_size, dna, dna_real, execute_plan,
                        fraction_sample_size, lemma1_lower_bound,
                        lemma2_hoeffding_bound, num_slots, queries_per_slot,
                        required_cores, z_score)


# ---------------------------------------------------------------------------
# sampling (Eq. 1 / Eq. 2)


def test_eq2_paper_example_exact():
    plan = cochran_sample_size(0.99, 0.50, 0.05)
    assert plan.size == 664
    assert abs(plan.raw - 663.5776) < 1e-4


def test_z_scores_match_table():
    assert z_score(0.99) == 2.576
    assert z_score(0.95) == 1.960
    # non-tabled level falls back to the rational approximation
    assert abs(z_score(0.97) - 2.1701) < 1e-3


@given(st.floats(0.5, 0.999), st.floats(0.01, 0.49), st.floats(0.01, 0.3))
@settings(max_examples=100, deadline=None)
def test_cochran_monotonic_properties(ci, p, e):
    s = cochran_sample_size(ci, p, e).size
    # tighter error -> more samples
    s_tight = cochran_sample_size(ci, p, e / 2).size
    assert s_tight >= s
    # p=0.5 is the conservative maximum
    s_half = cochran_sample_size(ci, 0.5, e).size
    assert s_half >= s


@given(st.integers(1, 10_000))
@settings(max_examples=60, deadline=None)
def test_fpc_never_exceeds_population(X):
    assert cochran_sample_size(0.99, 0.5, 0.05, population=X).size <= X
    assert 1 <= fraction_sample_size(X, 0.05) <= X


# ---------------------------------------------------------------------------
# bounds (Lemmas 1-2)


def test_lemma1_arithmetic():
    assert lemma1_lower_bound(100, 2.0, 50.0) == pytest.approx(4.0)
    with pytest.raises(InfeasibleDeadline):
        lemma1_lower_bound(10, 5.0, 1.0)      # t_max > T


def test_lemma2_closed_form():
    stats = RuntimeStats(np.full(16, 2.0))
    got = lemma2_hoeffding_bound(100, 50.0, stats, p_f=0.05)
    slack = math.sqrt(4.0 * math.log(2 / 0.05) / 32)
    assert got == pytest.approx((100 / 50.0) * (2.0 + slack))


@given(st.lists(st.floats(0.01, 5.0), min_size=2, max_size=64),
       st.integers(10, 10_000), st.floats(0.01, 0.2))
@settings(max_examples=100, deadline=None)
def test_lemma2_dominates_mean_demand(times, X, p_f):
    """Hoeffding bound >= naive X*t_bar/T bound (slack is non-negative)."""
    stats = RuntimeStats(np.array(times))
    T = stats.t_max * 10
    l2 = lemma2_hoeffding_bound(X, T, stats, p_f=p_f)
    assert l2 >= X * stats.t_avg / T - 1e-9


def test_mean_of_equal_samples_never_exceeds_their_max():
    """The float mean of five equal samples rounds 1 ULP above them; the
    clipped mean keeps Lemma 2's ``t_hat >= t_bar`` precondition."""
    t = 3.2286664189281677
    assert np.full(5, t).mean() > t                # the rounding itself
    stats = RuntimeStats(np.full(5, t))
    assert stats.t_avg == t == stats.t_max
    assert lemma2_hoeffding_bound(10, 10 * t, stats, p_f=0.125) > 0


def test_bound_report_reduction():
    stats = RuntimeStats(np.array([1.0, 1.5, 2.0]))
    rep = BoundReport.from_stats(100, 100.0, stats)
    assert rep.reduction_vs_lemma2(rep.lemma2_cores) == 0.0
    assert rep.reduction_vs_lemma2(1) > 0


# ---------------------------------------------------------------------------
# slot plans (Alg. 1 lines 4-7)


@given(st.integers(0, 500), st.integers(1, 40), st.integers(1, 40))
@settings(max_examples=150, deadline=None)
def test_slot_plan_invariants(n_queries, ell, k):
    ids = list(range(n_queries))
    if n_queries > ell * k:
        with pytest.raises(ValueError):
            build_slot_plan(ids, ell, k)
        return
    plan = build_slot_plan(ids, ell, k)
    # every query exactly once
    seen = [q for slot in plan.slots for q in slot]
    assert sorted(seen) == ids
    # no slot exceeds k; at most ell slots
    assert all(len(s) <= k for s in plan.slots)
    assert len(plan.slots) <= ell
    assert plan.cores_used <= k


@given(st.integers(1, 200), st.integers(1, 16), st.integers(1, 16))
@settings(max_examples=100, deadline=None)
def test_core_totals_match_queue_sums(n_queries, ell, k):
    if n_queries > ell * k:
        return
    plan = build_slot_plan(range(n_queries), ell, k)
    src = SimulatedTimeSource(mean=0.5, cv=0.5, seed=7)
    execution = execute_plan(plan, lambda ids: src.measure(ids))
    for j in range(plan.k):
        queue = plan.core_queue(j)
        expect = sum(execution.per_query_times[q] for q in queue)
        assert execution.core_totals[j] == pytest.approx(expect)
    # T_max is the max over cores and bounds the barrier makespan from below
    assert execution.t_max_core <= execution.slot_barrier_makespan + 1e-9


def test_slot_arithmetic_matches_paper():
    # Alg.1 L4: ell = floor((T - t_max)/t_max); L5: k = ceil((X-s)/ell)
    assert num_slots(10.0 - 1.0, 1.0) == 9
    assert queries_per_slot(100 - 10, 9) == 10


def test_core_queue_contents_and_range():
    """Explicit coverage for SlotPlan.core_queue (ISSUE-4 satellite): the
    j-th-query-of-every-slot assignment, with out-of-range cores raising."""
    plan = build_slot_plan(range(10), ell=4, k=3)
    # slots: (0,1,2) (3,4,5) (6,7,8) (9,)
    assert plan.core_queue(0) == [0, 3, 6, 9]
    assert plan.core_queue(1) == [1, 4, 7]
    assert plan.core_queue(2) == [2, 5, 8]
    with pytest.raises(IndexError):
        plan.core_queue(3)
    with pytest.raises(IndexError):
        plan.core_queue(-1)
    # queues partition the plan's queries
    union = sorted(q for j in range(plan.k) for q in plan.core_queue(j))
    assert union == list(range(10))


def test_slot_barrier_makespan_closed_form():
    """slot_barrier_makespan = sum of per-slot maxima (the straggler
    monitor's pessimistic completion), >= the no-barrier T_max."""
    plan = build_slot_plan(range(6), ell=3, k=2)
    times = {0: 1.0, 1: 5.0, 2: 2.0, 3: 1.0, 4: 3.0, 5: 4.0}
    execution = execute_plan(
        plan, lambda ids: RuntimeStats(np.array([times[q] for q in ids])))
    # slot maxima: max(1,5)+max(2,1)+max(3,4) = 5+2+4
    assert execution.slot_barrier_makespan == pytest.approx(11.0)
    # per-core totals: core0 = 1+2+3, core1 = 5+1+4 -> T_max = 10
    assert execution.t_max_core == pytest.approx(10.0)
    assert execution.t_max_core <= execution.slot_barrier_makespan


# ---------------------------------------------------------------------------
# Algorithm 1 / Algorithm 2 end-to-end (simulated executors)


def _executor(mean=0.1, cv=0.2, seed=0):
    src = SimulatedTimeSource(mean=mean, cv=cv, seed=seed)
    return lambda ids: src.measure(ids)


def test_dna_accepts_within_deadline():
    res = dna(500, deadline=5.0, executor=_executor(mean=0.05), sample_size=20)
    assert res.accepted
    assert res.completion_time <= 5.0
    assert res.cores >= 1


def test_dna_real_respects_cmax_and_deadline():
    res = dna_real(500, deadline=10.0, executor=_executor(mean=0.05),
                   max_cores=64, sample_size=25, scaling_factor=0.9)
    assert res.accepted
    assert res.cores <= 64
    assert res.completion_time <= 10.0
    # headline property: never above the Lemma-2 baseline in core count
    assert res.cores <= res.bounds.lemma2_cores


def test_dna_real_admission_rejects():
    with pytest.raises(InfeasibleDeadline):
        dna_real(10_000, deadline=1.0, executor=_executor(mean=0.5),
                 max_cores=2, sample_size=10)


@given(st.integers(50, 400), st.floats(0.5, 1.0), st.integers(4, 30),
       st.integers(0, 3))
@settings(max_examples=25, deadline=None)
def test_dna_real_properties(X, d, s, seed):
    """Whenever D&A_REAL accepts: deadline met, all queries processed,
    cores <= C_max. (cores <= Lemma-2 is the paper's EMPIRICAL finding, not
    a theorem — it is checked in the deterministic tests and benchmarks,
    not property-asserted here.)"""
    executor = _executor(mean=0.05, cv=0.3, seed=seed)
    try:
        res = dna_real(X, deadline=8.0, executor=executor, max_cores=64,
                       sample_size=min(s, X), scaling_factor=d)
    except InfeasibleDeadline:
        return
    assert res.accepted
    assert res.completion_time <= 8.0 + 1e-9
    assert res.cores <= 64
    assert res.plan.num_queries == X - min(s, X)


def test_smaller_d_never_fewer_cores():
    """Paper Fig. 3 direction: lower d -> >= cores (same sample seed)."""
    res_hi = dna_real(300, 10.0, _executor(seed=11), 64, sample_size=15,
                      scaling_factor=1.0)
    res_lo = dna_real(300, 10.0, _executor(seed=11), 64, sample_size=15,
                      scaling_factor=0.7)
    assert res_lo.cores >= res_hi.cores


def test_required_cores_ceil():
    assert required_cores(3.01) == 4
    assert required_cores(0.0) == 1


# ---------------------------------------------------------------------------
# sampling / admission correctness regressions (ISSUE 2)


class _RecordingExecutor:
    """Wraps an executor and records every id block it is asked to run."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list[list[int]] = []

    def __call__(self, ids):
        ids = list(ids)
        self.calls.append(ids)
        return self.inner(ids)


def test_dna_preprocesses_a_random_sample():
    """Regression: the preprocessing sample must be a seeded random draw
    without replacement — not the first s query ids (which bias t_max/t_avg
    whenever cost correlates with id order, against Eq. 1's premise)."""
    ex = _RecordingExecutor(_executor(mean=0.01, cv=0.1, seed=0))
    res = dna(500, deadline=5.0, executor=ex, sample_size=20, seed=123)
    sample = ex.calls[0]
    assert len(sample) == 20 and len(set(sample)) == 20
    assert all(0 <= q < 500 for q in sample)
    assert sample != list(range(20))
    # sample + slotted remainder partition the workload exactly
    slotted = [q for slot in res.plan.slots for q in slot]
    assert sorted(sample + slotted) == list(range(500))


def test_dna_retry_redraws_fresh_sample():
    """Regression: a deadline-missing attempt must NOT re-execute the same
    sample ids — the docstring's "retry (fresh sample)" is a redraw."""
    inner = _executor(mean=0.01, cv=0.1, seed=1)
    calls: list[list[int]] = []

    def ex(ids):
        ids = list(ids)
        calls.append(ids)
        if len(calls) == 1:               # poison only the first attempt
            return RuntimeStats(np.full(len(ids), 99.0))   # t_max > T
        return inner(ids)

    res = dna(300, deadline=5.0, executor=ex, sample_size=15, seed=7)
    assert res.attempts == 2
    assert calls[0] != calls[1], "retry re-executed the same sample"
    assert len(set(calls[1])) == 15


def test_dna_sample_deterministic_per_seed():
    ex_a = _RecordingExecutor(_executor(mean=0.01, cv=0.1, seed=3))
    ex_b = _RecordingExecutor(_executor(mean=0.01, cv=0.1, seed=3))
    res_a = dna(200, deadline=5.0, executor=ex_a, sample_size=10, seed=42)
    res_b = dna(200, deadline=5.0, executor=ex_b, sample_size=10, seed=42)
    assert ex_a.calls[0] == ex_b.calls[0]
    assert res_a.cores == res_b.cores


def test_dna_real_preprocesses_a_random_sample():
    ex = _RecordingExecutor(_executor(mean=0.01, cv=0.1, seed=5))
    res = dna_real(400, deadline=10.0, executor=ex, max_cores=64,
                   sample_size=25, seed=9)
    sample = ex.calls[0]
    assert len(sample) == 25 and len(set(sample)) == 25
    assert sample != list(range(25))
    slotted = [q for slot in res.plan.slots for q in slot]
    assert sorted(sample + slotted) == list(range(400))


def test_readmit_honest_feasibility():
    """Regression: readmit routes through lemma1_lower_bound (t_max > T and
    T <= 0 are infeasible, not ratio-masked) and reports feasible=False when
    the asked deadline does not hold — with the minimal §III-A extension."""
    alloc = DeviceAllocator(devices=list(range(4)), spares_fraction=0.0)
    stats = RuntimeStats(np.full(5, 1.0))
    ok = alloc.readmit(2, 10.0, stats)
    assert ok.feasible and not ok.extended and ok.cores == 1
    bad = alloc.readmit(100, 1.0, stats)
    assert not bad.feasible and bad.extended
    assert bad.deadline == pytest.approx(25.0)
    assert bad.cores == 4                    # full capacity genuinely needed
    # t_max exceeds the deadline: the raw X*t_max/T ratio can still be small
    # (here 1*1/0.5 = 2 <= 4 cores) — the shared bound rejects it instead
    tight = alloc.readmit(1, 0.5, stats)
    assert not tight.feasible and tight.extended
    assert tight.deadline >= stats.t_max
    assert tight.cores == 1                  # one query fits one core at T'
    # non-positive deadline is no longer masked by max(deadline, 1e-12)
    zero = alloc.readmit(10, 0.0, stats)
    assert not zero.feasible and zero.extended and zero.deadline >= 2.5
    done = alloc.readmit(0, 1.0, stats)
    assert done.feasible and done.cores == 0


def test_admission_or_extend_adopts_extension():
    from repro.ft.elastic import admission_or_extend

    alloc = DeviceAllocator(devices=list(range(4)), spares_fraction=0.0)
    stats = RuntimeStats(np.full(5, 1.0))
    assert admission_or_extend(alloc, 4, 10.0, stats) == 10.0
    assert admission_or_extend(alloc, 100, 1.0, stats) == pytest.approx(25.0)
