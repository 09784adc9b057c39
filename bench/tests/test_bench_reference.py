"""The plain references, the byte counts, the peaks table and the check."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import check, graphgen, reference, roofline  # noqa: E402

ALPHA = 0.2


def _cycle(n=3):
    src = np.arange(n, dtype=np.int32)
    return src, (src + 1) % n


def test_exact_ppr_on_a_cycle():
    n = 3
    pt = reference.transition_t(n, *_cycle(n))
    got = reference.exact_ppr(pt, np.array([0, 2]), alpha=ALPHA)
    k = np.arange(n)
    row0 = ALPHA * (1 - ALPHA) ** k / (1 - (1 - ALPHA) ** n)
    # the iteration stops at an L1 error under 1e-12
    np.testing.assert_allclose(got[0], row0, rtol=0, atol=1e-12)
    np.testing.assert_allclose(got[1], np.roll(row0, 2), rtol=0, atol=1e-12)


@pytest.mark.parametrize("rmax", [2.0, 0.5, 1e-3])
def test_push_reference_counts_on_a_cycle(rmax):
    # one node holds all the residual; each sweep moves (1 - alpha) of it on
    n = 3
    pt = reference.transition_t(n, *_cycle(n))
    r_sum, sweeps = reference.push_reference(pt, np.ones(n), np.array([1]),
                                             alpha=ALPHA, rmax=rmax)
    want = max(0, math.ceil(math.log(rmax) / math.log(1 - ALPHA)))
    assert sweeps.tolist() == [want]
    assert r_sum[0] == pytest.approx((1 - ALPHA) ** want, rel=1e-12)


def test_reference_refuses_a_dangling_node():
    with pytest.raises(ValueError):
        reference.transition_t(3, np.array([0, 1]), np.array([1, 0]))


def test_byte_counts_of_a_tiny_graph():
    # n = 3 nodes, m = 3 arcs, one column: 24 + 12 + 48
    assert roofline.push_sweep_bytes(3, 3, 1) == 84
    assert roofline.push_bytes(5, 3, 3, 1) == 420
    # 8 columns: 24 + 96 + 384
    assert roofline.push_sweep_bytes(3, 3, 8) == 504
    # ceil(0.5 * 10) = 5 walks of 1/0.2 = 5 steps of 16 bytes
    assert roofline.walk_bytes(0.5, 10.0, 0.2) == pytest.approx(400.0)
    assert roofline.walk_bytes(0.51, 10.0, 0.2) == pytest.approx(480.0)


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v4")


def test_check_numbers_and_verdict():
    ref = np.array([[0.5, 0.3, 0.2]])
    limits = {"max_rel_err": 0.5, "rsum_rel_gap": 1e-3, "mass_gap": 1e-2,
              "walks_short": 0}
    same = dict(check.numbers(ref, np.array([0.1]), ref, np.array([0.1]),
                              delta=0.25), walks_short=0)
    assert same == {"max_rel_err": 0.0, "rsum_rel_gap": 0.0,
                    "mass_gap": 0.0, "walks_short": 0}
    assert check.verdict(same, limits)
    off = dict(check.numbers(np.array([[0.5, 0.1, 0.2]]),
                             np.array([0.1002]), ref, np.array([0.1]),
                             delta=0.25), walks_short=0)
    assert off["max_rel_err"] == pytest.approx(2 / 3)
    assert off["rsum_rel_gap"] == pytest.approx(2e-3)
    assert off["mass_gap"] == pytest.approx(0.2)
    assert not check.verdict(off, limits)
    assert not check.verdict(dict(same, mass_gap=math.nan), limits)
    assert not check.verdict(dict(same, walks_short=1), limits)


def test_walks_short_counts_answers_under_fora_budget():
    # budgets ceil(0.1 * 100) = 10 and ceil(0.101 * 100) = 11
    assert check.fora_budget(0.101, 100.0) == 11
    assert check.walks_short([0.1, 0.101], [10, 10], 100.0) == 1
    assert check.walks_short([0.1, 0.101], [16, 16], 100.0) == 0
    lines = check.walk_budget_lines([4, 5], [0.1, 0.101], [10, 10], 100.0)
    assert ["under FORA's budget" in line for line in lines] == [False, True]


def test_control_in_bfloat16_fails_where_float32_passes():
    """The control at a size a test run can hold: the references computed
    in bfloat16 fail the check that the same computed in float32 passes."""
    n, m = 4_000, 32_000
    src, dst = graphgen.generate(n, m, directed=True, seed=5,
                                 max_in_degree=900)
    pt = reference.transition_t(n, src, dst)
    deg = np.bincount(src, minlength=n)
    sources = np.array([3, 1_234, 3_999])
    rmax = 1e-5
    pi = reference.exact_ppr(pt, sources, alpha=ALPHA)
    r_sum, _ = reference.push_reference(pt, deg, sources, alpha=ALPHA,
                                        rmax=rmax)
    steps = reference.iterations(ALPHA, 1e-12)
    limits = {"max_rel_err": 0.5, "rsum_rel_gap": 3e-4, "mass_gap": 1e-2,
              "walks_short": 0}
    readings = {}
    for dtype in ("float32", "bfloat16"):
        got, got_r = reference.control_answers(n, src, dst, sources,
                                               alpha=ALPHA, rmax=rmax,
                                               steps=steps, dtype=dtype)
        readings[dtype] = dict(check.numbers(got, got_r, pi, r_sum,
                                             delta=1 / n), walks_short=0)
    assert check.verdict(readings["float32"], limits), readings
    assert not check.verdict(readings["bfloat16"], limits), readings
