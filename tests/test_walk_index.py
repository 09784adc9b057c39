"""Walk-index subsystem (DESIGN.md §11): builder-vs-live exactness, budget
fallback invariance, accuracy envelope under partial coverage, the
walk_endpoint_gather kernel, and the executor integration."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional dev dep (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.index import WalkIndex
from repro.kernels import ops, ref
from repro.kernels.walk_gather import walk_endpoint_gather_pallas
from repro.ppr import (ForaExecutor, ForaParams, PprWorkload, fora_fused,
                       ppr_power_iteration, small_test_graph)
from repro.ppr.random_walk import lane_streams, walk_endpoints

PARAMS = ForaParams(alpha=0.2, epsilon=0.5)


@pytest.fixture(scope="module")
def graph():
    return small_test_graph(n=120, avg_deg=6, seed=0)


def _index(graph, width, seed=3):
    rp = PARAMS.resolve(graph)
    return WalkIndex.build(graph.device(), width=width, alpha=rp.alpha,
                           walk_tail=rp.walk_tail, seed=seed)


# ---------------------------------------------------------------------------
# exactness: stored endpoints ARE the live endpoints of the same stream


def test_builder_matches_live_walkers(graph):
    """endpoints[v, i] must equal a live walk from v on lane i's stream —
    the bit-for-bit contract that makes table lookups and live fallbacks
    interchangeable."""
    idx = _index(graph, width=16)
    dg = graph.device()
    lanes = jnp.arange(16, dtype=jnp.int32)
    us = lane_streams(idx.key, lanes, idx.num_steps)
    for v in [0, 7, 42, graph.n - 1]:
        starts = jnp.full((16,), v, jnp.int32)
        live = walk_endpoints(dg.edge_dst, dg.out_offsets, dg.out_degree,
                              starts, us, alpha=idx.alpha)
        np.testing.assert_array_equal(np.asarray(idx.endpoints)[v],
                                      np.asarray(live))


def test_index_backed_fused_bit_for_bit_full_coverage(graph):
    """ISSUE-5 property: with the stored budget covering the full walk
    budget, the index-backed fused path must match the live-walk path (same
    RNG stream) bit-for-bit — the table path is a pure gather, the live
    path steps every lane, and the outputs are IDENTICAL."""
    dg = graph.device()
    srcs = np.array([0, 7, 42], np.int32)
    key = jax.random.PRNGKey(5)
    idx = _index(graph, width=256)
    gather = fora_fused(dg, srcs, PARAMS, key, num_walks=256, index=idx)
    live_idx = _index(graph, width=256)
    live_idx.retire(np.arange(graph.n))   # budget 0 -> every lane walks live
    live = fora_fused(dg, srcs, PARAMS, key, num_walks=256, index=live_idx)
    np.testing.assert_array_equal(np.asarray(gather.pi), np.asarray(live.pi))
    np.testing.assert_array_equal(np.asarray(gather.walks_effective),
                                  np.asarray(live.walks_effective))


@given(st.integers(0, 2**31 - 1), st.integers(0, 5))
@settings(max_examples=8, deadline=None)
def test_any_budget_configuration_is_answer_invariant(seed, case):
    """Budget changes (retire to any level, width shortfalls) only move
    lanes between the table and the live fallback on the SAME stream, so
    every configuration of an unrefreshed index gives identical answers."""
    graph = small_test_graph(n=80, avg_deg=5, seed=1)
    dg = graph.device()
    srcs = np.array([3, 11], np.int32)
    key = jax.random.PRNGKey(seed)
    full = _index(graph, width=128, seed=7)
    ref_res = fora_fused(dg, srcs, PARAMS, key, num_walks=128, index=full)
    other = _index(graph, width=128, seed=7)
    rng = np.random.default_rng(case)
    nodes = rng.choice(graph.n, size=rng.integers(1, graph.n), replace=False)
    other.retire(nodes, budget=int(rng.integers(0, 129)))
    got = fora_fused(dg, srcs, PARAMS, key, num_walks=128, index=other)
    np.testing.assert_array_equal(np.asarray(ref_res.pi), np.asarray(got.pi))


def test_width_shortfall_falls_back_to_live_tail(graph):
    """width < num_walks: lanes beyond the table walk live on the same
    streams — still identical to the all-live index run."""
    dg = graph.device()
    srcs = np.array([0, 42], np.int32)
    key = jax.random.PRNGKey(2)
    small = _index(graph, width=64, seed=9)
    a = fora_fused(dg, srcs, PARAMS, key, num_walks=256, index=small)
    all_live = _index(graph, width=64, seed=9)
    all_live.retire(np.arange(graph.n))
    b = fora_fused(dg, srcs, PARAMS, key, num_walks=256, index=all_live)
    np.testing.assert_array_equal(np.asarray(a.pi), np.asarray(b.pi))


# ---------------------------------------------------------------------------
# accuracy: the (epsilon, p_f) envelope survives partial coverage + refresh


def test_partial_coverage_meets_fora_guarantee(graph):
    """Under partial coverage (width shortfall AND refreshed rows — the
    fully decorrelated worst case) the index-backed estimator must still
    satisfy |pi_hat - pi| <= eps*pi for pi >= delta."""
    dg = graph.device()
    srcs = np.array([0, 7, 42], np.int32)
    exact = ppr_power_iteration(graph, srcs, alpha=0.2)
    idx = _index(graph, width=512, seed=4)
    idx.refresh(np.arange(0, graph.n, 3))        # off the base stream
    idx.retire(np.arange(1, graph.n, 3), budget=128)
    res = fora_fused(dg, srcs, PARAMS, jax.random.PRNGKey(0),
                     index=idx)                  # default (full) walk budget
    pi = np.asarray(res.pi)
    delta = 1.0 / graph.n
    mask = exact >= delta
    rel = np.abs(pi - exact)[mask] / exact[mask]
    assert rel.max() < 0.5, f"rel err {rel.max()} exceeds eps"
    assert np.allclose(pi.sum(axis=1), 1.0, atol=1e-3)


def test_refresh_decorrelates_and_restores_budget(graph):
    idx = _index(graph, width=64)
    before = np.asarray(idx.endpoints).copy()
    nodes = np.arange(0, graph.n, 2)
    idx.retire(nodes, budget=0)
    assert idx.partial
    idx.refresh(nodes)
    after = np.asarray(idx.endpoints)
    assert (np.asarray(idx.budget)[nodes] == idx.width).all()
    changed = (before[nodes] != after[nodes]).mean()
    assert changed > 0.5, "refresh must redraw rows on a fresh stream"
    untouched = np.setdiff1d(np.arange(graph.n), nodes)
    np.testing.assert_array_equal(before[untouched], after[untouched])


def test_coverage_and_validation(graph):
    idx = _index(graph, width=64)
    assert idx.coverage(64) == 1.0
    assert idx.coverage(256) == pytest.approx(0.25)
    idx.retire(np.arange(graph.n), budget=32)     # halve every budget
    # a partial index keeps the live-walk fallback for every lane, so there
    # is no time saving for admission to bank — coverage must say so
    assert idx.coverage(64) == 0.0
    with pytest.raises(ValueError):
        idx.coverage(0)
    # param mismatch is rejected before any device work
    dg = graph.device()
    with pytest.raises(ValueError, match="rebuild the index"):
        fora_fused(dg, np.array([0], np.int32),
                   ForaParams(alpha=0.3, epsilon=0.5),
                   jax.random.PRNGKey(0), index=idx)


def test_sharded_residency_rejects_index(graph):
    from jax.sharding import Mesh

    from repro.ppr import ShardedDeviceGraph

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    sdg = ShardedDeviceGraph.from_graph(graph, mesh)
    idx = _index(graph, width=16)
    with pytest.raises(ValueError, match="single-device"):
        fora_fused(sdg, np.array([0], np.int32), PARAMS,
                   jax.random.PRNGKey(0), index=idx)


# ---------------------------------------------------------------------------
# walk_endpoint_gather kernel


def test_walk_endpoint_gather_pallas_matches_ref():
    rng = np.random.default_rng(0)
    n, W, B, L = 300, 32, 4, 24
    endpoints = jnp.asarray(rng.integers(0, n, (n, W)), dtype=jnp.int32)
    budget = jnp.asarray(rng.integers(0, W + 1, n), dtype=jnp.int32)
    starts = jnp.asarray(rng.integers(0, n, (B, L)), dtype=jnp.int32)
    weights = jnp.asarray(rng.random((B, L)), dtype=jnp.float32)
    a = ref.walk_endpoint_gather_ref(endpoints, budget, starts, weights)
    b = walk_endpoint_gather_pallas(endpoints, budget, starts, weights,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # dispatch: force="pallas" exercises interpret mode off-TPU
    c = ops.walk_endpoint_gather(endpoints, budget, starts, weights,
                                 force="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


def test_walk_endpoint_gather_budget_masks_lanes():
    """Lanes at/beyond a node's budget must contribute exactly zero (they
    belong to the live fallback)."""
    n, W = 8, 4
    endpoints = jnp.zeros((n, W), jnp.int32).at[:, :].set(5)
    budget = jnp.asarray([0, 1, 2, 3, 4, 4, 4, 4], jnp.int32)
    starts = jnp.asarray([[0, 1, 2, 4]], jnp.int32)
    weights = jnp.ones((1, 4), jnp.float32)
    out = np.asarray(ref.walk_endpoint_gather_ref(endpoints, budget, starts,
                                                  weights))
    # lane i is covered iff i < budget[start]: lane 0 @node0 (budget 0),
    # lane 1 @node1 (budget 1) and lane 2 @node2 (budget 2) all fail the
    # strict bound; only lane 3 @node4 (budget 4) lands
    assert out[0, 5] == pytest.approx(1.0)
    assert out.sum() == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# executor integration + zero-host-sync


def test_executor_builds_index_once_and_covers(graph):
    workload = PprWorkload(graph, num_queries=8, seed=0)
    builds = WalkIndex.builds
    ex = ForaExecutor(workload, PARAMS, fused=True, index_budget=1 << 14)
    assert ex.index_coverage == 0.0               # not warmed yet
    ex(list(range(4)))
    assert WalkIndex.builds == builds + 1
    assert ex.index_coverage == 1.0               # 2^14 covers any budget
    ex.run_chunk([4, 5])
    assert WalkIndex.builds == builds + 1         # build-once
    # degrade keeps the index (alpha / truncation length unchanged)
    idx = ex.walk_index
    ex.degrade(0.5)
    ex.run_chunk([6, 7])
    assert ex.walk_index is idx


def test_executor_rejects_index_with_sharding_or_legacy(graph):
    workload = PprWorkload(graph, num_queries=4, seed=0)
    with pytest.raises(ValueError, match="single-device"):
        ForaExecutor(workload, PARAMS, fused=True, devices=2, index_budget=8)
    with pytest.raises(ValueError, match="fused"):
        ForaExecutor(workload, PARAMS, fused=False, index_budget=8)


def test_index_backed_fused_no_host_transfer(graph):
    """The zero-host-sync contract survives the index: with the table
    device-resident, the whole index-backed call runs under
    transfer_guard('disallow')."""
    dg = graph.device()
    idx = _index(graph, width=128)
    srcs = jnp.asarray(np.array([3, 9], np.int32))
    key = jax.random.PRNGKey(1)
    fora_fused(dg, srcs, PARAMS, key, num_walks=128, index=idx)   # warm
    with jax.transfer_guard("disallow"):
        res = fora_fused(dg, srcs, PARAMS, key, num_walks=128, index=idx)
    pi = np.asarray(res.pi)                     # readout outside the guard
    assert np.allclose(pi.sum(axis=1), 1.0, atol=1e-3)
