"""Sliced-ELL layout + kernel: parity vs the COO segment_sum oracle on
power-law graphs (DESIGN.md §8), width heuristic, DeviceGraph layout policy,
and the web-scale memory acceptance bound (dense ELL infeasible, sliced
CSR-sized)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional dev dep (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.ell_spmv import ell_spmm_pallas, ell_spmm_sliced_pallas
from repro.ppr import DeviceGraph, ForaParams, fora_fused, small_test_graph
from repro.ppr.forward_push import forward_push, forward_push_coo
from repro.ppr.graph import Graph

GIB = 1 << 30
MIB = 1 << 20


def powerlaw_graph(n: int, avg_deg: int = 4, hubs: int = 1,
                   seed: int = 0) -> Graph:
    """Synthetic power-law graph: ``hubs`` nodes receive an in-edge from
    every other node (max in-degree ~ n), the rest is a sparse random tail.
    Nodes in [0.9n, n) have no out-edges (dangling -> self-loop at
    construction); random targets stay below 0.8n so nodes in [0.8n, 0.9n)
    have in-degree 0 (no virtual row at all in the sliced view)."""
    rng = np.random.default_rng(seed)
    m_tail = n * avg_deg
    src = np.concatenate([
        np.tile(np.arange(n, dtype=np.int64), hubs),          # hub in-edges
        rng.integers(0, int(0.9 * n), size=m_tail),
    ])
    dst = np.concatenate([
        np.repeat(np.arange(hubs, dtype=np.int64), n),
        rng.integers(0, int(0.8 * n), size=m_tail),
    ])
    return Graph.from_edges(n, src, dst, name=f"powerlaw{n}")


def coo_push_oracle(g: Graph, x: np.ndarray,
                    threshold: np.ndarray | None = None) -> np.ndarray:
    """The semantic definition the kernels must match: one pull relaxation
    y = P^T f(x) computed edge-by-edge with np.add.at (segment sum)."""
    xs = x if threshold is None else np.where(x > threshold[None, :], x, 0.0)
    contrib = xs[:, g.edge_src] / np.maximum(g.out_degree, 1)[g.edge_src]
    out = np.zeros(x.shape, np.float64)
    for b in range(x.shape[0]):
        np.add.at(out[b], g.edge_dst, contrib[b])
    return out


# ---------------------------------------------------------------------------
# layout


def test_width_heuristic_lane_aligned_and_cheaper():
    g = powerlaw_graph(400, seed=1)
    W = g.sliced_ell_width(pad_multiple=8)
    assert W % 8 == 0 and W >= 8
    deg = g.in_degree.astype(np.int64)
    sliced_cells = int(np.ceil(deg / W).sum()) * W
    dense_cells = g.n * ((g.max_in_degree + 7) // 8) * 8
    assert sliced_cells <= dense_cells
    # power-law: the win must be large (hub row dominates the dense table)
    assert dense_cells >= 10 * sliced_cells


def test_width_floor_follows_backend():
    """Real-TPU lane floor (ROADMAP follow-up): where the TPU Pallas SpMM
    runs, the default sliced width snaps to multiples of 128 (the kernel's
    lane-chunk width); interpret/CPU, and the TPU's XLA SpMM, keep the
    cheap 8."""
    import repro.ppr.graph as graph_mod

    g = powerlaw_graph(400, seed=1)
    assert graph_mod._default_pad_multiple() == 8       # CPU test session
    w_cpu = g.sliced_ell_width()
    assert w_cpu % 8 == 0
    # explicit 128 floor — what a TPU deployment resolves to
    w_tpu = g.sliced_ell_width(pad_multiple=128)
    assert w_tpu % 128 == 0 and w_tpu >= 128
    deg = g.in_degree.astype(np.int64)
    dense_w = ((g.max_in_degree + 127) // 128) * 128
    cells = {W: int(np.ceil(deg / W).sum()) * W
             for W in (128, 256, dense_w)}
    assert cells[w_tpu] == min(cells.values())          # still area-minimal
    # the backend hook itself drives the default resolution
    orig = graph_mod._default_pad_multiple
    try:
        graph_mod.__dict__["_default_pad_multiple"] = lambda: 128
        assert g.sliced_ell_width() % 128 == 0
        sl = g.ell_in_sliced()
        assert sl.width % 128 == 0
    finally:
        graph_mod.__dict__["_default_pad_multiple"] = orig


def test_sliced_view_invariants():
    g = powerlaw_graph(300, seed=2)
    sl = g.ell_in_sliced(width=12, pad_multiple=8)   # rounds up to 16
    assert sl.width == 16
    assert sl.neighbors.shape == (sl.n_virtual, 16)
    assert int(sl.mask.sum()) == g.m                 # every edge exactly once
    assert (np.diff(sl.row_map) >= 0).all()          # sorted for segment_sum
    # every row's virtual-row count is ceil(in_deg / W); deg-0 rows get none
    counts = np.bincount(sl.row_map, minlength=g.n)
    expect = -(-g.in_degree.astype(np.int64) // 16)
    np.testing.assert_array_equal(counts, expect)
    assert (g.in_degree == 0).any()                  # generator covers deg-0
    # hub row split into many slices, each fully inside its width
    assert counts[0] == -(-g.in_degree[0] // 16) > 10


@given(st.integers(80, 240), st.integers(8, 40), st.integers(0, 3))
@settings(max_examples=12, deadline=None)
def test_sliced_ref_matches_coo_oracle(n, width, seed):
    """Property: sliced SpMM == edge-list segment_sum oracle on power-law
    graphs with max in-degree >> W, dangling nodes, ragged last slices."""
    g = powerlaw_graph(n, seed=seed)
    sl = g.ell_in_sliced(width=width)
    assert g.max_in_degree > sl.width                # rows actually split
    rng = np.random.default_rng(seed)
    x = rng.random((3, g.n)).astype(np.float32)
    got = np.asarray(ops.ell_spmm_sliced(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
        jnp.asarray(sl.weights), jnp.asarray(sl.row_map), jnp.asarray(x)))
    np.testing.assert_allclose(got, coo_push_oracle(g, x), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# kernel (interpret mode) vs oracle


@pytest.mark.parametrize("n,width,block_n", [
    (100, 8, 32),     # slices of the hub row straddle block_n tiles
    (150, 24, 64),    # W spanning a ragged fraction of a 128-lane chunk
    (130, 8, 256),    # whole table in one tile
])
def test_sliced_pallas_matches_ref(n, width, block_n):
    g = powerlaw_graph(n, seed=5)
    sl = g.ell_in_sliced(width=width)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((4, g.n)).astype(np.float32))
    args = (jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
            jnp.asarray(sl.weights), jnp.asarray(sl.row_map), x)
    got = ell_spmm_sliced_pallas(*args, block_n=block_n, interpret=True)
    expect = ref.ell_spmm_sliced_ref(args[0], args[1], x, args[2],
                                     row_map=args[3])
    assert got.shape == (4, g.n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


def test_sliced_threshold_fusion_matches_explicit_masking():
    g = powerlaw_graph(120, seed=3)
    sl = g.ell_in_sliced(width=8)
    rng = np.random.default_rng(3)
    x = rng.random((2, g.n)).astype(np.float32)
    thr = (rng.random(g.n) * 0.5).astype(np.float32)
    got = np.asarray(ops.ell_spmm_sliced(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
        jnp.asarray(sl.weights), jnp.asarray(sl.row_map), jnp.asarray(x),
        threshold=jnp.asarray(thr), force="pallas"))
    np.testing.assert_allclose(got, coo_push_oracle(g, x, thr),
                               atol=1e-4, rtol=1e-4)


def test_sliced_equals_dense_spmm():
    """With no row above W the sliced path is the dense path + identity
    fold; with splits it must still agree with the dense kernel wherever the
    dense table is feasible."""
    g = small_test_graph(n=96, avg_deg=5, seed=4)
    nbr, msk, w = g.ell_in()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.random((2, g.n)).astype(np.float32))
    dense = ell_spmm_pallas(jnp.asarray(nbr), jnp.asarray(msk),
                            jnp.asarray(w), x, block_n=32,
                            interpret=True)
    for width in (8, 64):
        sl = g.ell_in_sliced(width=width)
        sliced = ell_spmm_sliced_pallas(
            jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
            jnp.asarray(sl.weights), jnp.asarray(sl.row_map), x, block_n=32,
            interpret=True)
        np.testing.assert_allclose(np.asarray(sliced), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# DeviceGraph layout policy + fused path


def test_device_graph_auto_layout():
    hub = powerlaw_graph(400, seed=6)
    uniform = small_test_graph(n=200, avg_deg=8, seed=1)
    assert DeviceGraph.from_graph(hub).layout == "sliced"
    assert DeviceGraph.from_graph(uniform).layout == "dense"
    forced = DeviceGraph.from_graph(uniform, layout="sliced", width=8)
    assert forced.layout == "sliced" and forced.ell_width == 8
    assert int(np.asarray(forced.in_mask).sum()) == uniform.m
    with pytest.raises(ValueError):
        DeviceGraph.from_graph(uniform, layout="csr")


def test_forward_push_sliced_parity_with_coo():
    """Deterministic push parity: sliced ELL sweep == COO segment_sum sweep
    (same frontier schedule => identical pi, r, iteration count)."""
    g = powerlaw_graph(350, seed=8)
    rp = ForaParams(alpha=0.2, epsilon=0.5).resolve(g)
    dg = g.device()
    assert dg.layout == "sliced"
    seeds = np.zeros((3, g.n), np.float32)
    seeds[[0, 1, 2], [0, 11, 42]] = 1.0
    push = forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                        dg.out_degree, jnp.asarray(seeds), alpha=rp.alpha,
                        rmax=rp.rmax, n=g.n, row_map=dg.in_row_map)
    push_coo = forward_push_coo(jnp.asarray(g.edge_src),
                                jnp.asarray(g.edge_dst),
                                jnp.asarray(g.out_degree),
                                jnp.asarray(seeds), alpha=rp.alpha,
                                rmax=rp.rmax, n=g.n)
    assert int(push.iters) == int(push_coo.iters)
    np.testing.assert_allclose(np.asarray(push.pi), np.asarray(push_coo.pi),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(push.r), np.asarray(push_coo.r),
                               atol=1e-5, rtol=1e-4)


def test_fora_fused_sliced_meets_guarantee():
    """End-to-end FORA on an auto-sliced power-law graph satisfies the
    eps-guarantee vs the power-iteration oracle."""
    from repro.ppr import ppr_power_iteration

    g = powerlaw_graph(400, seed=9)
    dg = g.device()
    assert dg.layout == "sliced"
    params = ForaParams(alpha=0.2, epsilon=0.5)
    res = fora_fused(dg, np.array([0, 17, 203]), params,
                     jax.random.PRNGKey(0))
    pi = np.asarray(res.pi)
    exact = ppr_power_iteration(g, np.array([0, 17, 203]), alpha=0.2)
    delta = 1.0 / g.n
    mask = exact >= delta
    rel = np.abs(pi - exact)[mask] / exact[mask]
    assert rel.max() < 0.5, f"sliced fused rel err {rel.max()} exceeds eps"
    assert np.allclose(pi.sum(axis=1), 1.0, atol=1e-3)


# ---------------------------------------------------------------------------
# web-scale acceptance: dense infeasible, sliced CSR-sized (ISSUE 2)


def test_webscale_memory_bound_and_parity():
    """LiveJournal-class degree skew at reduced node count: the dense ELL
    table would exceed 4 GiB (computed, never allocated) while the sliced
    table fits in < 256 MiB, and `fora_fused` still produces oracle-parity
    PPR through it."""
    n = 25_000
    g = powerlaw_graph(n, avg_deg=4, seed=12)
    assert g.max_in_degree >= 0.9 * n                # the hub row
    assert g.ell_in_dense_nbytes() > 4 * GIB
    sl = g.ell_in_sliced()
    assert sl.nbytes < 256 * MIB
    dg = g.device()
    assert dg.layout == "sliced"

    # keep the walk phase CPU-sized; the guarantee maths is unchanged
    params = ForaParams(alpha=0.2, epsilon=0.5, delta=4e-3, p_f=0.01)
    rp = params.resolve(g)
    sources = np.array([0, 12_345])
    res = fora_fused(dg, sources, params, jax.random.PRNGKey(0))
    pi = np.asarray(res.pi)
    assert np.allclose(pi.sum(axis=1), 1.0, atol=1e-3)

    # push phase is deterministic: sliced ELL == COO segment_sum oracle
    seeds = np.zeros((2, n), np.float32)
    seeds[[0, 1], sources] = 1.0
    push = forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                        dg.out_degree, jnp.asarray(seeds), alpha=rp.alpha,
                        rmax=rp.rmax, n=n, row_map=dg.in_row_map)
    push_coo = forward_push_coo(jnp.asarray(g.edge_src),
                                jnp.asarray(g.edge_dst),
                                jnp.asarray(g.out_degree), jnp.asarray(seeds),
                                alpha=rp.alpha, rmax=rp.rmax, n=n)
    assert int(push.iters) == int(push_coo.iters)
    np.testing.assert_allclose(np.asarray(push.pi), np.asarray(push_coo.pi),
                               atol=1e-6, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(push.r), np.asarray(push_coo.r),
                               atol=1e-6, rtol=1e-4)
