"""The benchmark's generator gives exactly n and m and keeps its hub under
the published largest in-degree, deterministically."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench import graphgen  # noqa: E402

CONFIGS = json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]


def check_shape(src, dst, n, m, directed, hub):
    """Exactly ``m`` distinct edges, no self-loop, no dangling node, and the
    largest in-degree (degree, undirected) in ``[0.9 hub, hub]``."""
    assert src.size == dst.size == m
    assert np.all(src != dst)
    lo, hi = (src, dst) if directed else (np.minimum(src, dst),
                                          np.maximum(src, dst))
    assert np.unique(lo.astype(np.int64) * n + hi).size == m
    ends = src if directed else np.concatenate([src, dst])
    assert np.bincount(ends, minlength=n).min() >= 1
    into = dst if directed else np.concatenate([src, dst])
    assert 0.9 * hub <= np.bincount(into, minlength=n).max() <= hub


@pytest.mark.parametrize("n,m,directed,hub", [(2_000, 16_400, True, 700),
                                              (3_000, 9_700, False, 500),
                                              (500, 2_500, True, 150)])
def test_exact_size_simple_and_no_dangling(n, m, directed, hub):
    src, dst = graphgen.generate(n, m, directed=directed, seed=7,
                                 max_in_degree=hub)
    assert src.size == dst.size == m
    assert src.dtype == dst.dtype == np.int32
    assert np.all(src != dst)
    lo, hi = (src, dst) if directed else (np.minimum(src, dst),
                                          np.maximum(src, dst))
    assert np.unique(lo.astype(np.int64) * n + hi).size == m
    if not directed:
        assert np.all(src < dst)
    ends = src if directed else np.concatenate([src, dst])
    assert np.bincount(ends, minlength=n).min() >= 1
    into = dst if directed else np.concatenate([src, dst])
    assert np.bincount(into, minlength=n).max() <= hub


@pytest.mark.parametrize("directed", [True, False])
def test_the_program_builds_exactly_m_arcs(directed):
    from repro.ppr.graph import Graph

    n, m = 1_500, 9_000
    src, dst = graphgen.generate(n, m, directed=directed, seed=3,
                                 max_in_degree=500)
    graph = Graph.from_edges(n, src, dst, directed=directed)
    assert graph.m == (m if directed else 2 * m)
    assert graph.out_degree.min() >= 1


def test_deterministic_per_seed():
    a = graphgen.generate(1_000, 7_000, directed=True, seed=11,
                          max_in_degree=400)
    b = graphgen.generate(1_000, 7_000, directed=True, seed=11,
                          max_in_degree=400)
    c = graphgen.generate(1_000, 7_000, directed=True, seed=12,
                          max_in_degree=400)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("n,m,directed", [(10, 9, True), (10, 91, True),
                                          (10, 46, False), (1, 1, True)])
def test_impossible_sizes_are_refused(n, m, directed):
    with pytest.raises(ValueError):
        graphgen.generate(n, m, directed=directed, seed=0,
                          max_in_degree=5)


@pytest.mark.parametrize("hub", [1, 20_000])
def test_a_hub_no_exponent_can_give_is_refused(hub):
    with pytest.raises(ValueError, match="no popularity exponent"):
        graphgen.generate(2_000, 16_400, directed=True, seed=0,
                          max_in_degree=hub)


def test_a_hub_over_the_bound_is_refused():
    """At this size repeated arcs merge too few of node 0's draws: its
    surplus arcs move to nodes under the bound, and the graph builds."""
    n, m, hub = 500, 500, 60
    src, dst = graphgen.generate(n, m, directed=True, seed=7,
                                 max_in_degree=hub)
    check_shape(src, dst, n, m, True, hub)


@pytest.mark.parametrize("n,m,directed,hub", [(1_000, 5_000, True, 4),
                                              (1_000, 2_600, False, 5)])
def test_a_bound_that_cannot_hold_m_edges_is_refused(n, m, directed, hub):
    with pytest.raises(ValueError, match="cannot hold"):
        graphgen.generate(n, m, directed=directed, seed=0, max_in_degree=hub)


# DBLP and a 1/16 copy of DBLP and of Pokec (arXiv:2407.00068 Table I): the
# hub takes so small a share of the draws that almost none of them merge
SPARSE_HUBS = [(38_349, 248_769, False, 343), (102_050, 1_913_910, True, 548)]


@pytest.mark.parametrize("n,m,directed,hub", SPARSE_HUBS)
def test_a_sparse_hub_is_moved_down_to_the_bound(n, m, directed, hub):
    src, dst = graphgen.generate(n, m, directed=directed, seed=7,
                                 max_in_degree=hub)
    check_shape(src, dst, n, m, directed, hub)


def test_a_moved_graph_is_deterministic_per_seed():
    n, m, directed, hub = SPARSE_HUBS[0]
    a, b = (graphgen.generate(n, m, directed=directed, seed=7,
                              max_in_degree=hub) for _ in range(2))
    assert a[0].tobytes() + a[1].tobytes() == b[0].tobytes() + b[1].tobytes()


def test_dblp_at_its_published_size_builds_under_the_bound():
    n, m, hub = 613_586, 3_980_318, 343
    src, dst = graphgen.generate(n, m, directed=False, seed=7,
                                 max_in_degree=hub)
    check_shape(src, dst, n, m, False, hub)


def test_the_web_stanford_graph_keeps_its_bytes():
    # the edge list both web-stanford cells serve: new bytes move the benchmark
    cfg = json.loads((ROOT / "bench/configs/web-stanford.json").read_text())
    src, dst = graphgen.generate(cfg["n"], cfg["m"], directed=cfg["directed"],
                                 seed=cfg["graph_seed"],
                                 max_in_degree=cfg["max_in_degree"])
    assert hashlib.sha256(src.tobytes() + dst.tobytes()).hexdigest() == (
        "c44132d390d3aef66748fcbd8bd4276c0afdc0ca2d62faf8bc55345e0f4715e5")


@pytest.mark.parametrize("config", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_the_configured_graph_has_its_published_shape(config):
    cfg = json.loads((ROOT / config["file"]).read_text())
    n, directed = cfg["n"], cfg["directed"]
    edges = cfg["m"] if directed else cfg["m"] // 2   # as bench/run.py builds
    src, dst = graphgen.generate(n, edges, directed=directed,
                                 seed=cfg["graph_seed"],
                                 max_in_degree=cfg["max_in_degree"])
    assert src.size == edges
    into = dst if directed else np.concatenate([src, dst])
    top = np.bincount(into, minlength=n).max()
    # the configuration's notes give the hub the generator keeps
    assert 0.9 * cfg["max_in_degree"] <= top <= cfg["max_in_degree"]
    assert f"{top:,}" in cfg["assumed"]["max_in_degree"]
