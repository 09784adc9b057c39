"""The benchmark's graph generator: a power-law graph at exactly n and m.

A copy of the repository's ``ppr/datasets.py::synthesize`` generator, kept
here so that no change to the program can move the yardstick, and corrected
to give the published order and size exactly: lognormal out-degrees with the
mean m/n, targets drawn from a Zipf-like popularity over node ids mixed with
a uniform tail, and then more draws until exactly ``m`` distinct edges exist
(arcs for a directed graph, unordered pairs for an undirected one). Every
node keeps at least one edge, so no node is dangling and the program adds no
self-loop: the graph it builds has exactly ``m`` arcs (``2 m`` undirected).

The popularity exponent is fitted to the published largest in-degree
(degree, for an undirected graph): the Zipf draws aim ``HUB_MARGIN`` of it
at node 0, the most popular node. Where the hub takes a large share of the
draws, repeated edges merge and bring it under the bound (node 0 keeps
36,552 of 38,606 for the web-stanford configuration). Where the hub takes a
small share (DBLP, Pokec, LiveJournal), few of its draws merge: then each
node over the bound keeps ``bound`` of its edges, and each of the others
keeps its other end and moves its end on that node to a node under the
bound, drawn by the same law; merging, topping up and trimming to ``m``
follow, until no node is over. Where the merges alone bring every node
under the bound, nothing moves and no more numbers are drawn. Refused: a
bound that no popularity exponent can aim at, and one under which n nodes
cannot hold m edges.

Deterministic per seed. Numpy only.
"""

from __future__ import annotations

import math

import numpy as np

UNIFORM_SHARE = 0.15  # share of targets drawn uniformly (datasets.py)
DEGREE_SIGMA = 1.0    # lognormal sigma of the out-degrees (datasets.py)
HUB_MARGIN = 1.1      # node 0's Zipf draws over the published largest in-degree


def zipf_exponent(n: int, m: int, max_in_degree: int) -> float:
    """The popularity exponent ``a`` at which ``HUB_MARGIN * max_in_degree``
    of ``m`` target draws land on node 0. A Zipf draw lands on node 0 with
    probability ``n ** (a - 1)``, and ``1 - UNIFORM_SHARE`` of the targets
    are Zipf draws."""
    share = HUB_MARGIN * max_in_degree / ((1.0 - UNIFORM_SHARE) * m)
    a = 1.0 + math.log(share) / math.log(n)
    if not 0.0 < a < 1.0:
        raise ValueError(f"no popularity exponent gives n={n}, m={m} a "
                         f"largest in-degree of {max_in_degree}")
    return a


def _targets(rng: np.random.Generator, n: int, size: int,
             a: float) -> np.ndarray:
    """Zipf-like popular targets over node ids, with a uniform tail."""
    u = rng.random(size)
    dst = (n * (u ** (1.0 / (1.0 - a)))).astype(np.int64) % n
    uniform = rng.integers(0, n, size=size)
    return np.where(rng.random(size) < UNIFORM_SHARE, uniform, dst)


def _no_self_loops(rng: np.random.Generator, n: int, src: np.ndarray,
                   dst: np.ndarray) -> np.ndarray:
    """Move a target that equals its source to another node, uniformly."""
    loop = src == dst
    shift = rng.integers(1, n, size=int(loop.sum()))
    dst = dst.copy()
    dst[loop] = (dst[loop] + shift) % n
    return dst


def _keys(n: int, src: np.ndarray, dst: np.ndarray,
          directed: bool) -> np.ndarray:
    if not directed:
        src, dst = np.minimum(src, dst), np.maximum(src, dst)
    return src * n + dst


def _exactly_m(rng: np.random.Generator, n: int, m: int, keys: np.ndarray,
               p_src: np.ndarray, a: float, directed: bool) -> np.ndarray:
    """``keys`` topped up with more draws, from sources in proportion to
    their degree, until ``m`` exist; then the surplus dropped at random,
    never the first edge of a node."""
    while keys.size < m:
        need = m - keys.size
        size = need + need // 4 + 64
        s = rng.choice(n, size=size, p=p_src)
        d = _no_self_loops(rng, n, s, _targets(rng, n, size, a))
        keys = np.unique(np.concatenate([keys, _keys(n, s, d, directed)]))
    if keys.size > m:
        lo, hi = keys // n, keys % n
        ends = np.concatenate([lo, hi]) if not directed else lo
        _, first = np.unique(ends, return_index=True)
        keep = np.zeros(keys.size, bool)
        keep[first % keys.size] = True
        spare = np.flatnonzero(~keep)
        drop = rng.choice(spare, size=keys.size - m, replace=False)
        keys = np.delete(keys, drop)
    return keys


def _redraw(rng: np.random.Generator, n: int, src: np.ndarray, a: float,
            room: np.ndarray) -> np.ndarray:
    """A target for each of ``src``, by ``_targets``' law and never the
    source itself, among the nodes where ``room`` holds."""
    dst = np.empty_like(src)
    todo = np.arange(src.size)
    while todo.size:
        d = _no_self_loops(rng, n, src[todo], _targets(rng, n, todo.size, a))
        ok = room[d]
        dst[todo[ok]] = d[ok]
        todo = todo[~ok]
    return dst


def _move_surplus(rng: np.random.Generator, n: int, src: np.ndarray,
                  dst: np.ndarray, degree: np.ndarray, bound: int, a: float,
                  directed: bool) -> np.ndarray:
    """The keys of the edges once each node over ``bound`` has kept
    ``bound`` of its edges: each of the others, picked at random, keeps its
    other end (an arc its source) and gets a new end among the nodes under
    the bound. Repeated edges are merged."""
    edge = np.arange(src.size)
    if directed:
        end, other = dst, src
    else:
        end, other = np.concatenate([src, dst]), np.concatenate([dst, src])
        edge = np.concatenate([edge, edge])
    at = np.flatnonzero(degree[end] > bound)       # ends on a node over it
    order = np.lexsort((rng.random(at.size), end[at]))
    at = at[order]
    node = end[at]
    rank = np.arange(at.size) - np.searchsorted(node, node)
    at = at[rank < degree[node] - bound]
    # an edge between two nodes over the bound moves one end at a time
    moved, first = np.unique(edge[at], return_index=True)
    kept_end = other[at][first]
    new_end = _redraw(rng, n, kept_end, a, degree < bound)
    stay = np.ones(src.size, bool)
    stay[moved] = False
    return np.unique(_keys(n, np.concatenate([src[stay], kept_end]),
                           np.concatenate([dst[stay], new_end]), directed))


def generate(n: int, m: int, *, directed: bool, seed: int,
             max_in_degree: int) -> tuple[np.ndarray, np.ndarray]:
    """Edges ``(src, dst)`` (int32) of a graph with exactly ``m`` distinct
    edges over ``n`` nodes, no self-loop and no in-degree (degree, if
    undirected) above ``max_in_degree``. For an undirected graph each edge
    is given once, ``src < dst``. Every node has an edge (an out-arc, if
    directed), so none is dangling."""
    if n < 2 or not n <= m <= n * (n - 1) // (1 if directed else 2):
        raise ValueError(f"no simple graph with n={n} and m={m}")
    a = zipf_exponent(n, m, max_in_degree)
    if (1 if directed else 2) * m > n * max_in_degree:
        raise ValueError(f"{n} nodes cannot hold {m} edges with no "
                         f"{'in-degree' if directed else 'degree'} over "
                         f"{max_in_degree}")
    rng = np.random.default_rng(seed)
    avg_deg = m / n
    mu = np.log(avg_deg) - DEGREE_SIGMA ** 2 / 2.0
    deg = np.maximum(1, rng.lognormal(mu, DEGREE_SIGMA, size=n)).astype(
        np.int64)
    deg = np.minimum(deg, max(64, int(16 * avg_deg)))
    # each node draws its own edges first, so every node has one
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    dst = _no_self_loops(rng, n, src, _targets(rng, n, src.size, a))
    p_src = deg / deg.sum()
    keys = _exactly_m(rng, n, m, np.unique(_keys(n, src, dst, directed)),
                      p_src, a, directed)
    while True:
        src, dst = keys // n, keys % n
        ends = dst if directed else np.concatenate([src, dst])
        degree = np.bincount(ends, minlength=n)
        if degree.max() <= max_in_degree:
            return src.astype(np.int32), dst.astype(np.int32)
        keys = _exactly_m(rng, n, m,
                          _move_surplus(rng, n, src, dst, degree,
                                        max_in_degree, a, directed),
                          p_src, a, directed)
