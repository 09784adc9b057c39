"""Graph container for PPR computations on TPU.

Three synchronized views of one directed graph (dangling nodes receive a
self-loop at construction so both push and walk semantics are total):

* **COO**  — ``edge_src``/``edge_dst`` sorted by src: drives the
  ``segment_sum`` frontier relaxation in :mod:`repro.ppr.forward_push`
  (the taxonomy's GNN message-passing regime — JAX has no CSR SpMV, so
  scatter-by-edge IS the system here, per the assignment notes).
* **CSR**  — ``out_offsets`` into ``edge_dst``: O(1) uniform out-neighbor
  sampling for random walks (``edge_dst[offsets[v] + u % deg(v)]``).
* **ELL**  — ``(n, k_max)`` padded neighbor table + validity mask: the
  VMEM-tileable layout consumed by the Pallas ``ell_spmv``/``ell_spmm``
  kernels. ``ell()`` is the out-neighbor view; ``ell_in()`` is the pull-form
  in-neighbor view (rows indexed by destination, weights 1/deg_out(src))
  that turns a push sweep into one SpMM (DESIGN.md §5).
* **Sliced ELL** — ``ell_in_sliced()``: the power-law-safe variant of
  ``ell_in``. Rows with in-degree > W are split into ceil(deg/W) *virtual*
  rows of width <= W; ``row_map (n_virtual,) int32`` points each virtual row
  back at its real row, and the SpMM combines slice partials with a
  ``segment_sum``. Memory is O(m + n_virtual·W) instead of O(n·k_max) — on
  LiveJournal-class graphs (max in-degree in the tens of thousands) that is
  the difference between tens of GiB and a CSR-sized table (DESIGN.md §8).

All index arrays are int32 (TPU-native); n and m up to ~2^31.

``DeviceGraph`` (via ``Graph.device()``) is the upload-once device-resident
mirror: CSR + pull-ELL arrays are put on device exactly once per Graph and
reused by every query of a workload — the fused FORA hot path (DESIGN.md §7)
never re-transfers graph structure. The mirror picks the dense or sliced ELL
layout automatically from the degree distribution (``layout="auto"``).

``ShardedDeviceGraph`` (via ``Graph.device(mesh=...)``) is the multi-chip
generalisation (DESIGN.md §9): the push table is row-sharded over a mesh axis
(dense by destination row, sliced by virtual row) while the CSR walk arrays
are replicated — the D&A allocator's "k cores" become k shards of one mesh.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Any, ClassVar, NamedTuple

import numpy as np


def _round_up(v: int, multiple: int) -> int:
    return max(multiple, ((v + multiple - 1) // multiple) * multiple)


def inverse_out_degree(out_degree: np.ndarray) -> np.ndarray:
    """FORA's spread factor 1/max(deg_out, 1) as float32 — the ONE weight
    formula shared by the fresh residency builders (``ell_in`` /
    ``ell_in_sliced``) and the dynamic-graph delta path (``repro.dyn``).
    Both must produce the same bits per node or apply-then-compact stops
    being an identity (DESIGN.md §16)."""
    return 1.0 / np.maximum(out_degree, 1).astype(np.float32)


def _default_pad_multiple() -> int:
    """Lane-alignment floor for the sliced push table. The TPU Pallas SpMM
    chunks the lane axis in 128s (DESIGN.md §8), so where it runs, widths
    below 128 only add fold overhead. Everywhere else the floor is 8: on
    the CPU, and on the TPU while its push SpMM runs as XLA
    (``kernels/ops.py`` ``TPU_KERNELS``, ROADMAP S0) — there a 128 floor
    would only pad, and web-Stanford's table would grow from 3.1M cells to
    33.7M for 1.8M edges, every one gathered each sweep. Deferred import so
    graph.py stays importable without jax."""
    from ..kernels import ops

    return 128 if ops.tpu_kernel("ell_spmm_sliced") else 8


class SlicedEll(NamedTuple):
    """Sliced pull-form ELL view: high-degree rows split into virtual rows.

    ``neighbors``/``mask``/``weights`` are (n_virtual, width); ``row_map``
    (n_virtual,) int32 maps each virtual row to its real destination row and
    is sorted ascending (slices of one row are contiguous), so the SpMM
    combine is a sorted ``segment_sum``. Real rows with in-degree 0
    contribute no virtual row — the segment combine leaves them at 0.
    """

    neighbors: np.ndarray   # (n_virtual, width) int32, global source ids
    mask: np.ndarray        # (n_virtual, width) bool
    weights: np.ndarray     # (n_virtual, width) f32, 1/deg_out(src)
    row_map: np.ndarray     # (n_virtual,) int32, ascending
    width: int              # W — slice width (lane-aligned)
    n: int                  # real row count the view folds back into

    @property
    def n_virtual(self) -> int:
        return int(self.neighbors.shape[0])

    @property
    def nbytes(self) -> int:
        """Resident bytes of the sliced table (+ row_map)."""
        return (self.neighbors.nbytes + self.mask.nbytes
                + self.weights.nbytes + self.row_map.nbytes)


@dataclass(frozen=True)
class Graph:
    """Immutable directed graph in COO+CSR(+lazy ELL) form."""

    n: int
    edge_src: np.ndarray     # (m,) int32, sorted ascending
    edge_dst: np.ndarray     # (m,) int32
    directed: bool = True
    name: str = "graph"

    def __post_init__(self) -> None:
        if self.n < 1:
            raise ValueError("graph must have at least one node")
        es = np.asarray(self.edge_src, dtype=np.int32)
        ed = np.asarray(self.edge_dst, dtype=np.int32)
        if es.shape != ed.shape or es.ndim != 1:
            raise ValueError("edge_src/edge_dst must be equal-length 1-D")
        if es.size and (es.min() < 0 or es.max() >= self.n
                        or ed.min() < 0 or ed.max() >= self.n):
            raise ValueError("edge endpoints out of range")
        if es.size and np.any(np.diff(es) < 0):
            order = np.argsort(es, kind="stable")
            es, ed = es[order], ed[order]
        object.__setattr__(self, "edge_src", es)
        object.__setattr__(self, "edge_dst", ed)

    # -- basic stats ---------------------------------------------------------
    @property
    def m(self) -> int:
        return int(self.edge_src.size)

    @cached_property
    def out_degree(self) -> np.ndarray:
        return np.bincount(self.edge_src, minlength=self.n).astype(np.int32)

    @cached_property
    def out_offsets(self) -> np.ndarray:
        """CSR row offsets, shape (n+1,)."""
        off = np.zeros(self.n + 1, dtype=np.int32)
        np.cumsum(self.out_degree, out=off[1:])
        return off

    @cached_property
    def max_out_degree(self) -> int:
        return int(self.out_degree.max()) if self.n else 0

    @property
    def avg_out_degree(self) -> float:
        return self.m / self.n

    # -- ELL view (for the Pallas kernel) -------------------------------------
    def ell(self, k_max: int | None = None,
            pad_multiple: int = 8) -> tuple[np.ndarray, np.ndarray]:
        """Padded neighbor table: (neighbors (n,K) int32, mask (n,K) bool).

        K = max out-degree rounded up to ``pad_multiple`` (lane alignment).
        Rows beyond their degree point at node 0 with mask False.
        """
        K = self.max_out_degree if k_max is None else k_max
        if K < self.max_out_degree:
            raise ValueError(f"k_max={K} < max out-degree {self.max_out_degree}"
                             " — high-degree rows need the sliced layout "
                             "(see ell_in_sliced for the pull view)")
        K = max(pad_multiple, ((K + pad_multiple - 1) // pad_multiple) * pad_multiple)
        neighbors = np.zeros((self.n, K), dtype=np.int32)
        mask = np.zeros((self.n, K), dtype=bool)
        deg = self.out_degree
        off = self.out_offsets
        # Vectorised ragged fill: position of each edge within its row.
        pos = np.arange(self.m, dtype=np.int64) - off[self.edge_src].astype(np.int64)
        neighbors[self.edge_src, pos] = self.edge_dst
        mask[self.edge_src, pos] = True
        del deg
        return neighbors, mask

    @cached_property
    def in_degree(self) -> np.ndarray:
        return np.bincount(self.edge_dst, minlength=self.n).astype(np.int32)

    @cached_property
    def max_in_degree(self) -> int:
        return int(self.in_degree.max()) if self.m else 0

    def ell_in(self, pad_multiple: int = 8
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pull-form padded in-neighbor table for the push-as-SpMM kernel.

        Returns (neighbors (n,K) int32, mask (n,K) bool, weights (n,K) f32):
        row i lists the sources of i's in-edges; weights carry FORA's spread
        factor 1/deg_out(src) so that  ell_spmm(nbr, mask, w, pushed) ==
        P^T pushed  (DESIGN.md §5). Padding entries point at node 0 with
        mask False and weight 0.
        """
        order = np.argsort(self.edge_dst, kind="stable")
        src_s = self.edge_src[order]
        dst_s = self.edge_dst[order]
        in_deg = np.bincount(dst_s, minlength=self.n)
        K = self.max_in_degree if self.m else 1
        K = max(pad_multiple,
                ((K + pad_multiple - 1) // pad_multiple) * pad_multiple)
        neighbors = np.zeros((self.n, K), dtype=np.int32)
        mask = np.zeros((self.n, K), dtype=bool)
        off = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(in_deg, out=off[1:])
        pos = np.arange(self.m, dtype=np.int64) - off[dst_s]
        neighbors[dst_s, pos] = src_s
        mask[dst_s, pos] = True
        inv_deg = inverse_out_degree(self.out_degree)
        weights = inv_deg[neighbors] * mask
        return neighbors, mask, weights.astype(np.float32)

    def ell_in_dense_nbytes(self, pad_multiple: int = 8) -> int:
        """Resident bytes :meth:`ell_in` *would* allocate — computed without
        materialising it, so web-scale infeasibility can be detected (and
        benchmarked) before an allocation that would OOM."""
        K = _round_up(self.max_in_degree if self.m else 1, pad_multiple)
        # int32 neighbors + bool mask + f32 weights per cell
        return self.n * K * (4 + 1 + 4)

    def _sliced_width_cells(self, pad_multiple: int | None = None
                            ) -> tuple[int, int]:
        """(width, padded cell count) minimising the sliced-table area —
        the single source of the cost formula used by both the width
        heuristic and the DeviceGraph auto-layout policy."""
        if pad_multiple is None:
            pad_multiple = _default_pad_multiple()
        if pad_multiple < 1:
            raise ValueError("pad_multiple must be >= 1")
        dense_w = _round_up(self.max_in_degree if self.m else 1, pad_multiple)
        deg = self.in_degree.astype(np.int64)
        candidates = []
        w = pad_multiple
        while w < dense_w:
            candidates.append(w)
            w *= 2
        candidates.append(dense_w)
        costs = {W: int(np.ceil(deg / W).sum()) * W for W in candidates}
        best = min(candidates, key=lambda W: (costs[W], W))
        return best, costs[best]

    def sliced_ell_width(self, pad_multiple: int | None = None) -> int:
        """Slice width W minimising the padded sliced-table area.

        Candidates are ``pad_multiple * 2^j`` (lane-aligned, geometric — the
        cost landscape is smooth enough that power-of-two steps find the
        basin) plus the dense width itself; cost(W) = sum_i ceil(deg_in(i)/W)
        * W, the cell count of the resulting (n_virtual, W) table. Ties go to
        the smaller W (less VMEM per row block). ``pad_multiple=None``
        resolves the backend-appropriate lane floor
        (:func:`_default_pad_multiple`): 128 where the TPU Pallas SpMM
        runs, 8 elsewhere.

        With an active ``kernels.autotune`` tuning cache and no pinned
        ``pad_multiple``, a measured width for this backend/shape-bucket
        overrides the area heuristic (DESIGN.md §15) — cold cache keeps the
        heuristic bit-for-bit.
        """
        if pad_multiple is None:
            tuned = _tuned_push_config(self, "sliced")
            if tuned is not None and tuned.width is not None:
                return tuned.width
        return self._sliced_width_cells(pad_multiple)[0]

    def ell_in_sliced(self, width: int | None = None,
                      pad_multiple: int | None = None) -> SlicedEll:
        """Power-law-safe pull-form ELL: rows wider than ``width`` are split.

        Same semantics as :meth:`ell_in` after folding virtual rows back
        through ``row_map`` with a segment sum; memory is O(m + n_virtual·W)
        instead of O(n·k_max). ``width=None`` applies
        :meth:`sliced_ell_width`'s area-minimising heuristic.
        """
        if pad_multiple is None:
            pad_multiple = _default_pad_multiple()
        W = self.sliced_ell_width(pad_multiple) if width is None \
            else _round_up(width, pad_multiple)
        order = np.argsort(self.edge_dst, kind="stable")
        src_s = self.edge_src[order]
        dst_s = self.edge_dst[order]
        in_deg = self.in_degree.astype(np.int64)
        slices = -(-in_deg // W)                       # ceil; 0 for deg-0 rows
        n_virtual = int(slices.sum())
        if n_virtual == 0:                             # edgeless graph
            return SlicedEll(neighbors=np.zeros((1, W), np.int32),
                             mask=np.zeros((1, W), bool),
                             weights=np.zeros((1, W), np.float32),
                             row_map=np.zeros(1, np.int32), width=W, n=self.n)
        voff = np.zeros(self.n + 1, dtype=np.int64)    # first virtual row of i
        np.cumsum(slices, out=voff[1:])
        row_map = np.repeat(np.arange(self.n, dtype=np.int32),
                            slices).astype(np.int32)
        off = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(in_deg, out=off[1:])
        pos = np.arange(self.m, dtype=np.int64) - off[dst_s]  # rank in row
        vrow = voff[dst_s] + pos // W
        vpos = pos % W
        neighbors = np.zeros((n_virtual, W), dtype=np.int32)
        mask = np.zeros((n_virtual, W), dtype=bool)
        neighbors[vrow, vpos] = src_s
        mask[vrow, vpos] = True
        inv_deg = inverse_out_degree(self.out_degree)
        weights = (inv_deg[neighbors] * mask).astype(np.float32)
        return SlicedEll(neighbors=neighbors, mask=mask, weights=weights,
                         row_map=row_map, width=W, n=self.n)

    @cached_property
    def _device(self) -> "DeviceGraph":
        return DeviceGraph.from_graph(self)

    # most-recent sharded residencies kept per graph: elastic re-grants walk
    # through different mesh shapes over a long-lived Graph, and an unbounded
    # cache would pin every superseded full-graph device copy forever
    SHARDED_CACHE_MAX: ClassVar[int] = 2

    @cached_property
    def _sharded_devices(self) -> dict:
        return {}

    def device(self, mesh: Any = None, *,
               axis: str = "shard") -> "DeviceGraph | ShardedDeviceGraph":
        """Upload-once device mirror; repeated calls return the same object.

        Without ``mesh`` this is the single-device :class:`DeviceGraph`.
        With a ``jax.sharding.Mesh`` it is the node-sharded
        :class:`ShardedDeviceGraph` over that mesh's ``axis`` — cached per
        (mesh, axis) for the ``SHARDED_CACHE_MAX`` most recent meshes (older
        residencies stay alive only while an executor still holds them).
        """
        if mesh is None:
            return self._device
        cache = self._sharded_devices
        key = (mesh, axis)
        if key in cache:
            cache[key] = cache.pop(key)            # refresh LRU recency
        else:
            cache[key] = ShardedDeviceGraph.from_graph(self, mesh, axis=axis)
            while len(cache) > self.SHARDED_CACHE_MAX:
                cache.pop(next(iter(cache)))       # evict least recently used
        return cache[key]

    # -- constructors ----------------------------------------------------------
    @staticmethod
    def from_edges(n: int, src: np.ndarray, dst: np.ndarray, *,
                   directed: bool = True, add_dangling_self_loops: bool = True,
                   dedup: bool = True, name: str = "graph") -> "Graph":
        """Build a graph, symmetrising if undirected, fixing dangling nodes.

        Dangling nodes (out-degree 0) get a self-loop so that the random-walk
        transition is total and forward push conserves mass — the same
        adjacency is used by the power-iteration oracle, so reproduction
        comparisons are apples-to-apples (DESIGN.md §3 deviation list).
        """
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        keep = src != dst  # drop self-loops; re-added below only for dangling
        src, dst = src[keep], dst[keep]
        if not directed:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        if dedup and src.size:
            key = src * n + dst
            _, idx = np.unique(key, return_index=True)
            src, dst = src[idx], dst[idx]
        if add_dangling_self_loops:
            deg = np.bincount(src, minlength=n)
            dangling = np.flatnonzero(deg == 0)
            if dangling.size:
                src = np.concatenate([src, dangling])
                dst = np.concatenate([dst, dangling])
        order = np.argsort(src, kind="stable")
        return Graph(n=n, edge_src=src[order].astype(np.int32),
                     edge_dst=dst[order].astype(np.int32),
                     directed=directed, name=name)

    def summary(self) -> dict:
        return {"name": self.name, "n": self.n, "m": self.m,
                "type": "Directed" if self.directed else "Undirected",
                "avg_out_degree": round(self.avg_out_degree, 2),
                "max_out_degree": self.max_out_degree}


@dataclass(frozen=True, eq=False)
class DeviceGraph:
    """Device-resident graph arrays for the fused FORA hot path.

    Holds jax arrays for the CSR walk view (edge_dst / out_offsets /
    out_degree) and the pull-form ELL push view (in_neighbors / in_mask /
    in_weights, weights = 1/deg_out(src)). Built exactly once per Graph via
    ``Graph.device()``; ``DeviceGraph.uploads`` counts constructions so tests
    and benchmarks can assert the upload-once contract.

    The push view is either the dense ``(n, k_max)`` table
    (``in_row_map is None``) or the sliced ``(n_virtual, W)`` table with its
    ``row_map`` (DESIGN.md §8). ``layout="auto"`` slices only when the dense
    table would waste >= ``AUTO_SLICE_RATIO`` x the sliced cells — power-law
    graphs slice, small near-uniform test graphs keep the dense fast path.
    """

    n: int
    m: int
    edge_src: Any
    edge_dst: Any
    out_offsets: Any
    out_degree: Any
    in_neighbors: Any
    in_mask: Any
    in_weights: Any
    in_row_map: Any = None     # (n_virtual,) int32 on device, or None (dense)
    ell_width: int = 0         # K of the resident table (dense or sliced)
    block_n: int = 256         # Pallas row tile for the push SpMM (autotuned)

    uploads: ClassVar[int] = 0
    AUTO_SLICE_RATIO: ClassVar[float] = 4.0

    @property
    def layout(self) -> str:
        return "dense" if self.in_row_map is None else "sliced"

    @property
    def ell_nbytes(self) -> int:
        """Resident bytes of the device push table (+ row_map when sliced)."""
        arrays = (self.in_neighbors, self.in_mask, self.in_weights,
                  self.in_row_map)
        return int(sum(a.size * a.dtype.itemsize
                       for a in arrays if a is not None))

    @classmethod
    def from_graph(cls, graph: Graph, *, layout: str = "auto",
                   width: int | None = None,
                   pad_multiple: int | None = None,
                   block_n: int | None = None) -> "DeviceGraph":
        import jax.numpy as jnp  # deferred: graph.py stays importable sans jax

        lay = _resolve_push_layout(graph, layout, width, pad_multiple,
                                   block_n=block_n)
        DeviceGraph.uploads += 1
        return cls(
            n=graph.n, m=graph.m,
            edge_src=jnp.asarray(graph.edge_src),
            edge_dst=jnp.asarray(graph.edge_dst),
            out_offsets=jnp.asarray(graph.out_offsets),
            out_degree=jnp.asarray(graph.out_degree),
            in_neighbors=jnp.asarray(lay.neighbors),
            in_mask=jnp.asarray(lay.mask),
            in_weights=jnp.asarray(lay.weights),
            in_row_map=None if lay.row_map is None else jnp.asarray(lay.row_map),
            ell_width=lay.width,
            block_n=lay.block_n,
        )


class _PushLayout(NamedTuple):
    """Host-side pull table + the dense/sliced decision — the single layout
    policy shared by the single-device and sharded residencies."""

    layout: str             # "dense" | "sliced"
    neighbors: np.ndarray   # (rows, K) int32 — real rows (dense) or virtual
    mask: np.ndarray        # (rows, K) bool
    weights: np.ndarray     # (rows, K) f32
    row_map: np.ndarray | None   # (rows,) int32 ascending, None when dense
    width: int              # K of the resident table
    block_n: int = 256      # Pallas row tile (autotuned, numerics-neutral)


def _tuned_push_config(graph: Graph, layout: str):
    """Tuning-cache lookup for this graph's shape bucket (DESIGN.md §15).

    Called exclusively at residency-build time — host-side, before the
    arrays go to the device — so an active cache never adds a lookup (or
    any host sync) to the fused serving loop. Returns None when the cache
    is cold or jax is unavailable."""
    try:
        from ..kernels import autotune
    except Exception:          # noqa: BLE001 — layout must work sans jax
        return None
    cache = autotune.get_cache()
    if cache is None:
        return None
    return cache.lookup(autotune.current_backend(), layout,
                        autotune.shape_bucket(graph.n, graph.m))


def _resolve_push_layout(graph: Graph, layout: str, width: int | None,
                         pad_multiple: int | None,
                         block_n: int | None = None) -> _PushLayout:
    if layout not in ("auto", "dense", "sliced"):
        raise ValueError(f"layout must be auto|dense|sliced, got {layout!r}")
    pinned_pm = pad_multiple is not None
    pinned_w = width is not None
    if pad_multiple is None:
        pad_multiple = _default_pad_multiple()
    if layout == "auto":
        sl_width, sliced_cells = graph._sliced_width_cells(pad_multiple)
        dense_cells = graph.n * _round_up(
            graph.max_in_degree if graph.m else 1, pad_multiple)
        layout = "sliced" if dense_cells >= DeviceGraph.AUTO_SLICE_RATIO * \
            max(1, sliced_cells) else "dense"
        if width is None:
            width = sl_width              # reuse the scan's answer
    # measured config, if any, refines whatever the caller did NOT pin;
    # a cold cache leaves every value — and thus the residency — bit-identical
    tuned = _tuned_push_config(graph, layout)
    if tuned is not None:
        if block_n is None:
            block_n = tuned.block_n
        if layout == "sliced" and not pinned_w and not pinned_pm \
                and tuned.width is not None:
            width = tuned.width
            if tuned.pad_multiple is not None:
                pad_multiple = tuned.pad_multiple
    if block_n is None:
        block_n = 256
    if layout == "sliced":
        sl = graph.ell_in_sliced(width=width, pad_multiple=pad_multiple)
        return _PushLayout(layout="sliced", neighbors=sl.neighbors,
                           mask=sl.mask, weights=sl.weights,
                           row_map=sl.row_map, width=sl.width,
                           block_n=block_n)
    nbr, mask, weights = graph.ell_in(pad_multiple=pad_multiple)
    return _PushLayout(layout="dense", neighbors=nbr, mask=mask,
                       weights=weights, row_map=None, width=int(nbr.shape[1]),
                       block_n=block_n)


@dataclass(frozen=True, eq=False)
class ShardedDeviceGraph:
    """Node-sharded device residency for multi-chip fused FORA (DESIGN.md §9).

    The pull-form push table is row-sharded across ``mesh`` along ``axis``:

    * **dense** tables by destination row — each shard computes its own
      (B, rows_local) output block and the blocks are reassembled with one
      tiled all-gather per sweep;
    * **sliced** tables by *virtual* row — each shard folds its local slice
      partials onto the full (B, n) frame through its ``row_map`` segment
      sum, and the partial frames are combined with one ``psum`` all-reduce.

    The CSR walk arrays (edge_dst / out_offsets / out_degree) are
    **replicated** so ``residual_walks`` stays shard-local: the walk lane
    budget is split across shards (global lane ids keep the estimator's
    weights exact) and endpoint masses are psum-combined. Gather indices of
    the push table are global node ids, so the kernel body is untouched —
    only the row axis is partitioned.

    Built via ``Graph.device(mesh=...)`` (upload-once per (graph, mesh));
    ``uploads`` counts constructions like :class:`DeviceGraph`'s.
    """

    n: int
    m: int
    mesh: Any                  # jax.sharding.Mesh
    axis: str                  # mesh axis the rows are sharded over
    num_shards: int
    rows_per_shard: int        # local (virtual) row count (row-padded)
    edge_dst: Any              # replicated CSR walk arrays
    out_offsets: Any
    out_degree: Any
    in_neighbors: Any          # (rows_pad, K), P(axis, None)
    in_mask: Any
    in_weights: Any
    in_row_map: Any = None     # (rows_pad,) int32, P(axis), or None (dense)
    ell_width: int = 0
    block_n: int = 256         # Pallas row tile for the push SpMM (autotuned)

    uploads: ClassVar[int] = 0

    @property
    def layout(self) -> str:
        return "dense" if self.in_row_map is None else "sliced"

    @property
    def ell_nbytes(self) -> int:
        """Resident bytes of the sharded push table summed over all shards."""
        arrays = (self.in_neighbors, self.in_mask, self.in_weights,
                  self.in_row_map)
        return int(sum(a.size * a.dtype.itemsize
                       for a in arrays if a is not None))

    def replicate(self, x: Any) -> Any:
        """Stage a broadcast input (query sources, PRNG key) replicated over
        the mesh — the caller-side transfer that keeps the measured fused
        region transfer-free, mirroring the single-device contract where the
        caller uploads sources before the clock starts."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(self.mesh, P()))

    @classmethod
    def from_graph(cls, graph: Graph, mesh: Any, *, axis: str = "shard",
                   layout: str = "auto", width: int | None = None,
                   pad_multiple: int | None = None,
                   block_n: int | None = None) -> "ShardedDeviceGraph":
        import jax  # deferred: graph.py stays importable sans jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r} "
                             f"(axes: {mesh.axis_names})")
        k = int(mesh.shape[axis])
        lay = _resolve_push_layout(graph, layout, width, pad_multiple,
                                   block_n=block_n)
        nbr, mask, weights = lay.neighbors, lay.mask, lay.weights
        row_map = lay.row_map
        rows = int(nbr.shape[0])
        rows_pad = -(-rows // k) * k
        if rows_pad != rows:
            pad = rows_pad - rows
            nbr = np.pad(nbr, ((0, pad), (0, 0)))
            mask = np.pad(mask, ((0, pad), (0, 0)))
            weights = np.pad(weights, ((0, pad), (0, 0)))
            if row_map is not None:
                # padding rows carry no mass (mask False -> weight 0); repeat
                # the last real id so every local segment stays ascending
                row_map = np.concatenate(
                    [row_map, np.full(pad, row_map[-1], np.int32)])
        row_sh = NamedSharding(mesh, P(axis, None))
        repl = NamedSharding(mesh, P())
        ShardedDeviceGraph.uploads += 1
        return cls(
            n=graph.n, m=graph.m, mesh=mesh, axis=axis, num_shards=k,
            rows_per_shard=rows_pad // k,
            edge_dst=jax.device_put(graph.edge_dst, repl),
            out_offsets=jax.device_put(graph.out_offsets, repl),
            out_degree=jax.device_put(graph.out_degree, repl),
            in_neighbors=jax.device_put(nbr, row_sh),
            in_mask=jax.device_put(mask, row_sh),
            in_weights=jax.device_put(weights.astype(np.float32), row_sh),
            in_row_map=None if row_map is None else jax.device_put(
                row_map, NamedSharding(mesh, P(axis))),
            ell_width=lay.width,
            block_n=lay.block_n,
        )
