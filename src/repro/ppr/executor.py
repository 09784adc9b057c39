"""Bridges the PPR engine to the D&A core (the paper's experiment plumbing).

``ForaExecutor`` satisfies :data:`repro.core.slots.Executor`: given query ids
it runs each query through JAX FORA and returns **measured** per-query wall
times. Queries are (source vertex) ids; a query-id -> source mapping comes
from the workload. One query per call reproduces the paper's one-query-per-
core model; ``block_size > 1`` is the beyond-paper vectorised mode where a
whole slot executes as one batched device step and the block time is shared.

By default the executor runs the **fused device-resident hot path**
(DESIGN.md §7): the graph is uploaded once as a :class:`DeviceGraph`, the
static walk lane count is calibrated once per workload from a probe push,
and every measured query is a single jitted ``fora_fused`` call whose only
host sync is the final readout. ``fused=False`` keeps the legacy multi-call
``fora()`` path (host round-trips between push and walk) for comparison —
``benchmarks/fora_hot_path.py`` measures both.

``devices=k`` makes one *slot* a mesh of k chips (DESIGN.md §9): the graph
residency becomes a node-sharded :class:`ShardedDeviceGraph` and the same
fused call runs under ``shard_map`` — push rows and walk lanes split across
the mesh, so the D&A allocator's "k cores" grant real parallel hardware.
"""

from __future__ import annotations

import math
import time
from collections.abc import Sequence
from dataclasses import dataclass, field, replace

import jax
import numpy as np

from ..core.estimator import RuntimeStats
from .fora import (ForaParams, _pow2_ceil_host, default_walk_budget, fora,
                   fora_fused)
from .forward_push import forward_push_np
from .graph import DeviceGraph, Graph, ShardedDeviceGraph
from .random_walk import _BULK_RNG_ELEMS, walk_length_for_tail

# Reference batch size for the pinned bulk-RNG decision: the bulk-vs-per-step
# strategies draw DIFFERENT streams (random_walk.py), and the legacy per-call
# heuristic counts the actual batch B — so the same query's walks would change
# bits with chunk size. The executor pins the decision at a fixed reference
# batch instead, making every fused call (any chunk size, any engine lane
# count) draw the same per-query stream.
_REF_BLOCK = 64

# Fused-batch quantum for the bit-parity contract. XLA's SpMM codegen
# reduces a row with different bits depending on which loop the row lands
# in — the vectorised main loop covers rows in full 8-wide groups, the
# scalar remainder handles the B mod 8 tail (and the degenerate B=1 batch
# is different again). Rows inside full vector groups are bit-identical at
# EVERY batch size; tail rows are not. So both parity-contract paths
# quantise the batch to a multiple of this width: ``answer_chunk`` pads by
# cycling the chunk's own qids (duplicate qid -> same per-query stream ->
# identical row, free copies), and the engine rounds its lane-pool row
# count up. Every real row then always runs in a full vector group and its
# bits never depend on batch composition.
_PAR_BATCH_QUANTUM = 8


def _pad_batch(size: int) -> int:
    """Round a fused batch size up to the parity quantum."""
    return -(-size // _PAR_BATCH_QUANTUM) * _PAR_BATCH_QUANTUM


@dataclass
class PprWorkload:
    """X queries = X source vertices, deterministic per seed."""

    graph: Graph
    num_queries: int
    seed: int = 0
    sources: np.ndarray = field(init=False)

    def __post_init__(self) -> None:
        rng = np.random.default_rng(self.seed)
        self.sources = rng.integers(0, self.graph.n, size=self.num_queries,
                                    dtype=np.int64)

    def source_of(self, qid: int) -> int:
        """Source vertex of query ``qid``. Out-of-range ids raise — the old
        silent ``qid % num_queries`` wraparound masked slot-plan indexing
        bugs (a plan cell pointing past the workload produced a *valid*
        source and a wrong answer instead of an error)."""
        if not 0 <= qid < self.num_queries:
            raise IndexError(
                f"query id {qid} out of range [0, {self.num_queries})")
        return int(self.sources[qid])


@dataclass
class ForaExecutor:
    """Measured executor: wall-clocks JAX FORA per query (paper mode) or per
    block (vectorised mode). First call triggers jit compilation; a warmup
    run keeps compile time out of the sampled statistics, mirroring the
    paper's steady-state Xeon measurements."""

    workload: PprWorkload
    params: ForaParams = field(default_factory=ForaParams)
    block_size: int = 1            # 1 = paper-faithful
    fused: bool = True             # device-resident single-jit hot path
    walk_safety: float = 1.0       # calibration headroom on the probe r_sum
    ell_layout: str = "auto"       # auto|dense|sliced push table (DESIGN §8)
    devices: int = 1               # >1: a slot is a mesh of k chips (DESIGN §9)
    index_budget: int = 0          # >0: pre-draw a WalkIndex of this many
    #                                lanes per node and serve covered walk
    #                                lanes from it (DESIGN.md §11)
    index_seed: int = 0
    query_seeded: bool = True      # per-query walk keys fold_in(base, qid):
    #                                answers are a function of the query id
    #                                alone, independent of chunk composition
    #                                (the engine's bit-parity contract)
    adaptive_budget: bool = False  # recalibrate the walk budget per block
    #                                from observed residual mass (EWMA)
    budget_ewma: float = 0.5       # smoothing for the observed r_max
    walk_index: "object | None" = field(default=None, init=False, repr=False)
    _warmed: bool = field(default=False, init=False)
    calls: int = field(default=0, init=False)
    _device_graph: "DeviceGraph | ShardedDeviceGraph | None" = field(
        default=None, init=False, repr=False)
    _num_walks: int | None = field(default=None, init=False)
    _warmed_sizes: set = field(default_factory=set, init=False)
    _bulk_rng: bool | None = field(default=None, init=False)
    _obs_rmax: float | None = field(default=None, init=False)

    def __post_init__(self) -> None:
        if self.devices < 1:
            raise ValueError("devices must be >= 1")
        if self.devices > 1 and not self.fused:
            raise ValueError("devices>1 (node-sharded slots) requires the "
                             "fused hot path; the legacy fora() path is "
                             "single-device only")
        if self.index_budget < 0:
            raise ValueError("index_budget must be >= 0")
        if self.index_budget and (not self.fused or self.devices > 1):
            raise ValueError("index_budget requires the fused hot path on a "
                             "single-device slot (the sharded residency "
                             "draws walk lanes per shard)")

    # -- helpers ---------------------------------------------------------------
    def _block_sources(self, qids: Sequence[int]) -> np.ndarray:
        return np.array([self.workload.source_of(q) for q in qids],
                        dtype=np.int64)

    def _build_mesh(self):
        """A 1-D ("shard",) mesh over the first ``devices`` jax devices —
        the slot's hardware slice (cores = devices x lanes, DESIGN.md §9)."""
        from jax.sharding import Mesh

        devs = jax.devices()
        if self.devices > len(devs):
            raise ValueError(f"devices={self.devices} requested but only "
                             f"{len(devs)} present")
        return Mesh(np.array(devs[:self.devices]), ("shard",))

    def _base_key(self) -> jax.Array:
        """Base PRNG key for query-seeded walk streams: per-query keys are
        fold_in(base, qid), so they depend on the workload seed and the
        query id alone — never on chunk composition or call order."""
        return jax.random.PRNGKey(self.workload.seed)

    def _run_block(self, sources: np.ndarray, seed: int,
                   qids: Sequence[int] | None = None) -> None:
        if self.fused:
            # host spans on the profiler's clock: fora.call holds
            # fora.stage (here and inside fora_fused), fora.enqueue and
            # fora.wait
            with jax.profiler.TraceAnnotation(
                    "fora.call", qid=int(qids[0]) if qids else seed,
                    size=len(sources)):
                with jax.profiler.TraceAnnotation("fora.stage"):
                    if self.query_seeded and qids is not None:
                        key = self._base_key()
                        qseeds = np.ascontiguousarray(
                            np.asarray(qids, np.int32))
                    else:
                        key = jax.random.PRNGKey(seed)
                        qseeds = None
                # the module's name, resolved at call time, so that a wrapper
                # put there sees every call
                res = fora_fused(self._device_graph, sources, self.params,
                                 key, num_walks=self._num_walks,
                                 index=self.walk_index, query_seeds=qseeds,
                                 bulk_rng=self._bulk_rng)
                with jax.profiler.TraceAnnotation("fora.wait"):
                    res.pi.block_until_ready()  # the block's single host sync
        else:
            key = jax.random.PRNGKey(seed)
            res = fora(self.workload.graph, sources, self.params, key)
            pi = res.pi
            if hasattr(pi, "block_until_ready"):
                pi.block_until_ready()

    def _calibration_qids(self, size: int = 8) -> list[int]:
        """Seeded random probe block WITHOUT replacement. The first-``size``
        ids would bias the calibrated budget whenever query cost correlates
        with id order (sources sorted by degree, say) — the same first-s bias
        PR 2 removed from the ``dna``/``dna_real`` sample draw. Deterministic
        per workload seed so calibration is reproducible, but on a stream
        distinct from the one that drew the workload's sources (the [seed]
        stream) so the probe selection is not coupled to the realized
        source vertices."""
        nq = self.workload.num_queries
        rng = np.random.default_rng([self.workload.seed, 1])
        return np.sort(rng.choice(nq, size=min(size, nq),
                                  replace=False)).tolist()

    def _calibrate_walk_budget(self) -> int:
        """Pick ONE static walk lane count for the whole workload: push a
        probe block (warmup only — this sync never lands in measured time),
        read the worst residual mass, and budget pow2(ceil(r_max * omega))
        with ``walk_safety`` headroom. Rows whose true budget exceeds the
        calibrated lanes are still unbiased (weight r_sum/W), merely a bit
        noisier — the same trade the seed path's batch-max budget made."""
        rp = self.params.resolve(self.workload.graph)
        sources = self._block_sources(self._calibration_qids())
        push = forward_push_np(self.workload.graph, sources,
                               alpha=rp.alpha, rmax=rp.rmax)
        r_max = float(np.asarray(push.r.sum(axis=1)).max())
        need = max(1, math.ceil(r_max * rp.omega * self.walk_safety))
        return min(_pow2_ceil_host(need), default_walk_budget(rp))

    def _probe_qids(self) -> list[int]:
        nq = self.workload.num_queries
        probes = {0, 1, nq // 2, nq - 1}
        return sorted(q for q in probes if 0 <= q < nq)

    def warmup(self) -> None:
        """Pre-compile every executable variant that measured queries can
        hit: distinct sources can land on different (pow2-quantised) walk
        budgets on the legacy path, and a compile spike inside a measured
        query would contaminate the D&A statistics the way no real
        steady-state deployment is contaminated. The fused path compiles
        exactly one executable (static budget), but probing still warms the
        dispatch path and the DeviceGraph upload."""
        if self._warmed:
            return
        with jax.profiler.TraceAnnotation("fora.warmup"):
            self._warmup()
        self._warmed = True

    def _warmup(self) -> None:
        if self.fused:
            with jax.profiler.TraceAnnotation("fora.upload"):
                self._upload()
            if self._num_walks is None:
                with jax.profiler.TraceAnnotation("fora.calibrate"):
                    self._num_walks = self._calibrate_walk_budget()
            if self.index_budget and self.walk_index is None:
                # pre-draw the walk endpoints once per workload (FORA+,
                # DESIGN.md §11) — build cost is warmup, never measured time
                from ..index import WalkIndex

                rp = self.params.resolve(self.workload.graph)
                self.walk_index = WalkIndex.build(
                    self._device_graph, width=self.index_budget,
                    alpha=rp.alpha, walk_tail=rp.walk_tail,
                    seed=self.index_seed)
        if self.fused and self._num_walks is not None:
            # pin the bulk-RNG strategy at the reference batch so every
            # chunk size draws the same per-query stream (see _REF_BLOCK)
            steps = walk_length_for_tail(
                self.params.alpha, self.params.walk_tail)
            self._bulk_rng = (_REF_BLOCK * steps * self._num_walks
                              <= _BULK_RNG_ELEMS)
        nq = self.workload.num_queries
        for qid in self._probe_qids():
            if self.block_size <= 1:
                probe = [qid]
            else:
                # clamp the probe window inside the workload (source_of no
                # longer wraps out-of-range ids)
                size = min(self.block_size, nq)
                start = min(qid, nq - size)
                probe = list(range(start, start + size))
            with jax.profiler.TraceAnnotation("fora.probe", qid=probe[0]):
                self._run_block(self._block_sources(probe), seed=qid,
                                qids=probe)
            self._warmed_sizes.add(len(probe))

    def _upload(self) -> None:
        """The graph's device residency, once per executor: "auto" reuses
        the graph's cached upload-once mirror; a forced layout builds its
        own device copy for this executor."""
        if self._device_graph is not None:
            return
        mesh = self._build_mesh() if self.devices > 1 else None
        if self.ell_layout == "auto":
            self._device_graph = self.workload.graph.device(mesh=mesh)
        elif mesh is not None:
            self._device_graph = ShardedDeviceGraph.from_graph(
                self.workload.graph, mesh, layout=self.ell_layout)
        else:
            self._device_graph = DeviceGraph.from_graph(
                self.workload.graph, layout=self.ell_layout)

    def _warm_size(self, size: int) -> None:
        """Compile an executable variant for an unseen batch size (e.g. the
        remainder chunk of a query list) OUTSIDE the measured region."""
        if size in self._warmed_sizes:
            return
        nq = self.workload.num_queries
        qids = [i % nq for i in range(size)]   # cycle: size may exceed nq
        self._run_block(self._block_sources(qids), seed=0, qids=qids)
        self._warmed_sizes.add(size)

    def run_chunk(self, query_ids: Sequence[int], *,
                  seed: int | None = None) -> RuntimeStats:
        """One chunk of queries as a SINGLE batched device step — the
        resumable unit the serving runtime feeds a slot at a time
        (DESIGN.md §10), yielding control back to the event loop between
        device steps.

        The zero-host-sync-per-block contract survives chunking: staging the
        chunk's sources and PRNG key is wrapped in an explicit
        ``transfer_guard("allow")`` scope (the block's sanctioned upload), so
        the fused call itself still runs under whatever ambient guard the
        caller holds — pinned by a ``transfer_guard("disallow")`` test — and
        the trailing ``block_until_ready`` is the chunk's single sync.
        Compile spikes for unseen chunk sizes are absorbed outside the
        measured region (``_warm_size``), like the block path.
        """
        ids = list(query_ids)
        if not ids:
            raise ValueError("empty query chunk")
        self.warmup()
        self._recalibrate_block()
        self._warm_size(len(ids))
        if seed is None:
            seed = ids[0]
        if not self.fused:
            src = self._block_sources(ids)
            t0 = time.perf_counter()
            self._run_block(src, seed=seed)
            dt = time.perf_counter() - t0
        else:
            with jax.transfer_guard("allow"):
                src = jax.device_put(
                    np.ascontiguousarray(self._block_sources(ids),
                                         dtype=np.int32))
                if self.query_seeded:
                    key = self._base_key()
                    qseeds = jax.device_put(
                        np.ascontiguousarray(np.asarray(ids, np.int32)))
                else:
                    key = jax.random.PRNGKey(seed)
                    qseeds = None
            t0 = time.perf_counter()
            res = fora_fused(self._device_graph, src, self.params, key,
                             num_walks=self._num_walks,
                             index=self.walk_index, query_seeds=qseeds,
                             bulk_rng=self._bulk_rng)
            res.pi.block_until_ready()          # the chunk's single sync
            dt = time.perf_counter() - t0
            if self.adaptive_budget:
                # observe the block's worst residual mass at the harvest
                # boundary (pi is already synced; this readback stays out
                # of any ambient transfer guard the steady-state loop holds
                # because adaptive mode is opt-in)
                self.observe_residual_mass(
                    float(np.asarray(res.residual_mass).max()))
        self.calls += 1
        return RuntimeStats(np.full(len(ids), dt / len(ids)))

    def observe_residual_mass(self, r_max: float) -> None:
        """Feed an observed per-block max residual mass into the adaptive
        walk-budget EWMA (satellite of the engine PR — the PR-1 follow-up):
        the next block / engine insertion recalibrates against it."""
        if self._obs_rmax is None:
            self._obs_rmax = float(r_max)
        else:
            b = self.budget_ewma
            self._obs_rmax = (1.0 - b) * self._obs_rmax + b * float(r_max)

    def _recalibrate_block(self) -> None:
        """Per-block adaptive walk-budget re-calibration: shrink (or grow)
        the static walk lane count to pow2(ceil(ewma_rmax * omega * safety)),
        capped by the worst-case default. Opt-in (``adaptive_budget``); the
        pow2 quantisation plus the EWMA keeps executable churn rare, and any
        recompile lands in ``_warm_size`` outside the measured region."""
        if (not self.adaptive_budget or not self.fused
                or self._obs_rmax is None or self._num_walks is None):
            return
        rp = self.params.resolve(self.workload.graph)
        need = max(1, math.ceil(self._obs_rmax * rp.omega * self.walk_safety))
        target = min(_pow2_ceil_host(need), default_walk_budget(rp))
        if target != self._num_walks:
            self._num_walks = target
            self._bulk_rng = (_REF_BLOCK
                              * walk_length_for_tail(self.params.alpha,
                                                     self.params.walk_tail)
                              * target <= _BULK_RNG_ELEMS)
            self._warmed_sizes.clear()   # stale executables: re-warm lazily

    def current_walk_budget(self) -> int | None:
        """The calibrated static walk lane count (post warmup; the engine
        reads this at insertion so adaptive re-calibration feeds lane
        budgets too)."""
        return self._num_walks

    def answer_chunk(self, query_ids: Sequence[int]) -> np.ndarray:
        """PPR rows for one chunk via the chunked fused path — the
        bit-parity reference the engine is tested against. Requires
        ``query_seeded`` (otherwise chunk answers depend on composition and
        no cross-batch parity exists)."""
        ids = list(query_ids)
        return np.asarray(self.chunk_result(ids).pi)[:len(ids)]

    def chunk_result(self, query_ids: Sequence[int]):
        """The device-resident :class:`~repro.ppr.fora.FusedForaResult`
        behind :meth:`answer_chunk` (residual mass and walk lanes too).
        Its batch is padded to the parity quantum: rows past
        ``len(query_ids)`` repeat the chunk's own queries."""
        if not (self.fused and self.query_seeded):
            raise ValueError("answer_chunk needs the fused query-seeded path")
        ids = list(query_ids)
        if not ids:
            raise ValueError("empty query chunk")
        # quantise the batch into full vector groups by cycling the chunk's
        # own qids (see _PAR_BATCH_QUANTUM): duplicate qids draw the same
        # stream, so the extra rows are free copies
        pad_to = _pad_batch(len(ids))
        run_ids = (ids * pad_to)[:pad_to]
        self.warmup()
        self._recalibrate_block()
        self._warm_size(len(run_ids))
        with jax.transfer_guard("allow"):
            src = jax.device_put(
                np.ascontiguousarray(self._block_sources(run_ids),
                                     dtype=np.int32))
            qseeds = jax.device_put(
                np.ascontiguousarray(np.asarray(run_ids, np.int32)))
        return fora_fused(self._device_graph, src, self.params,
                          self._base_key(), num_walks=self._num_walks,
                          index=self.walk_index, query_seeds=qseeds,
                          bulk_rng=self._bulk_rng)

    def degrade(self, factor: float) -> None:
        """DCAF-style graceful degradation for the *remaining* queries: scale
        the per-query budget down by raising epsilon (coarser FORA guarantee
        -> higher rmax, fewer pushes and walks) and capping the calibrated
        walk-lane budget by ``factor`` (pow2-floored so the executable stays
        cacheable). The next call warms the degraded executable outside the
        measured region; answers stay unbiased, only noisier."""
        if not 0.0 < factor < 1.0:
            raise ValueError(f"factor must be in (0,1), got {factor}")
        self.params = replace(self.params,
                              epsilon=self.params.epsilon / factor)
        if self._num_walks is not None and self._num_walks > 1:
            capped = max(1, int(self._num_walks * factor))
            self._num_walks = 1 << (capped.bit_length() - 1)   # pow2 floor
        # params changed -> every compiled variant is stale; re-warm lazily
        # (the walk index survives: its endpoints depend only on alpha and
        # the truncation length, neither of which degrade touches)
        self._warmed = False
        self._warmed_sizes.clear()

    @property
    def index_coverage(self) -> float:
        """Fraction of the calibrated walk budget the attached walk index
        serves (0.0 without an index / before warmup) — the per-query index
        coverage the cache-aware cost model consumes (DESIGN.md §11)."""
        if self.walk_index is None or self._num_walks is None:
            return 0.0
        return self.walk_index.coverage(self._num_walks)

    def __call__(self, query_ids: Sequence[int]) -> RuntimeStats:
        ids = list(query_ids)
        if not ids:
            raise ValueError("empty query block")
        self.warmup()
        times = np.empty(len(ids), dtype=np.float64)
        if self.block_size <= 1:
            for i, qid in enumerate(ids):
                src = self._block_sources([qid])
                t0 = time.perf_counter()
                self._run_block(src, seed=qid, qids=[qid])
                times[i] = time.perf_counter() - t0
                self.calls += 1
        else:
            tail = len(ids) % self.block_size
            if tail:
                self._warm_size(tail)   # compile spike stays out of the clock
            for lo in range(0, len(ids), self.block_size):
                chunk = ids[lo: lo + self.block_size]
                src = self._block_sources(chunk)
                t0 = time.perf_counter()
                self._run_block(src, seed=chunk[0], qids=chunk)
                dt = time.perf_counter() - t0
                times[lo: lo + len(chunk)] = dt / len(chunk)
                self.calls += 1
        return RuntimeStats(times)
