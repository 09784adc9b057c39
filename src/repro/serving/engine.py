"""Continuous-batching query engine: a persistent lane pool with mid-step
insert / evict (DESIGN.md §14).

D&A's slot model (Alg. 2) grants a job its lanes for a whole slot, so lanes
go dark whenever a job's residual query set shrinks below its grant. The
engine decouples lane occupancy from job boundaries — the JetStream /
continuous-batching shape: ONE persistent fused device loop runs over a
fixed pool of L lanes, individual queries from *any* admitted job are
inserted into free lanes mid-stream, and a lane is evicted the moment its
query converges. Two layers share the lane-pool model:

``QueryEngine`` — the real device engine. Lane state is five device
arrays (``pi``/``r`` dense (L, n) rows, per-lane walk keys, ``active`` and
``walked`` masks). Each ``step()`` is one jitted call that

  1. runs a bounded number of frontier sweeps over ALL lanes — the sweep is
     bit-for-bit :func:`repro.ppr.forward_push.forward_push`'s while-loop
     body, and a converged (or idle, or awaiting-harvest) lane's frontier is
     empty, so extra sweeps are exact arithmetic identities: converged lanes
     contribute zero work;
  2. detects per-lane push convergence on device;
  3. runs the walk phase for lanes that just converged — each lane's FULL
     pow2-quantised walk budget in one step (a lane's weighted
     ``segment_sum`` reduction cannot be split across steps bit-safely),
     masked to zero contribution for every other lane.

Nothing in ``step()`` touches the host: occupancy/convergence readback
happens once per ``harvest()`` at the boundary (the transfer-guard tests
and the dnalint host-sync rule pin this). Because per-query walk keys are
``fold_in(base, qid)`` (:class:`~repro.ppr.executor.ForaExecutor`'s
query-seeded contract) and the bulk-RNG decision is pinned, a query's
answer is bit-identical whether it ran through the engine — in any lane,
under any interleaving — or through the chunked ``run_chunk`` path.

``SimLaneEngine`` (re-exported from :mod:`repro.serving.lanes`, which the
jax-free runtime imports directly) — the virtual-time twin the serving
runtime's engine mode schedules against (``ServingConfig.engine``): the
same lane pool and EDF ready queue, with per-query durations drawn from
the job's executor at admission. Deterministic and WAL-replayable;
`benchmarks/serving_sim.py` drives it for the queries/sec-at-fixed-SLA
headline.

The engine runs live walk lanes only; ``WalkIndex``/``ResultCache`` hits
keep bypassing insertion entirely at the runtime layer (DESIGN.md §11).
"""

from __future__ import annotations

import heapq
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ppr.executor import _pad_batch
from ..ppr.forward_push import forward_push
from ..ppr.random_walk import _BULK_RNG_ELEMS, per_query, residual_walks
from ..ppr.random_walk import walk_length_for_tail
from .lanes import LaneTask, SimLaneEngine

__all__ = ["HarvestedQuery", "LaneTask", "QueryEngine", "SimLaneEngine"]


# ---------------------------------------------------------------------------
# device engine


class HarvestedQuery(NamedTuple):
    """One converged lane read back at the harvest boundary."""

    qid: int
    lane: int
    pi: np.ndarray             # (n,) PPR row, bit-identical to the chunked path
    walks_effective: int
    residual_mass: float


def _engine_step_impl(in_neighbors, in_mask, in_weights, in_row_map,
                      edge_dst, out_offsets, out_degree,
                      pi, r, keys, active, walked, *,
                      alpha: float, rmax: float, omega: float, n: int,
                      num_walks: int, num_steps: int, sweeps: int,
                      bulk_rng: bool, force: str | None = None):
    """One persistent-loop step over the whole lane pool — ONE executable,
    zero host syncs. The push sweep is exactly forward_push's while-loop
    body (same op order, same fused-threshold SpMM), so a lane that
    converges after any number of engine steps holds the same (pi, r) bits
    the chunked path's while_loop fixed point holds; lanes whose frontier
    is empty (idle / converged / awaiting harvest) pass through every sweep
    unchanged — zero logical work. Lanes that just converged run their full
    masked walk phase in this same step."""
    deg = out_degree.astype(jnp.float32)
    deg_safe = jnp.maximum(deg, 1.0)
    threshold = rmax * deg_safe                      # (n,)
    # Bounded resume of forward_push's OWN while_loop (pi0 carries the
    # reserve accumulated by earlier steps). Reusing the same compiled loop
    # body — not an unrolled copy of it — is what makes the chain of engine
    # steps bit-identical to one uninterrupted chunked-path push: XLA fuses
    # an unrolled sweep sequence differently than the while_loop body.
    push = forward_push(in_neighbors, in_mask, in_weights, out_degree, r,
                        alpha=alpha, rmax=rmax, n=n, max_iters=sweeps,
                        row_map=in_row_map, force=force, pi0=pi)
    pi, r = push.pi, push.r
    converged = jnp.logical_not(jnp.any(r > threshold[None, :], axis=1))
    walk_now = active & converged & jnp.logical_not(walked)
    # pow2 budget quantisation, identical to _fora_fused_impl
    r_sum = r.sum(axis=1)                            # (L,)
    need = jnp.maximum(jnp.ceil(r_sum * omega), 1.0)
    w_eff = jnp.exp2(jnp.ceil(jnp.log2(need)))
    w_eff = jnp.clip(w_eff, 1.0, float(num_walks)).astype(jnp.int32)
    # fixed-shape walk phase over every lane (SPMD cannot skip rows); only
    # lanes walking *now* accumulate their endpoint mass — the mask is the
    # zero-work contract for everyone else
    endpoint = per_query(lambda rr, k, a: residual_walks(
        edge_dst, out_offsets, out_degree, rr, k, alpha=alpha, n=n,
        num_walks=num_walks, num_steps=num_steps, active_walks=a,
        bulk_rng=bulk_rng), r, keys, w_eff).mass
    pi = pi + jnp.where(walk_now[:, None], endpoint, 0.0)
    walked = jnp.logical_or(walked, walk_now)
    return pi, r, walked, w_eff, r_sum


_ENGINE_STEP_STATICS = ("alpha", "rmax", "omega", "n", "num_walks",
                        "num_steps", "sweeps", "bulk_rng", "force")
_engine_step = jax.jit(_engine_step_impl,
                       static_argnames=_ENGINE_STEP_STATICS)


@jax.jit
def _engine_insert(pi, r, keys, active, walked, lane, source, qkey):
    """Stage one query into a lane: one-hot residual, zero reserve, the
    query's own walk key. Lane/source are traced scalars — no recompiles."""
    row = jnp.zeros((r.shape[1],), r.dtype).at[source].set(1.0)
    return (pi.at[lane].set(0.0), r.at[lane].set(row),
            keys.at[lane].set(qkey), active.at[lane].set(True),
            walked.at[lane].set(False))


@jax.jit
def _engine_release(pi, r, active, walked, mask):
    """Evict harvested lanes: zero their rows (an emptied lane's frontier
    stays empty — identity under future sweeps) and clear the masks."""
    pi = jnp.where(mask[:, None], 0.0, pi)
    r = jnp.where(mask[:, None], 0.0, r)
    return pi, r, active & ~mask, walked & ~mask


@jax.jit
def _engine_qkey(base, qid):
    return jax.random.fold_in(base, qid)


class QueryEngine:
    """Persistent continuous-batching engine over a fixed device lane pool.

    ``insert(qid, lane=None)`` stages a query into a free lane (host->device
    staging under an explicit ``transfer_guard("allow")`` scope, like
    ``run_chunk``'s), ``step()`` advances every lane with zero host syncs,
    ``harvest()`` is the single readback boundary: it returns converged
    queries and frees their lanes. Single-device fused executors only; the
    walk budget (and the pinned bulk-RNG decision) is read from the
    executor at insertion so per-block adaptive re-calibration feeds lane
    insertion too.
    """

    def __init__(self, executor, lanes: int, *, sweeps: int = 4):
        if lanes < 1:
            raise ValueError("engine needs a lane pool of >= 1")
        if not executor.fused or executor.devices > 1:
            raise ValueError("QueryEngine requires a single-device fused "
                             "ForaExecutor")
        if not executor.query_seeded:
            raise ValueError("QueryEngine requires query-seeded walk keys "
                             "(ForaExecutor.query_seeded)")
        if executor.index_budget:
            raise ValueError("walk-index lanes are a chunked-path "
                             "acceleration; index/cache hits bypass engine "
                             "insertion instead (DESIGN.md §14)")
        if sweeps < 1:
            raise ValueError("sweeps must be >= 1")
        executor.warmup()
        self.executor = executor
        self.lanes = int(lanes)
        self.sweeps = int(sweeps)
        self._dg = executor._device_graph
        self._rp = executor.params.resolve(executor.workload.graph)
        self._steps = walk_length_for_tail(self._rp.alpha, self._rp.walk_tail)
        self._num_walks = int(executor.current_walk_budget())
        self._bulk = self._pinned_bulk()
        n = self._dg.n
        # device arrays round the lane count up to full vector groups so
        # the fused SpMM always reduces every real row in the vectorised
        # main loop (same bits as the padded chunked path — see
        # executor._PAR_BATCH_QUANTUM); rows beyond `lanes` stay zero and
        # never host a query — an empty row's frontier is empty, so it is
        # an exact identity under every sweep
        rows = _pad_batch(self.lanes)
        self._rows = rows
        with jax.transfer_guard("allow"):
            self._base = jax.random.PRNGKey(executor.workload.seed)
            self._pi = jnp.zeros((rows, n), jnp.float32)
            self._r = jnp.zeros((rows, n), jnp.float32)
            self._keys = jnp.zeros((rows,) + self._base.shape,
                                   self._base.dtype)
            self._active = jnp.zeros((rows,), bool)
            self._walked = jnp.zeros((rows,), bool)
        self._w_eff = None         # last step's per-lane stats (device)
        self._r_sum = None
        self._occupant: dict[int, int] = {}      # lane -> qid
        self._free = list(range(lanes))
        heapq.heapify(self._free)
        self.steps = 0
        self.inserted = 0
        self.harvested = 0

    # -- occupancy ---------------------------------------------------------
    @property
    def busy(self) -> int:
        return len(self._occupant)

    @property
    def free(self) -> int:
        return self.lanes - len(self._occupant)

    def occupants(self) -> dict[int, int]:
        return dict(self._occupant)

    def _pinned_bulk(self) -> bool:
        if self.executor._bulk_rng is not None:
            return bool(self.executor._bulk_rng)
        return self._steps * self._num_walks <= _BULK_RNG_ELEMS

    def _sync_budget(self) -> None:
        """Adopt the executor's current calibrated walk budget (per-block
        adaptive re-calibration feeds the engine here); a budget change
        retraces the step executable at the next call — a harvest-boundary
        cost, never a steady-state one."""
        nw = self.executor.current_walk_budget()
        if nw is not None and int(nw) != self._num_walks:
            self._num_walks = int(nw)
            self._bulk = self._pinned_bulk()

    # -- lifecycle ---------------------------------------------------------
    def insert(self, qid: int, lane: int | None = None) -> int:
        """Insert one query into a free lane (lowest-index first when not
        pinned). Returns the lane. Staging is the sanctioned host->device
        boundary; the steady-state ``step()`` loop stays sync-free."""
        if lane is None:
            if not self._free:
                raise RuntimeError("no free lane")
            lane = heapq.heappop(self._free)
        else:
            if lane in self._occupant:
                raise RuntimeError(f"lane {lane} is occupied")
            self._free.remove(lane)
            heapq.heapify(self._free)
        self._sync_budget()
        source = self.executor.workload.source_of(qid)
        with jax.transfer_guard("allow"):
            lane_dev = jnp.asarray(np.int32(lane))
            src_dev = jnp.asarray(np.int32(source))
            qid_dev = jnp.asarray(np.int32(qid))
        qkey = _engine_qkey(self._base, qid_dev)
        (self._pi, self._r, self._keys, self._active,
         self._walked) = _engine_insert(self._pi, self._r, self._keys,
                                        self._active, self._walked,
                                        lane_dev, src_dev, qkey)
        self._occupant[lane] = qid
        self.inserted += 1
        return lane

    def step(self) -> None:
        """Advance the whole pool one fused device step — no host syncs."""
        dg = self._dg
        (self._pi, self._r, self._walked,
         self._w_eff, self._r_sum) = _engine_step(
            dg.in_neighbors, dg.in_mask, dg.in_weights, dg.in_row_map,
            dg.edge_dst, dg.out_offsets, dg.out_degree,
            self._pi, self._r, self._keys, self._active, self._walked,
            alpha=self._rp.alpha, rmax=self._rp.rmax, omega=self._rp.omega,
            n=dg.n, num_walks=self._num_walks, num_steps=self._steps,
            sweeps=self.sweeps, bulk_rng=self._bulk)
        self.steps += 1

    def harvest(self) -> list[HarvestedQuery]:
        """The per-step readback boundary: read the converged-lane mask,
        gather those lanes' pi rows and stats, evict them. Empty list when
        nothing converged yet."""
        if self._w_eff is None:
            return []
        done_dev = self._active & self._walked
        done = np.asarray(done_dev)
        lanes = [int(x) for x in np.nonzero(done)[0]]
        if not lanes:
            return []
        with jax.transfer_guard("allow"):
            idx = jnp.asarray(np.asarray(lanes, np.int32))
        rows = np.asarray(jnp.take(self._pi, idx, axis=0))
        weff = np.asarray(jnp.take(self._w_eff, idx))
        rmass = np.asarray(jnp.take(self._r_sum, idx))
        (self._pi, self._r, self._active,
         self._walked) = _engine_release(self._pi, self._r, self._active,
                                         self._walked, done_dev)
        out = []
        for i, lane in enumerate(lanes):
            qid = self._occupant.pop(lane)
            heapq.heappush(self._free, lane)
            out.append(HarvestedQuery(qid=qid, lane=lane, pi=rows[i],
                                      walks_effective=int(weff[i]),
                                      residual_mass=float(rmass[i])))
        self.harvested += len(out)
        if self.executor.adaptive_budget and out:
            # feed observed residual mass back into the per-block budget
            # EWMA — the engine analog of run_chunk's harvest-boundary read
            self.executor.observe_residual_mass(
                max(h.residual_mass for h in out))
        return out

    def run_to_completion(self, max_steps: int = 10_000) -> list[
            HarvestedQuery]:
        """Drain every inserted query (test/benchmark convenience): step +
        harvest until the pool is empty."""
        out = []
        for _ in range(max_steps):
            if not self._occupant:
                return out
            self.step()
            out.extend(self.harvest())
        raise RuntimeError("engine failed to drain the lane pool")
