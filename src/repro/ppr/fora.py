"""FORA: forward push + Monte-Carlo random walks (Wang et al., KDD'17).

The paper's workload engine. Parameters follow FORA's single-source setting:
approximation guarantee |pi_hat - pi| <= eps * pi for all pi >= delta with
probability 1 - p_f, with the standard choices delta = 1/n, p_f = 1/n.

    omega = (2*eps/3 + 2) * ln(2/p_f) / (eps^2 * delta)     (total walk budget)
    rmax  = eps * sqrt(delta / (3 * m * ln(2/p_f)))          (push threshold)

Phase 1 pushes until all residuals satisfy r(v) <= rmax*deg(v); phase 2 runs
ceil(r_sum * omega) walks sampled from the residual distribution (TPU
adaptation — see random_walk.py) and adds the endpoint mass to the reserve.
Estimator is unbiased; randomness makes per-query time fluctuate, which is
exactly the phenomenon the paper's scaling factor d addresses.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .forward_push import forward_push, forward_push_np
from .graph import DeviceGraph, Graph, ShardedDeviceGraph
from .random_walk import (_BULK_RNG_ELEMS, WalkMass, counted_walk_endpoints,
                          lane_streams, per_query, residual_walks,
                          residual_walks_batched, sample_walk_starts,
                          walk_length_for_tail)


@dataclass(frozen=True)
class ForaParams:
    alpha: float = 0.2
    epsilon: float = 0.5
    delta: float | None = None     # default 1/n
    p_f: float | None = None       # default 1/n
    rmax_scale: float = 1.0        # beyond-paper tuning knob (push/walk balance)
    walk_tail: float = 1e-4
    max_walks: int = 1 << 22       # hard cap keeping the walk phase jit-static

    def resolve(self, graph: "Graph | DeviceGraph") -> "ResolvedFora":
        n, m = graph.n, graph.m
        delta = self.delta if self.delta is not None else 1.0 / n
        p_f = self.p_f if self.p_f is not None else 1.0 / n
        log_term = math.log(2.0 / p_f)
        omega = (2.0 * self.epsilon / 3.0 + 2.0) * log_term / (self.epsilon ** 2 * delta)
        rmax = self.rmax_scale * self.epsilon * math.sqrt(delta / (3.0 * m * log_term))
        return ResolvedFora(alpha=self.alpha, epsilon=self.epsilon,
                            delta=delta, p_f=p_f, omega=omega, rmax=rmax,
                            walk_tail=self.walk_tail, max_walks=self.max_walks)


@dataclass(frozen=True)
class ResolvedFora:
    alpha: float
    epsilon: float
    delta: float
    p_f: float
    omega: float
    rmax: float
    walk_tail: float
    max_walks: int


class ForaResult(NamedTuple):
    pi: np.ndarray        # (B, n) PPR estimates
    push_iters: int
    walks_used: int
    residual_mass: np.ndarray  # (B,) r_sum after push (drives walk count)


def fora(graph: Graph, sources: np.ndarray, params: ForaParams = ForaParams(),
         key: jax.Array | None = None) -> ForaResult:
    """Single-source FORA for a batch of sources (B,). Returns dense rows."""
    rp = params.resolve(graph)
    if key is None:
        key = jax.random.PRNGKey(0)
    sources = np.asarray(sources, dtype=np.int32).reshape(-1)

    push = forward_push_np(graph, sources, alpha=rp.alpha, rmax=rp.rmax)
    residual = np.asarray(push.r)
    r_sum = residual.sum(axis=1)

    # Walk budget: FORA uses ceil(r_sum * omega) per source. W must be static
    # for jit, so we take the batch max (extra walks only reduce variance —
    # they are still weighted by each row's own r_sum / W) and round UP to
    # the next power of two so repeated queries reuse the same compiled
    # executable instead of re-jitting per distinct budget.
    walks = int(min(rp.max_walks, max(1, math.ceil(float(r_sum.max()) * rp.omega))))
    walks = 1 << (walks - 1).bit_length()
    wr = residual_walks_batched(graph, residual, key, alpha=rp.alpha,
                                num_walks=walks, tail=rp.walk_tail)
    pi = np.asarray(push.pi) + np.asarray(wr.endpoint_mass)
    return ForaResult(pi=pi, push_iters=int(push.iters),
                      walks_used=walks, residual_mass=r_sum)


def fora_query_block(graph: Graph, sources: np.ndarray,
                     params: ForaParams = ForaParams(),
                     seed: int = 0) -> np.ndarray:
    """The serving-path entry point: one block of queries -> PPR rows."""
    key = jax.random.PRNGKey(seed)
    return fora(graph, sources, params, key).pi


class FusedForaResult(NamedTuple):
    """Device-resident FORA result — nothing here has touched the host.

    Readout (``np.asarray(res.pi)`` / ``block_until_ready``) is the caller's
    single host sync per query block.
    """

    pi: jax.Array              # (B, n) PPR estimates, on device
    residual_mass: jax.Array   # (B,) r_sum after push, on device
    push_iters: jax.Array      # () int32, on device
    walks_effective: jax.Array  # (B,) int32 pow2-quantised budgets, on device
    walks_budget: int          # static lane count W the executable was built at
    # Work counters, (B,) float32 per row, on device. Shares of the work
    # done: front_arcs / (push_iters * m) of the push's arc reads carried
    # mass, walk_steps_live / walk_steps_run of the walks' lane-steps.
    front_arcs: jax.Array      # out-degrees of frontier nodes, over sweeps
    walk_steps_live: jax.Array  # lane-steps begun by a live, weighted lane
    walk_steps_run: jax.Array  # lane-steps the walk loop ran


def _pow2_ceil_host(v: int) -> int:
    return 1 << (max(1, int(v)) - 1).bit_length()


def default_walk_budget(rp: ResolvedFora) -> int:
    """Static walk lane count when no calibrated budget is supplied: the
    worst case r_sum = 1 (pushes cannot increase total residual mass)."""
    return _pow2_ceil_host(min(rp.max_walks, math.ceil(rp.omega)))


def _fora_fused_impl(in_neighbors, in_mask, in_weights, in_row_map, edge_dst,
                     out_offsets, out_degree, sources, key,
                     idx_endpoints=None, idx_budget=None, idx_key=None,
                     query_seeds=None, *,
                     alpha: float, rmax: float, omega: float, n: int,
                     num_walks: int, num_steps: int, max_push_iters: int,
                     force: str | None = None,
                     shard_axis: str | None = None, num_shards: int = 1,
                     index_lanes: int = 0, index_partial: bool = False,
                     bulk_rng: bool | None = None, block_n: int = 256):
    """The whole FORA query block as ONE executable: seed construction,
    frontier push (pull-form ELL SpMM, dense or sliced view), pow2
    walk-budget quantisation and the residual walks all stay on device.
    See DESIGN.md §7 for the host<->device dataflow.

    With ``shard_axis`` (the body runs per-shard under ``shard_map`` over a
    :class:`ShardedDeviceGraph` mesh, DESIGN.md §9) the push combines row
    blocks per sweep via the per-shard collectives, and the walk budget is
    split into ``num_walks / num_shards`` lanes per shard (global lane ids —
    the union of the shards' RNG streams is the single-device stream);
    endpoint masses are psum-combined, so every returned array is replicated.

    With ``index_lanes > 0`` (a :class:`repro.index.WalkIndex` attached,
    DESIGN.md §11) the walk phase's first ``index_lanes`` lanes are served
    from the pre-drawn endpoint table (``idx_endpoints``/``idx_budget``, via
    :func:`repro.kernels.ops.walk_endpoint_gather`) instead of being stepped
    live; shortfall lanes — and, when ``index_partial``, table lanes whose
    start node's budget does not cover them — fall back to live draws on the
    index's per-lane trajectory streams (``idx_key``). Start sampling is the
    same inverse-CDF draw from the query key as the live path, so per-query
    randomness is untouched and the zero-host-sync contract is preserved.

    ``query_seeds`` (int32 (B,), usually the query ids) switches per-query
    key derivation from ``split(key, B)`` — which ties a query's stream to
    its *position and batch* — to ``fold_in(key, qid)``, making every
    query's stream a function of (base key, qid) alone. This is the
    composition-invariance contract the continuous-batching engine's
    bit-parity rests on: the same query inserted into any lane of any batch
    draws the same walks. ``bulk_rng`` pins the bulk-vs-per-step draw
    strategy (two *different* streams) explicitly; ``None`` keeps the legacy
    B-dependent heuristic.
    """
    B = sources.shape[0]
    seeds = jnp.zeros((B, n), jnp.float32).at[
        jnp.arange(B), sources].set(1.0)
    with jax.named_scope("fora.push"):
        push = forward_push(in_neighbors, in_mask, in_weights, out_degree,
                            seeds, alpha=alpha, rmax=rmax, n=n,
                            max_iters=max_push_iters, row_map=in_row_map,
                            force=force, shard_axis=shard_axis,
                            block_n=block_n)
    r_sum = push.r.sum(axis=1)                               # (B,)
    # FORA budget ceil(r_sum * omega), quantised UP to the next power of two
    # on device (mirrors the host-side quantisation of fora()) and clipped to
    # the static lane count; lanes beyond the effective budget get weight 0.
    need = jnp.maximum(jnp.ceil(r_sum * omega), 1.0)
    w_eff = jnp.exp2(jnp.ceil(jnp.log2(need)))
    w_eff = jnp.clip(w_eff, 1.0, float(num_walks)).astype(jnp.int32)
    if query_seeds is None:
        keys = jax.random.split(key, B)
    else:
        keys = jax.vmap(lambda q: jax.random.fold_in(key, q))(query_seeds)
    # bulk-RNG decision must count the vmapped batch: the (L, W) draw
    # batches to (B, L, W) under vmap. Callers that need the stream to be
    # batch-composition-invariant (the executor / engine) pin it via the
    # bulk_rng static instead.
    if bulk_rng is None:
        bulk = B * num_steps * num_walks <= _BULK_RNG_ELEMS
    else:
        bulk = bulk_rng
    if index_lanes > 0:
        # walk-index mode: starts sampled exactly as the live path samples
        # them (same key split, same op order), endpoints for the covered
        # lanes gathered from the pre-drawn table, shortfall walked live on
        # the index's per-lane streams
        starts = per_query(lambda r, k: sample_walk_starts(
            r, k, num_walks=num_walks, n=n)[0], push.r, keys)
        with jax.named_scope("fora.walk_steps"):
            walked = _index_walks(
                idx_endpoints, idx_budget, idx_key, edge_dst, out_offsets,
                out_degree, starts, r_sum, w_eff, alpha=alpha, n=n,
                num_walks=num_walks, num_steps=num_steps,
                index_lanes=index_lanes, index_partial=index_partial,
                force=force)
    elif shard_axis is None:
        walked = _walk_parts(per_query(lambda r, k, a: residual_walks(
            edge_dst, out_offsets, out_degree, r, k, alpha=alpha, n=n,
            num_walks=num_walks, num_steps=num_steps, active_walks=a,
            bulk_rng=bulk), push.r, keys, w_eff))
    else:
        lanes = num_walks // num_shards           # caller rounds num_walks up
        offset = jax.lax.axis_index(shard_axis) * lanes
        walked = _walk_parts(per_query(lambda r, k, a: residual_walks(
            edge_dst, out_offsets, out_degree, r, k, alpha=alpha, n=n,
            num_walks=num_walks, num_steps=num_steps, active_walks=a,
            bulk_rng=bulk, lanes=lanes, lane_offset=offset),
            push.r, keys, w_eff))
        with jax.named_scope("fora.walk_steps"):
            # the shards' endpoint masses and step counts, added up, so
            # every output is replicated as the out_specs declare
            walked = jax.lax.psum(walked, shard_axis)
    return (push.pi + walked.mass, r_sum, push.iters, w_eff,
            push.front_arcs, walked.steps_live, walked.steps_run)


def _walk_parts(walked) -> WalkMass:
    """The batch's walk phase as a :class:`WalkMass`. A walk function that
    returns its endpoint mass alone (``bench/tests`` put one in place of
    ``residual_walks`` to leave the walks out) reports no lane-steps."""
    if isinstance(walked, WalkMass):
        return walked
    none = jnp.zeros(walked.shape[:1], jnp.float32)
    return WalkMass(walked, none, none)


def _index_walks(idx_endpoints, idx_budget, idx_key, edge_dst, out_offsets,
                 out_degree, starts, r_sum, w_eff, *, alpha: float, n: int,
                 num_walks: int, num_steps: int, index_lanes: int,
                 index_partial: bool, force: str | None) -> WalkMass:
    """The index-backed walk phase of a batch (DESIGN.md §11): the covered
    lanes' endpoints gathered from the table, the shortfall walked live.
    Only the live lanes count as lane-steps run."""
    act = jnp.clip(w_eff, 1, num_walks).astype(r_sum.dtype)
    lane = jnp.arange(num_walks, dtype=jnp.int32)
    w_all = jnp.where(lane[None, :] < act[:, None],
                      (r_sum / act)[:, None], 0.0).astype(r_sum.dtype)
    endpoint = ops.walk_endpoint_gather(
        idx_endpoints, idx_budget, starts[:, :index_lanes],
        w_all[:, :index_lanes], force=force)
    none = jnp.zeros(starts.shape[:1], jnp.float32)
    live_lo = 0 if index_partial else index_lanes
    if live_lo == num_walks:
        return WalkMass(endpoint, none, none)
    live_lanes = jnp.arange(live_lo, num_walks, dtype=jnp.int32)
    us = lane_streams(idx_key, live_lanes, num_steps)
    w_live = w_all[:, live_lo:]
    if index_partial:
        # table-covered head cells already contributed above
        covered = (lane[None, :index_lanes]
                   < idx_budget[starts[:, :index_lanes]])
        w_live = w_live.at[:, :index_lanes].set(
            jnp.where(covered, 0.0, w_live[:, :index_lanes]))
    e_live, steps_live = counted_walk_endpoints(
        edge_dst, out_offsets, out_degree, starts[:, live_lo:], us,
        alpha=alpha, weighted=w_live > 0)
    endpoint = endpoint + jax.vmap(lambda e, ww: jax.ops.segment_sum(
        ww, e, num_segments=n))(e_live, w_live)
    ran = jnp.full(starts.shape[:1], (num_walks - live_lo) * num_steps,
                   jnp.float32)
    return WalkMass(endpoint, steps_live, ran)


_FUSED_STATICS = ("alpha", "rmax", "omega", "n", "num_walks", "num_steps",
                  "max_push_iters", "force", "shard_axis", "num_shards",
                  "index_lanes", "index_partial", "bulk_rng", "block_n")
_fora_fused = jax.jit(_fora_fused_impl, static_argnames=_FUSED_STATICS)
# On TPU the (B,) sources buffer is donated (it aliases the int32
# walks_effective output). On CPU donation is a measured ~1.7 ms/call
# pessimisation (XLA CPU takes a defensive-copy path), so the plain
# executable is used there.
_fora_fused_donating = jax.jit(_fora_fused_impl,
                               static_argnames=_FUSED_STATICS,
                               donate_argnames=("sources",))


@functools.lru_cache(maxsize=64)
def _fora_fused_sharded_exe(mesh, axis: str, num_shards: int, sliced: bool,
                            seeded: bool, alpha: float, rmax: float,
                            omega: float, n: int,
                            num_walks: int, num_steps: int,
                            max_push_iters: int, force: str | None,
                            bulk_rng: bool | None, block_n: int = 256,
                            donate: bool = False):
    """Build (and cache per mesh/statics) the shard_map'd fused executable.

    The whole fused body runs per-shard: in_specs shard the push table by
    (virtual) row along ``axis`` and replicate everything else; out_specs are
    replicated because the body's collectives (all-gather / psum) already
    leave every output identical on all shards. ``seeded`` adds the
    replicated per-query ``query_seeds`` input (fold_in key derivation).

    ``donate`` aliases the replicated (B,) ``sources`` buffer into the int32
    ``walks_effective`` output — the same TPU-only policy as the
    single-device ``_fora_fused_donating`` (on CPU XLA's defensive copy
    makes donation a pessimisation); callers must pass a copy they own."""
    from jax.sharding import PartitionSpec as P

    kwargs = dict(alpha=alpha, rmax=rmax, omega=omega, n=n,
                  num_walks=num_walks, num_steps=num_steps,
                  max_push_iters=max_push_iters, force=force,
                  shard_axis=axis, num_shards=num_shards, bulk_rng=bulk_rng,
                  block_n=block_n)
    row = P(axis, None)
    repl = P()
    if sliced:
        def fn(nbr, msk, wts, row_map, edge_dst, out_offsets, out_degree,
               sources, key, *qseeds):
            return _fora_fused_impl(nbr, msk, wts, row_map, edge_dst,
                                    out_offsets, out_degree, sources, key,
                                    None, None, None,
                                    qseeds[0] if qseeds else None, **kwargs)
        in_specs = (row, row, row, P(axis), repl, repl, repl, repl, repl)
        sources_pos = 7
    else:
        def fn(nbr, msk, wts, edge_dst, out_offsets, out_degree,
               sources, key, *qseeds):
            return _fora_fused_impl(nbr, msk, wts, None, edge_dst,
                                    out_offsets, out_degree, sources, key,
                                    None, None, None,
                                    qseeds[0] if qseeds else None, **kwargs)
        in_specs = (row, row, row, repl, repl, repl, repl, repl)
        sources_pos = 6
    if seeded:
        in_specs = in_specs + (repl,)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=(repl,) * 7,
                           check_vma=False)
    if donate:
        return jax.jit(mapped, donate_argnums=(sources_pos,))
    return jax.jit(mapped)


def _stage_sharded(dg: ShardedDeviceGraph, sources, rp: ResolvedFora,
                   key: jax.Array, *, num_walks: int,
                   force: str | None, query_seeds=None,
                   bulk_rng: bool | None = None):
    """The shard_map'd executable of :func:`fora_fused` over a sharded
    residency, its staged arguments and its lane count."""
    steps = walk_length_for_tail(rp.alpha, rp.walk_tail)
    # pow2 budget, then rounded up so every shard gets an equal lane slice.
    # When num_shards is itself a power of two (every TPU slice shape) the
    # round-up is a no-op and the sharded RNG stream is bit-identical to the
    # single-device one; a non-pow2 shard count widens the lane table, which
    # is still a valid unbiased FORA draw but a *different* stream than a
    # single device would sample.
    num_walks = _pow2_ceil_host(num_walks)
    num_walks = -(-num_walks // dg.num_shards) * dg.num_shards
    # TPU-only donation, mirroring the single-device policy: the caller's
    # sources buffer is copied first so donation invalidates only our copy
    donate = jax.default_backend() == "tpu"
    if donate:
        sources = jnp.array(sources, jnp.int32, copy=True).reshape(-1)
    else:
        sources = jnp.asarray(sources).astype(jnp.int32).reshape(-1)
    exe = _fora_fused_sharded_exe(
        dg.mesh, dg.axis, dg.num_shards, dg.in_row_map is not None,
        query_seeds is not None, rp.alpha, rp.rmax, rp.omega, dg.n,
        num_walks, steps, 10_000, force, bulk_rng, dg.block_n, donate)
    table = (dg.in_neighbors, dg.in_mask, dg.in_weights)
    if dg.in_row_map is not None:
        table = table + (dg.in_row_map,)
    args = (dg.edge_dst, dg.out_offsets, dg.out_degree, sources, key)
    if query_seeds is not None:
        args = args + (jnp.asarray(query_seeds).astype(jnp.int32).reshape(-1),)
    return partial(exe, *table, *args), num_walks


def fora_fused(dg: "DeviceGraph | ShardedDeviceGraph", sources,
               params: ForaParams = ForaParams(),
               key: jax.Array | None = None, *,
               num_walks: int | None = None,
               force: str | None = None,
               index: "object | None" = None,
               query_seeds=None,
               bulk_rng: bool | None = None) -> FusedForaResult:
    """Zero-host-sync FORA on a :class:`DeviceGraph` (or, node-sharded
    across a device mesh, a :class:`ShardedDeviceGraph` — DESIGN.md §9).

    One jitted call chains push -> pow2 walk-budget quantisation ->
    residual walks; the only host transfer per query block is the caller's
    final readout of the returned device arrays. ``num_walks`` pins the
    static walk lane count (e.g. a workload-calibrated budget from
    :class:`repro.ppr.executor.ForaExecutor`); by default it covers the
    worst case r_sum = 1 so the estimator never under-samples.

    ``index`` attaches a :class:`repro.index.WalkIndex` (DESIGN.md §11):
    walk lanes the stored budget covers are served from the pre-drawn
    endpoint table (a gather instead of an L-step scan), shortfall lanes
    are drawn live on the index's trajectory streams. The index must have
    been built at this call's alpha/walk-tail (validated here) and is
    single-device only — the sharded residency replicates its own walk
    arrays and rejects an index.

    ``query_seeds`` (int32 (B,)) derives each row's walk key as
    ``fold_in(key, query_seeds[i])`` instead of ``split(key, B)`` — per-query
    streams become independent of batch composition, the invariance the
    serving engine's bit-parity contract needs. ``bulk_rng`` pins the
    bulk-vs-per-step draw strategy (``None`` = legacy per-call heuristic).
    """
    with jax.profiler.TraceAnnotation("fora.stage"):
        run, num_walks = _stage(dg, sources, params, key, num_walks=num_walks,
                                force=force, index=index,
                                query_seeds=query_seeds, bulk_rng=bulk_rng)
    with jax.profiler.TraceAnnotation("fora.enqueue"):
        pi, r_sum, iters, w_eff, arcs, live, ran = run()
    return FusedForaResult(pi=pi, residual_mass=r_sum, push_iters=iters,
                           walks_effective=w_eff, walks_budget=num_walks,
                           front_arcs=arcs, walk_steps_live=live,
                           walk_steps_run=ran)


def _stage(dg, sources, params: ForaParams, key, *, num_walks, force, index,
           query_seeds, bulk_rng):
    """Everything :func:`fora_fused` does before its executable runs:
    resolve the parameters, convert and upload the sources and seeds, pick
    the executable. Returns the call, its arguments bound, and the lane
    count."""
    rp = params.resolve(dg)
    if key is None:
        key = jax.random.PRNGKey(0)
    if num_walks is None:
        num_walks = default_walk_budget(rp)
    if isinstance(dg, ShardedDeviceGraph):
        if index is not None:
            raise ValueError("walk index is single-device only; the sharded "
                             "residency draws its walk lanes per shard")
        return _stage_sharded(dg, sources, rp, key, num_walks=num_walks,
                              force=force, query_seeds=query_seeds,
                              bulk_rng=bulk_rng)
    num_walks = _pow2_ceil_host(num_walks)
    steps = walk_length_for_tail(rp.alpha, rp.walk_tail)
    index_lanes, index_partial = 0, False
    idx_e = idx_b = idx_k = None
    if index is not None:
        if index.n != dg.n:
            raise ValueError(f"index built for n={index.n}, graph has {dg.n}")
        if abs(index.alpha - rp.alpha) > 1e-12 or index.num_steps != steps:
            raise ValueError(
                f"index walked alpha={index.alpha}/L={index.num_steps}, "
                f"query needs alpha={rp.alpha}/L={steps} — rebuild the index")
        index_lanes = min(index.width, num_walks)
        index_partial = bool(index.partial)
        idx_e, idx_b, idx_k = index.endpoints, index.budget, index.key
    if jax.default_backend() == "tpu":
        # copy before donating: the int32/reshape conversions are no-ops for
        # an already-1D-int32 input, and donating the caller's own buffer
        # would invalidate it for reuse
        sources = jnp.array(sources, jnp.int32, copy=True).reshape(-1)
        fused_fn = _fora_fused_donating
    else:
        sources = jnp.asarray(sources).astype(jnp.int32).reshape(-1)
        fused_fn = _fora_fused
    if query_seeds is not None:
        query_seeds = jnp.asarray(query_seeds).astype(jnp.int32).reshape(-1)
    return partial(
        fused_fn, dg.in_neighbors, dg.in_mask, dg.in_weights, dg.in_row_map,
        dg.edge_dst, dg.out_offsets, dg.out_degree, sources, key,
        idx_e, idx_b, idx_k, query_seeds,
        alpha=rp.alpha, rmax=rp.rmax, omega=rp.omega, n=dg.n,
        num_walks=num_walks, num_steps=steps, max_push_iters=10_000,
        force=force, index_lanes=index_lanes, index_partial=index_partial,
        bulk_rng=bulk_rng, block_n=dg.block_n), num_walks


def fora_step_calib(edge_src, edge_dst, out_offsets, out_degree, seeds, key,
                    *, alpha: float, rmax: float, n: int, num_walks: int,
                    push_sweeps: int, walk_steps: int):
    """Straight-line FORA step with pinned loop counts — the dry-run cost
    calibration variant (XLA cost analysis counts loop bodies once; the
    launcher lowers this at (1,1)/(2,1)/(1,2) and extrapolates to the
    deployment counts). Math identical to fora_step per sweep/step."""
    deg = jnp.maximum(out_degree.astype(jnp.float32), 1.0)
    threshold = rmax * deg
    pi = jnp.zeros_like(seeds)
    r = seeds
    for _ in range(push_sweeps):
        front = (r > threshold[None, :]).astype(r.dtype)
        pushed = r * front
        pi = pi + alpha * pushed
        spread = (1.0 - alpha) * pushed / deg[None, :]
        moved = jax.ops.segment_sum(spread[:, edge_src].T, edge_dst,
                                    num_segments=n).T
        r = r * (1.0 - front) + moved

    B = seeds.shape[0]
    r_sum = r.sum(axis=1)                                 # (B,)
    csum = jnp.cumsum(r, axis=1)
    keys = jax.random.split(key, B)
    deg_i = jnp.maximum(out_degree, 1).astype(jnp.int32)
    out = pi
    u = jax.vmap(lambda k: jax.random.uniform(k, (num_walks,)))(keys)
    starts = jax.vmap(lambda c, uu, s: jnp.searchsorted(c, uu * s))(
        csum, u, r_sum).astype(jnp.int32)
    pos = jnp.clip(starts, 0, n - 1)
    alive = jnp.ones((B, num_walks), bool)
    for step_i in range(walk_steps):
        ks = jax.vmap(lambda k, i=step_i: jax.random.fold_in(k, i))(keys)
        stop = jax.vmap(lambda k: jax.random.uniform(k, (num_walks,)))(ks) < alpha
        nxt_u = jax.vmap(lambda k: jax.random.randint(k, (num_walks,), 0,
                                                      1 << 30))(ks)
        nxt = edge_dst[out_offsets[pos] + (nxt_u % deg_i[pos])]
        alive = jnp.logical_and(alive, jnp.logical_not(stop))
        pos = jnp.where(alive, nxt, pos)
    w = (r_sum / num_walks)[:, None] * jnp.ones((B, num_walks), seeds.dtype)
    endpoint = jax.vmap(lambda p, ww: jax.ops.segment_sum(
        ww, p, num_segments=n))(pos, w)
    return out + endpoint


def fora_step(edge_src, edge_dst, out_offsets, out_degree, seeds, key, *,
              alpha: float, rmax: float, n: int, num_walks: int,
              num_steps: int, max_push_iters: int = 512):
    """Single-jit FORA step with a static walk budget — the unit the D&A
    slot executor and the dry-run lower (one slot = one such step).

    seeds: (B, n) one-hot residuals. Returns pi_hat (B, n).
    """
    from .forward_push import forward_push_coo

    push = forward_push_coo(edge_src, edge_dst, out_degree, seeds,
                            alpha=alpha, rmax=rmax, n=n,
                            max_iters=max_push_iters)
    keys = jax.random.split(key, seeds.shape[0])
    walk = per_query(lambda r, k: residual_walks(
        edge_dst, out_offsets, out_degree, r, k, alpha=alpha, n=n,
        num_walks=num_walks, num_steps=num_steps), push.r, keys)
    return push.pi + walk.mass
