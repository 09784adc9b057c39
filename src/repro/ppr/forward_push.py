"""Frontier-synchronous forward push (FORA phase 1), TPU-native.

CPU FORA maintains a worklist and pushes one node at a time. On TPU the
worklist is hostile (data-dependent control flow, no vector parallelism), so
we relax **every** above-threshold node per iteration:

    front(v)   = r(v) > rmax * deg_out(v)          (FORA's push condition)
    pi        += alpha * r * front
    spread(v)  = (1 - alpha) * r(v) * front(v) / deg_out(v)
    r         <- r * (1 - front) + P^T (r * front) * (1 - alpha)

The relaxation is the *pull-form* ELL SpMM (DESIGN.md §5): each sweep is one
``kernels.ops.ell_spmm`` over the padded in-neighbor table with weights
1/deg_out(src) — or ``ell_spmm_sliced`` when the graph's DeviceGraph carries
a sliced table (``row_map`` set; power-law graphs, DESIGN.md §8) — under
``jax.lax.while_loop`` until no node is above threshold (or ``max_iters``). On the Pallas path the push condition itself is fused
into the kernel via the ``threshold`` argument — the kernel gathers the raw
residual and zeroes below-threshold sources in-register, so ``r * front``
never round-trips through HBM between sweeps.

Changing push *order* does not affect FORA's invariant

    pi_true(s,t) = pi(t) + sum_v r(v) * pi_true(v,t)

which holds after every iteration and is what the walk phase consumes; the
termination condition (all r(v) <= rmax*deg(v)) is identical to sequential
FORA's, so the approximation guarantee carries over unchanged.

Batched over B sources (leading axis); inside the kernel the batch rides the
lane axis. Residual/reserve live as dense (B, n) — the same layout the
``model``-axis sharding partitions in the distributed path.
``forward_push_coo`` keeps the original edge-list ``segment_sum`` relaxation
for the edge-sharded calibration path (``fora_step``), where edges rather
than rows are partitioned across the mesh.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .graph import Graph


class PushState(NamedTuple):
    pi: jax.Array        # (B, n) reserve (lower-bound PPR mass)
    r: jax.Array         # (B, n) residual
    iters: jax.Array     # () int32
    front_arcs: jax.Array  # (B,) float32, see PushResult


class PushResult(NamedTuple):
    pi: jax.Array        # (B, n)
    r: jax.Array         # (B, n)
    iters: jax.Array     # () number of frontier sweeps executed
    front_arcs: jax.Array  # (B,) float32 per row: the out-degrees of its
    #                        frontier nodes, summed over the sweeps (the arcs
    #                        that carried mass, of the iters * m each row's
    #                        sweeps read); a float, so no sweep count can
    #                        overflow it


def _front_arcs(front: jax.Array, deg: jax.Array) -> jax.Array:
    """Arcs leaving each row's frontier in one sweep: (B,) float32."""
    return jnp.sum(front * deg[None, :], axis=1)


@partial(jax.jit, static_argnames=("n", "max_iters", "force", "shard_axis",
                                   "block_n"))
def forward_push(in_neighbors: jax.Array, in_mask: jax.Array,
                 in_weights: jax.Array, out_degree: jax.Array,
                 seeds: jax.Array, *, alpha: float, rmax: float, n: int,
                 max_iters: int = 10_000, row_map: jax.Array | None = None,
                 force: str | None = None,
                 shard_axis: str | None = None,
                 pi0: jax.Array | None = None,
                 block_n: int = 256) -> PushResult:
    """Batched frontier push over the pull-form ELL view.

    ``in_neighbors``/``in_mask``/``in_weights`` are the (n, K) padded
    in-neighbor table from :meth:`Graph.ell_in` (weights 1/deg_out(src)) —
    or, with ``row_map`` (n_virtual,), the sliced (n_virtual, W) table from
    :meth:`Graph.ell_in_sliced`, consumed transparently (DESIGN.md §8);
    ``seeds`` is (B, n) one-hot (or any residual). Returns (pi, r) with the
    FORA invariant; every residual entry satisfies r(v) <= rmax * deg_out(v)
    on normal termination.

    With ``shard_axis`` (inside ``shard_map`` over a
    :class:`~repro.ppr.graph.ShardedDeviceGraph`'s mesh) the table arrays
    are this shard's row block and each sweep reassembles the full (B, n)
    relaxation via the per-shard collectives in :mod:`repro.kernels.ops`
    (all-gather for dense rows, psum for sliced partials — DESIGN.md §9);
    ``seeds``/``out_degree`` stay replicated so the frontier schedule is
    identical on every shard.

    ``pi0`` (default zeros) seeds the reserve accumulator, letting the
    serving engine resume a bounded push (``max_iters`` = sweeps per engine
    step) bit-identically to one uninterrupted run: chaining while_loop
    executions of the SAME body is the same left-fold as one long loop.

    ``block_n`` is the Pallas row tile forwarded to the SpMM kernels —
    autotuned per backend/shape via ``kernels.autotune`` and carried on
    :class:`~repro.ppr.graph.DeviceGraph`; numerics-neutral (per-virtual-row
    partials and fold order are independent of the tiling, DESIGN.md §15).
    """
    deg = out_degree.astype(jnp.float32)
    deg_safe = jnp.maximum(deg, 1.0)
    threshold = rmax * deg_safe                      # (n,)

    def cond(state: PushState) -> jax.Array:
        active = jnp.any(state.r > threshold[None, :])
        return jnp.logical_and(active, state.iters < max_iters)

    def body(state: PushState) -> PushState:
        front = (state.r > threshold[None, :]).astype(state.r.dtype)  # (B,n)
        pi = state.pi + alpha * state.r * front
        # one pull-form SpMM == P^T (r * front); the kernel applies the
        # push condition to the gathered residual itself (fused threshold)
        if row_map is None:
            if shard_axis is None:
                moved = ops.ell_spmm(in_neighbors, in_mask, in_weights,
                                     state.r, threshold=threshold,
                                     force=force, block_n=block_n)
            else:
                moved = ops.ell_spmm_shard(
                    in_neighbors, in_mask, in_weights, state.r,
                    axis_name=shard_axis, threshold=threshold,
                    force=force, block_n=block_n)[:, :n]  # drop row padding
        elif shard_axis is None:
            moved = ops.ell_spmm_sliced(in_neighbors, in_mask, in_weights,
                                        row_map, state.r,
                                        threshold=threshold, force=force,
                                        block_n=block_n)
        else:
            moved = ops.ell_spmm_sliced_shard(
                in_neighbors, in_mask, in_weights, row_map, state.r,
                axis_name=shard_axis, threshold=threshold, force=force,
                block_n=block_n)
        moved = (1.0 - alpha) * moved
        r = state.r * (1.0 - front) + moved
        return PushState(pi=pi, r=r, iters=state.iters + 1,
                         front_arcs=state.front_arcs + _front_arcs(front, deg))

    init = PushState(pi=jnp.zeros_like(seeds) if pi0 is None else pi0,
                     r=seeds, iters=jnp.zeros((), jnp.int32),
                     front_arcs=jnp.zeros(seeds.shape[:1], jnp.float32))
    final = jax.lax.while_loop(cond, body, init)
    return PushResult(*final)


@partial(jax.jit, static_argnames=("n", "max_iters"))
def forward_push_coo(edge_src: jax.Array, edge_dst: jax.Array,
                     out_degree: jax.Array, seeds: jax.Array,
                     *, alpha: float, rmax: float, n: int,
                     max_iters: int = 10_000) -> PushResult:
    """Edge-list relaxation (``segment_sum`` per sweep) — kept for the
    edge-sharded ``fora_step`` path where the mesh partitions edges, not
    rows. Math identical to :func:`forward_push`.
    """
    deg = out_degree.astype(jnp.float32)
    deg_safe = jnp.maximum(deg, 1.0)
    threshold = rmax * deg_safe                      # (n,)

    def cond(state: PushState) -> jax.Array:
        active = jnp.any(state.r > threshold[None, :])
        return jnp.logical_and(active, state.iters < max_iters)

    def body(state: PushState) -> PushState:
        front = (state.r > threshold[None, :]).astype(state.r.dtype)  # (B,n)
        pushed = state.r * front
        pi = state.pi + alpha * pushed
        spread = (1.0 - alpha) * pushed / deg_safe[None, :]
        # scatter along edges: every out-edge of v carries spread(v)
        moved = jax.ops.segment_sum(
            spread[:, edge_src].T, edge_dst, num_segments=n).T   # (B, n)
        r = state.r * (1.0 - front) + moved
        return PushState(pi=pi, r=r, iters=state.iters + 1,
                         front_arcs=state.front_arcs + _front_arcs(front, deg))

    init = PushState(pi=jnp.zeros_like(seeds), r=seeds,
                     iters=jnp.zeros((), jnp.int32),
                     front_arcs=jnp.zeros(seeds.shape[:1], jnp.float32))
    final = jax.lax.while_loop(cond, body, init)
    return PushResult(*final)


def forward_push_np(graph: Graph, sources: np.ndarray, *, alpha: float,
                    rmax: float, max_iters: int = 10_000) -> PushResult:
    """Convenience wrapper: one-hot seeds + device arrays from a Graph.

    Uses the upload-once :class:`DeviceGraph` mirror, so repeated calls on
    the same Graph never re-transfer the adjacency.
    """
    dg = graph.device()
    sources = np.asarray(sources, dtype=np.int32).reshape(-1)
    seeds = np.zeros((sources.size, graph.n), dtype=np.float32)
    seeds[np.arange(sources.size), sources] = 1.0
    return forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                        dg.out_degree, jnp.asarray(seeds), alpha=alpha,
                        rmax=rmax, n=graph.n, max_iters=max_iters,
                        row_map=dg.in_row_map)
