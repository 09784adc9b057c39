"""Vectorised alpha-terminated random walks (FORA phase 2), TPU-native.

CPU FORA runs ceil(r(v) * omega) walks per residual node with geometric
lengths. TPU adaptation (DESIGN.md deviation 3):

* **Starts**: W walker start nodes are sampled proportional to the residual
  via inverse-CDF (cumsum + searchsorted) — identical in distribution to
  FORA's per-node quota in expectation, and W is static for jit.
* **Steps**: walks advance in lockstep for L unrolled steps; termination is a
  Bernoulli(alpha) mask per step (geometric length), dead lanes frozen.
  L = ceil(ln(tail)/ln(1-alpha)) bounds the truncation mass by ``tail``.
* **Transition**: uniform out-neighbor via CSR gather
  ``edge_dst[offsets[v] + u % deg(v)]`` — one ``jnp.take`` per step, no ELL
  padding needed, no per-step collectives in the sharded path.
* **Randomness**: ONE int32 draw per (step, walker) serves both decisions —
  ``u < floor(alpha * 2^30)`` is the Bernoulli(alpha) stop (bias < 2^-30)
  and ``u % deg`` the neighbor choice (modulo/conditioning bias O(deg/2^30));
  drawn as one bulk (L, W) table when it fits ``_BULK_RNG_ELEMS`` (per-step
  RNG calls dominate the scan body on CPU otherwise), else per step from
  pre-split keys so multi-million-walk budgets don't materialise a
  multi-hundred-MB table.

Estimate: endpoints accumulate weight r_sum/W via segment_sum, giving the
unbiased FORA estimator  pi_hat = pi_push + sum_v r(v) * (MC endpoint dist).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph


def walk_length_for_tail(alpha: float, tail: float = 1e-4) -> int:
    """Smallest L with (1-alpha)^L <= tail (truncation mass bound)."""
    if not 0.0 < alpha < 1.0:
        raise ValueError("alpha in (0,1)")
    return int(np.ceil(np.log(tail) / np.log(1.0 - alpha)))


class WalkResult(NamedTuple):
    endpoint_mass: jax.Array   # (B, n) estimated sum_v r(v)*pi(v, .)
    walks: int                 # W actually used (static)


class WalkMass(NamedTuple):
    """What :func:`residual_walks` returns for one batch row."""

    mass: jax.Array         # (n,) endpoint mass
    steps_live: jax.Array   # () float32 lane-steps begun by a live lane that
    #                         carries weight: the steps that count
    steps_run: jax.Array    # () float32 lane-steps the scan stepped


# one bulk (num_steps, num_walks) int32 draw is ~10x cheaper than per-step
# RNG calls on CPU, but must not materialise GBs at the max_walks budget:
# cap the table at 2^25 elements (128 MB int32) and fall back to per-step
# generation beyond it.
_BULK_RNG_ELEMS = 1 << 25


def _stop_bound(alpha: float) -> jax.Array:
    """Bernoulli(alpha) stop threshold on the shared int32 draw."""
    return jnp.floor(alpha * (1 << 30)).astype(jnp.int32)


def _advance(edge_dst, out_offsets, deg, stop_bound, pos, alive, u_step):
    """One lockstep walk transition — THE transition function, shared by
    the live walkers below, the :class:`repro.index.WalkIndex` builder and
    the index-backed fused path, so a stored endpoint is bit-for-bit the
    endpoint a live walker on the same RNG stream would reach. ``pos`` may
    be any shape ``u_step`` broadcasts against ((W,), (B, W), (n, W))."""
    stop = u_step < stop_bound
    nxt = edge_dst[out_offsets[pos] + (u_step % deg[pos])]
    new_alive = jnp.logical_and(alive, jnp.logical_not(stop))
    return jnp.where(new_alive, nxt, pos), new_alive


def _live_lanes(alive: jax.Array, weighted: jax.Array | None) -> jax.Array:
    """Lanes that begin a step alive and carry weight, counted over the
    last (lane) axis as float32."""
    live = alive if weighted is None else jnp.logical_and(alive, weighted)
    return jnp.sum(live, axis=-1, dtype=jnp.float32)


def lane_streams(trajectory_key: jax.Array, lane_ids: jax.Array,
                 num_steps: int) -> jax.Array:
    """Per-lane trajectory RNG: lane i's step draws come from
    ``fold_in(trajectory_key, i)``, so ANY subset of lanes can be drawn
    consistently regardless of how many lanes a caller materialises — the
    property that lets a precomputed walk index and a live shortfall draw
    share one stream (DESIGN.md §11). Returns (num_steps, len(lane_ids))."""
    keys = jax.vmap(lambda i: jax.random.fold_in(trajectory_key, i))(lane_ids)
    us = jax.vmap(lambda k: jax.random.randint(k, (num_steps,), 0, 1 << 30))(
        keys)
    return us.T


def walk_endpoints(edge_dst: jax.Array, out_offsets: jax.Array,
                   out_degree: jax.Array, starts: jax.Array,
                   us: jax.Array, *, alpha: float) -> jax.Array:
    """Endpoints of alpha-terminated walks under explicit step draws.

    ``starts``: (..., L) start nodes; ``us``: (num_steps, L) int32 draws
    (typically :func:`lane_streams`), broadcast over any leading axes of
    ``starts`` — a (B, L) batch shares the per-lane streams (the FORA+
    trade: trajectories are reused across queries, starts stay per-query),
    and the (n, L) all-nodes grid is how the walk index is built.
    """
    return counted_walk_endpoints(edge_dst, out_offsets, out_degree, starts,
                                  us, alpha=alpha)[0]


def counted_walk_endpoints(edge_dst: jax.Array, out_offsets: jax.Array,
                           out_degree: jax.Array, starts: jax.Array,
                           us: jax.Array, *, alpha: float,
                           weighted: jax.Array | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """:func:`walk_endpoints`, and the lane-steps begun by a live lane
    that ``weighted`` (shaped as ``starts``; all lanes when None) marks,
    counted per leading index of ``starts`` as float32."""
    deg = jnp.maximum(out_degree, 1).astype(jnp.int32)
    bound = _stop_bound(alpha)
    extra = starts.ndim - 1

    def step(carry, u_step):
        pos, alive, live = carry
        live = live + _live_lanes(alive, weighted)
        u = u_step.reshape((1,) * extra + u_step.shape)
        return (*_advance(edge_dst, out_offsets, deg, bound, pos, alive, u),
                live), None

    init = (starts, jnp.ones(starts.shape, bool),
            jnp.zeros(starts.shape[:-1], jnp.float32))
    (endpos, _, live), _ = jax.lax.scan(step, init, us)
    return endpos, live


def sample_walk_starts(residual: jax.Array, key: jax.Array, *,
                       num_walks: int, n: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Inverse-CDF start sampling proportional to one row's residual — the
    exact draw :func:`residual_walks` performs internally (same key split,
    same op order), factored out so the index-backed fused path samples
    starts bit-identically to the live path. Returns (starts (num_walks,),
    r_sum ())."""
    with jax.named_scope("fora.walk_starts"):
        r_sum = residual.sum()
        csum = jnp.cumsum(residual)
        k_start, _ = jax.random.split(key)
        u = jax.random.uniform(k_start, (num_walks,)) * r_sum
        starts = jnp.searchsorted(csum, u, side="left").astype(jnp.int32)
        return jnp.clip(starts, 0, n - 1), r_sum


@partial(jax.jit, static_argnames=("n", "num_walks", "num_steps", "bulk_rng",
                                   "lanes"))
def residual_walks(edge_dst: jax.Array, out_offsets: jax.Array,
                   out_degree: jax.Array, residual: jax.Array,
                   key: jax.Array, *, alpha: float, n: int,
                   num_walks: int, num_steps: int,
                   active_walks: jax.Array | None = None,
                   bulk_rng: bool | None = None,
                   lanes: int | None = None,
                   lane_offset: jax.Array | int = 0) -> WalkMass:
    """Monte-Carlo estimate of sum_v r(v) * pi(v, t) for one batch row.

    residual: (n,) non-negative. Returns the (n,) endpoint mass with the
    lane-steps the scan ran and those of them begun by a live, weighted
    lane (:class:`WalkMass`).

    ``num_walks`` is the static lane count; ``active_walks`` (traced scalar,
    1 <= active_walks <= num_walks) is the *effective* budget used by the
    fused path's on-device pow2 quantisation: walker i contributes weight
    r_sum/active_walks iff i < active_walks, zero otherwise. This keeps the
    per-row budget adaptive (matching FORA's ceil(r_sum * omega)) without a
    host sync or a shape-dependent recompile. Estimator stays unbiased:
    starts are iid ~ residual/r_sum, so E[endpoint mass] = r_sum * pi_walk
    for any positive effective count.

    ``bulk_rng`` (static) selects the bulk (L, W) draw vs per-step keys;
    callers that vmap this function over a batch MUST size the decision to
    B * L * W (this function only sees per-row shapes) — None falls back to
    the per-row heuristic.

    ``lanes``/``lane_offset`` carve this call's slice out of the global
    ``num_walks`` lane budget (the node-sharded path, DESIGN.md §9): the RNG
    stream is drawn for all num_walks lanes — so the union over shards is
    bit-identical to a single-device run *at the same num_walks* (shard
    counts dividing the pow2 budget keep it unchanged; others widen it) —
    but only lanes [lane_offset, lane_offset + lanes) are advanced through
    the graph, and weights use *global* lane ids so the active_walks cutoff
    lands on the same walkers. Callers psum the per-shard endpoint masses
    and step counts.
    """
    lanes_local = num_walks if lanes is None else lanes
    # inverse-CDF start sampling proportional to residual — the shared draw
    # (the index-backed fused path calls the same helper, so its starts are
    # bit-identical to this live path's); searchsorted is elementwise, so
    # the sharded lane slice commutes with it
    starts, r_sum = sample_walk_starts(residual, key,
                                       num_walks=num_walks, n=n)
    with jax.named_scope("fora.walk_steps"):
        _, k_walk = jax.random.split(key)
        if lanes is not None:
            starts = jax.lax.dynamic_slice_in_dim(starts, lane_offset,
                                                  lanes_local)

        deg = jnp.maximum(out_degree, 1).astype(jnp.int32)
        stop_bound = _stop_bound(alpha)
        if active_walks is None:
            weighted = None
            weights = jnp.full((lanes_local,), r_sum / num_walks,
                               residual.dtype)
        else:
            act = jnp.clip(active_walks, 1, num_walks).astype(residual.dtype)
            lane = lane_offset + jnp.arange(lanes_local)   # global lane ids
            weighted = lane < act
            weights = jnp.where(weighted, r_sum / act,
                                0.0).astype(residual.dtype)

        def advance(carry, u_step):
            pos, alive, live = carry
            live = live + _live_lanes(alive, weighted)
            return (*_advance(edge_dst, out_offsets, deg, stop_bound,
                              pos, alive, u_step), live)

        init = (starts, jnp.ones(lanes_local, bool),
                jnp.zeros((), jnp.float32))
        if bulk_rng is None:
            bulk_rng = num_steps * num_walks <= _BULK_RNG_ELEMS
        if bulk_rng:
            us = jax.random.randint(k_walk, (num_steps, num_walks), 0,
                                    1 << 30)
            if lanes is not None:
                us = jax.lax.dynamic_slice_in_dim(us, lane_offset,
                                                  lanes_local, axis=1)

            def step(carry, u_step):
                return advance(carry, u_step), None

            (endpos, _, live), _ = jax.lax.scan(step, init, us)
        else:
            def step_keyed(carry, step_key):
                u_step = jax.random.randint(step_key, (num_walks,), 0,
                                            1 << 30)
                if lanes is not None:
                    u_step = jax.lax.dynamic_slice_in_dim(
                        u_step, lane_offset, lanes_local)
                return advance(carry, u_step), None

            keys = jax.random.split(k_walk, num_steps)
            (endpos, _, live), _ = jax.lax.scan(step_keyed, init, keys)
        mass = jax.ops.segment_sum(weights, endpos, num_segments=n)
        return WalkMass(mass, live, jnp.float32(lanes_local * num_steps))


def residual_walks_batched(graph: Graph, residual: np.ndarray | jax.Array,
                           key: jax.Array, *, alpha: float,
                           num_walks: int, tail: float = 1e-4) -> WalkResult:
    """vmap over the batch axis of residual (B, n)."""
    residual = jnp.asarray(residual)
    if residual.ndim == 1:
        residual = residual[None, :]
    steps = walk_length_for_tail(alpha, tail)
    B = residual.shape[0]
    bulk = B * steps * num_walks <= _BULK_RNG_ELEMS
    keys = jax.random.split(key, B)
    fn = jax.vmap(lambda r, k: residual_walks(
        jnp.asarray(graph.edge_dst), jnp.asarray(graph.out_offsets),
        jnp.asarray(graph.out_degree), r, k, alpha=alpha, n=graph.n,
        num_walks=num_walks, num_steps=steps, bulk_rng=bulk))
    return WalkResult(endpoint_mass=fn(residual, keys).mass, walks=num_walks)


@partial(jax.jit, static_argnames=("n", "num_walks", "num_steps"))
def source_walks(edge_dst: jax.Array, out_offsets: jax.Array,
                 out_degree: jax.Array, source: jax.Array, key: jax.Array,
                 *, alpha: float, n: int, num_walks: int,
                 num_steps: int) -> jax.Array:
    """Pure Monte-Carlo PPR from a single source (baseline engine)."""
    starts = jnp.full((num_walks,), source, jnp.int32)
    residual = jnp.zeros((n,), jnp.float32).at[source].set(1.0)
    del residual  # starts fixed; reuse the step loop below
    deg = jnp.maximum(out_degree, 1).astype(jnp.int32)

    def step(carry, step_key):
        pos, alive = carry
        k_stop, k_next = jax.random.split(step_key)
        stop = jax.random.uniform(k_stop, (num_walks,)) < alpha
        u_next = jax.random.randint(k_next, (num_walks,), 0, 1 << 30)
        nxt = edge_dst[out_offsets[pos] + (u_next % deg[pos])]
        new_alive = jnp.logical_and(alive, jnp.logical_not(stop))
        return (jnp.where(new_alive, nxt, pos), new_alive), None

    keys = jax.random.split(key, num_steps)
    (endpos, _), _ = jax.lax.scan(step, (starts, jnp.ones(num_walks, bool)), keys)
    return jax.ops.segment_sum(
        jnp.full((num_walks,), 1.0 / num_walks, jnp.float32), endpos,
        num_segments=n)
