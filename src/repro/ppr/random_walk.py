"""Vectorised alpha-terminated random walks (FORA phase 2), TPU-native.

CPU FORA runs ceil(r(v) * omega) walks per residual node with geometric
lengths. TPU adaptation (DESIGN.md deviation 3):

* **Starts**: W walker start nodes are sampled proportional to the residual
  via inverse-CDF (cumsum + searchsorted) — identical in distribution to
  FORA's per-node quota in expectation, and W is static for jit.
* **Steps**: each walk takes up to L = ceil(ln(tail)/ln(1-alpha)) steps
  (truncation mass bounded by ``tail``); termination is a Bernoulli(alpha)
  draw per step (geometric length), a stopped walk stays where it is. A
  walk is live for ~1/alpha of the L steps, so the lanes are compacted as
  they die: stages of static capacity W, W/2, ... down to 1024 lanes, each
  stepping until its live lanes fit half of it (``_staged_walk``).
* **Transition**: uniform out-neighbor via CSR gather
  ``edge_dst[offsets[v] + u % deg(v)]`` — one ``jnp.take`` per step, no ELL
  padding needed, no per-step collectives in the sharded path.
* **Randomness**: ONE int32 draw per (step, walker) serves both decisions —
  ``u < floor(alpha * 2^30)`` is the Bernoulli(alpha) stop (bias < 2^-30)
  and ``u % deg`` the neighbor choice (modulo/conditioning bias O(deg/2^30)).
  Two streams: one bulk (L, W) table, selected when it fits
  ``_BULK_RNG_ELEMS``, or one (W,) draw per step from pre-split keys. Each
  lane draws its own element of the stream by global lane id
  (``walk_draws``), so a compacted lane takes the trajectory it would take
  stepped in lockstep, and no table is built.

Estimate: endpoints accumulate weight r_sum/W via segment_sum, giving the
unbiased FORA estimator  pi_hat = pi_push + sum_v r(v) * (MC endpoint dist).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

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
    steps_run: jax.Array    # () float32 lane-steps the walk loop stepped:
    #                         capacity x steps, summed over its stages


# The default stream: the bulk (num_steps, num_walks) one up to 2^25 draws,
# per-step keys beyond. The streams differ; residual_walks draws each lane's
# elements of either alone.
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


def _randint30_at(key: jax.Array, counter: jax.Array) -> jax.Array:
    """Flat elements ``counter`` (uint32) of ``jax.random.randint(key,
    shape, 0, 1 << 30)``, for any shape of fewer than 2**32 elements, drawn
    for those elements alone.

    Under partitionable threefry (``jax_threefry_partitionable``, JAX's
    default) ``random_bits`` draws flat element i as the xor of the two
    words of ``threefry2x32(k, (i >> 32, i mod 2**32))``, and ``randint``
    over [0, 2**30) keeps the low 30 bits of its second subkey's draw: the
    first subkey's draw is multiplied by 2**32 mod 2**30 = 0.
    ``tests/test_walk_compaction.py`` pins this against ``randint``."""
    if not jax.config.jax_threefry_partitionable:
        raise NotImplementedError("drawing single lanes needs "
                                  "jax_threefry_partitionable")
    _, k_low = jax.random.split(key)
    words = jax.random.key_data(k_low)
    hi, lo = threefry2x32_p.bind(words[0], words[1],
                                 jnp.zeros_like(counter), counter)
    return ((hi ^ lo) & jnp.uint32((1 << 30) - 1)).astype(jnp.int32)


def walk_draws(k_walk: jax.Array, step: jax.Array, lane_ids: jax.Array, *,
               num_walks: int, num_steps: int, bulk: bool) -> jax.Array:
    """Step ``step``'s int32 draws for the global lanes ``lane_ids`` of a
    ``num_walks``-lane walk phase: ``randint(k_walk, (num_steps,
    num_walks), 0, 2**30)[step, lane_ids]`` when ``bulk``, else
    ``randint(split(k_walk, num_steps)[step], (num_walks,), 0,
    2**30)[lane_ids]``. Elementwise in the lane id, so any subset of lanes,
    in any order, draws what the whole lane table would, and the table is
    never built."""
    ids = lane_ids.astype(jnp.uint32)
    if not bulk:
        return _randint30_at(jax.random.split(k_walk, num_steps)[step], ids)
    if num_steps * num_walks >= 1 << 32:
        raise ValueError("the bulk stream holds fewer than 2**32 draws")
    flat = (jnp.asarray(step).astype(jnp.uint32) * jnp.uint32(num_walks)
            + ids)
    return _randint30_at(k_walk, flat)


def _lane_weights(r_sum: jax.Array, lane: jax.Array, num_walks: int,
                  active_walks: jax.Array | None, dtype
                  ) -> tuple[jax.Array, jax.Array | None]:
    """Each lane's endpoint weight, and which lanes carry one (None: all):
    r_sum/num_walks on every lane, or r_sum/active_walks on the global lane
    ids ``lane`` under ``active_walks`` and 0 beyond it."""
    if active_walks is None:
        return jnp.full(lane.shape, r_sum / num_walks, dtype), None
    act = jnp.clip(active_walks, 1, num_walks).astype(dtype)
    weighted = lane < act
    return jnp.where(weighted, r_sum / act, 0.0).astype(dtype), weighted


# A compacted stage never shrinks below one (8, 128) int32 tile of lanes.
_STAGE_FLOOR = 1024
# The TPU compiler takes ~15 s over one scatter into 4M int32 lanes, and a
# fraction of a second at 3M or fewer: lanes are scattered in pieces of at
# most 2M.
_PIECE = 1 << 21


def _stage_capacities(lanes: int) -> list[int]:
    """Static lane capacities of the walk's stages: ``lanes``, halved while
    the half still holds ``_STAGE_FLOOR`` lanes."""
    caps = [lanes]
    while caps[-1] // 2 >= _STAGE_FLOOR:
        caps.append(caps[-1] // 2)
    return caps


class _Stage(NamedTuple):
    """The walk loop's state in one stage: ``cap`` lanes."""

    t: jax.Array           # () int32 steps taken
    ids: jax.Array         # (cap,) int32 lane indices
    pos: jax.Array         # (cap,) int32 positions
    live: jax.Array        # (cap,) bool: alive and carrying weight
    nlive: jax.Array       # () int32 live lanes
    steps_live: jax.Array  # () float32 lane-steps begun by a live lane
    steps_run: jax.Array   # () float32 lane-steps stepped


def _count(live: jax.Array) -> jax.Array:
    return jnp.sum(live, dtype=jnp.int32)


def _rank(live: jax.Array) -> jax.Array:
    """Inclusive count of the live lanes up to each lane, as int32: within
    rows of 128 lanes by one matmul with a triangle of ones (exact: the
    counts stay under 2**8 in bfloat16 and 2**24 in float32), then over
    the rows. The TPU compiles a cumsum over millions of lanes in ~10 s,
    this in ~1 s."""
    size = live.shape[0]
    rows = jnp.pad(live, (0, -size % 128)).reshape(-1, 128)
    tri = jnp.triu(jnp.ones((128, 128), jnp.bfloat16))
    within = jnp.dot(rows.astype(jnp.bfloat16), tri,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:size]


def _permute(x: jax.Array, dest: jax.Array) -> jax.Array:
    """``x`` with element i moved to ``dest[i]`` (a permutation)."""
    size = x.shape[0]
    pieces = []
    for lo in range(0, size, _PIECE):
        hi = min(lo + _PIECE, size)
        at = jnp.where((dest >= lo) & (dest < hi), dest - lo, hi - lo)
        pieces.append(jnp.zeros(hi - lo, x.dtype).at[at].set(x, mode="drop"))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def _compact(s: _Stage, keep: int) -> tuple[_Stage, tuple[jax.Array,
                                                         jax.Array]]:
    """The stage's live lanes first, in order, then the rest; ``keep``
    lanes stay, and the lanes past ``keep`` leave with their (index, pos).
    No live lane leaves while at most ``keep`` lanes live."""
    rank = _rank(s.live)                            # live lanes up to here
    lane = jnp.arange(s.ids.shape[0], dtype=jnp.int32)
    dest = jnp.where(s.live, rank - 1, s.nlive + lane - rank)
    ids, pos = _permute(s.ids, dest), _permute(s.pos, dest)
    live = lane[:keep] < s.nlive
    kept = s._replace(ids=ids[:keep], pos=pos[:keep], live=live,
                      nlive=_count(live))
    return kept, (ids[keep:], pos[keep:])


def _staged_walk(edge_dst: jax.Array, out_offsets: jax.Array,
                 out_degree: jax.Array, starts: jax.Array, live: jax.Array,
                 draw, *, alpha: float, num_steps: int
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Advance the lanes from ``starts`` for ``num_steps`` steps, stepping
    only lanes that may still be live (``live``: alive and carrying
    weight). Stages of static capacity halve while their lanes die: a
    stage steps until its live lanes fit half of it, then compacts them
    into the next; the last stage steps to the end. ``draw(t, idx)``
    gives the lanes at indices ``idx`` their step-t draws, so every lane
    follows its lockstep trajectory. Returns the endpoints in lane order,
    the lane-steps begun by a live lane and the lane-steps stepped."""
    deg = jnp.maximum(out_degree, 1).astype(jnp.int32)
    bound = _stop_bound(alpha)
    caps = _stage_capacities(starts.shape[0])

    def stepper(cap: int):
        def step(s: _Stage) -> _Stage:
            pos, now = _advance(edge_dst, out_offsets, deg, bound, s.pos,
                                s.live, draw(s.t, s.ids))
            return _Stage(s.t + 1, s.ids, pos, now, _count(now),
                          s.steps_live + s.nlive.astype(jnp.float32),
                          s.steps_run + jnp.float32(cap))
        return step

    def running(cap: int, last: bool):
        def go(s: _Stage) -> jax.Array:
            more = s.t < num_steps
            return more if last else more & (s.nlive > cap // 2)
        return go

    s = _Stage(jnp.int32(0), jnp.arange(starts.shape[0], dtype=jnp.int32),
               starts, live, _count(live),
               jnp.float32(0.0), jnp.float32(0.0))
    left = []
    for i, cap in enumerate(caps):
        last = i + 1 == len(caps)
        s = jax.lax.while_loop(running(cap, last), stepper(cap), s)
        if not last:
            s, out = _compact(s, caps[i + 1])
            left.append(out)
    if not left:
        return s.pos, s.steps_live, s.steps_run
    # every lane's endpoint back in its own slot, for one segment_sum in
    # lane order
    order = jnp.concatenate([i for i, _ in left] + [s.ids])
    ends = jnp.concatenate([p for _, p in left] + [s.pos])
    return _permute(ends, order), s.steps_live, s.steps_run


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
    lane-steps the walk loop stepped and those of them begun by a live,
    weighted lane (:class:`WalkMass`).

    ``num_walks`` is the static lane count; ``active_walks`` (traced scalar,
    1 <= active_walks <= num_walks) is the *effective* budget used by the
    fused path's on-device pow2 quantisation: walker i contributes weight
    r_sum/active_walks iff i < active_walks, zero otherwise. This keeps the
    per-row budget adaptive (matching FORA's ceil(r_sum * omega)) without a
    host sync or a shape-dependent recompile. Estimator stays unbiased:
    starts are iid ~ residual/r_sum, so E[endpoint mass] = r_sum * pi_walk
    for any positive effective count.

    The lanes are compacted as they die (:func:`_staged_walk`): a lane that
    stops, or carries no weight, leaves the loop once half of a stage's
    lanes have, and each lane draws its own step draws by global id
    (:func:`walk_draws`). Every lane takes the trajectory the lockstep
    oracle :func:`lockstep_residual_walks` gives it, so the mass and
    ``steps_live`` equal the oracle's bit for bit; ``steps_run`` counts the
    lane-steps the stages stepped.

    ``bulk_rng`` (static) selects the bulk (L, W) stream vs per-step keys;
    callers that vmap this function over a batch MUST size the decision to
    B * L * W (this function only sees per-row shapes) — None falls back to
    the per-row heuristic.

    ``lanes``/``lane_offset`` carve this call's slice out of the global
    ``num_walks`` lane budget (the node-sharded path, DESIGN.md §9): lanes
    [lane_offset, lane_offset + lanes) are walked on the draws of their
    global ids, so the union over shards is bit-identical to a
    single-device run *at the same num_walks* (shard counts dividing the
    pow2 budget keep it unchanged; others widen it), and weights use
    *global* lane ids so the active_walks cutoff lands on the same walkers.
    Callers psum the per-shard endpoint masses and step counts.
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
        lane = lane_offset + jnp.arange(lanes_local)   # global lane ids
        weights, weighted = _lane_weights(r_sum, lane, num_walks,
                                          active_walks, residual.dtype)
        if bulk_rng is None:
            bulk_rng = num_steps * num_walks <= _BULK_RNG_ELEMS

        def draw(t, idx):
            return walk_draws(k_walk, t, lane_offset + idx,
                              num_walks=num_walks, num_steps=num_steps,
                              bulk=bulk_rng)

        live = (jnp.ones(lanes_local, bool) if weighted is None
                else weighted)
        endpos, steps_live, steps_run = _staged_walk(
            edge_dst, out_offsets, out_degree, starts, live, draw,
            alpha=alpha, num_steps=num_steps)
        mass = jax.ops.segment_sum(weights, endpos, num_segments=n)
        return WalkMass(mass, steps_live, steps_run)


def lockstep_residual_walks(edge_dst: jax.Array, out_offsets: jax.Array,
                            out_degree: jax.Array, residual: jax.Array,
                            key: jax.Array, *, alpha: float, n: int,
                            num_walks: int, num_steps: int,
                            active_walks: jax.Array | None = None,
                            bulk_rng: bool | None = None,
                            lanes: int | None = None,
                            lane_offset: jax.Array | int = 0) -> WalkMass:
    """The oracle :func:`residual_walks` is held to, with its signature:
    the step draws drawn whole with ``jax.random.randint`` ((num_steps,
    num_walks) int32, so memory-hungry at large budgets), every lane
    stepped ``num_steps`` times in lockstep (:func:`counted_walk_endpoints`)
    and the endpoints summed by weight."""
    lanes_local = num_walks if lanes is None else lanes
    starts, r_sum = sample_walk_starts(residual, key,
                                       num_walks=num_walks, n=n)
    _, k_walk = jax.random.split(key)
    if bulk_rng is None:
        bulk_rng = num_steps * num_walks <= _BULK_RNG_ELEMS
    if bulk_rng:
        us = jax.random.randint(k_walk, (num_steps, num_walks), 0, 1 << 30)
    else:
        us = jax.vmap(lambda k: jax.random.randint(k, (num_walks,), 0,
                                                   1 << 30))(
            jax.random.split(k_walk, num_steps))
    starts = jax.lax.dynamic_slice_in_dim(starts, lane_offset, lanes_local)
    us = jax.lax.dynamic_slice_in_dim(us, lane_offset, lanes_local, axis=1)
    lane = lane_offset + jnp.arange(lanes_local)
    weights, weighted = _lane_weights(r_sum, lane, num_walks, active_walks,
                                      residual.dtype)
    endpos, live = counted_walk_endpoints(edge_dst, out_offsets, out_degree,
                                          starts, us, alpha=alpha,
                                          weighted=weighted)
    return WalkMass(jax.ops.segment_sum(weights, endpos, num_segments=n),
                    live, jnp.float32(lanes_local * num_steps))


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
