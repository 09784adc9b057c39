"""Jit'd dispatch wrappers: the Pallas kernel where it lowers for the TPU,
the jnp (XLA) oracle elsewhere.

Call sites use these; the backend decision happens once at trace time and
is recorded in :data:`IMPLS`. The rule is unconditional and per op: on the
TPU an op runs its compiled Pallas kernel iff it is in
:data:`TPU_KERNELS`; every other op runs as XLA there. Off the TPU every op
runs as XLA. ``force="pallas"`` overrides for tests: it runs the kernel,
interpreted off the TPU and compiled on it — a kernel never runs in
interpret mode on the TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .ell_spmv import (ell_spmm_pallas, ell_spmm_sliced_pallas,
                       ell_spmv_pallas)
from .embedding_bag import embedding_bag_pallas
from .flash_attention import flash_attention_pallas
from .walk_gather import walk_endpoint_gather_pallas

# Ops whose Pallas kernel compiles for the TPU (tests/test_tpu_compile.py).
# The ELL SpMV/SpMM kernels are not among them: Mosaic refuses their
# in-kernel gather from a VMEM-resident x at any n, and that x outgrows VMEM
# at Table-I n (ROADMAP S0) — so on the TPU the push sweep runs as XLA.
# ``embedding_bag``'s body still slices loaded values (``dynamic_slice``),
# which Mosaic does not lower either.
TPU_KERNELS = frozenset({"walk_endpoint_gather", "flash_attention"})

# op name -> implementation its last trace chose: "pallas" (compiled for
# the TPU), "pallas-interpret" (force="pallas" off the TPU) or "xla"
IMPLS: dict[str, str] = {}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tpu_kernel(op: str) -> bool:
    """Whether ``op`` runs its compiled Pallas kernel by default: on the
    TPU, for the ops in :data:`TPU_KERNELS`."""
    return _on_tpu() and op in TPU_KERNELS


def _use_pallas(op: str, force: str | None) -> bool:
    """Apply the dispatch rule for ``op`` and record the choice."""
    use = force == "pallas" or (force is None and tpu_kernel(op))
    IMPLS[op] = ("pallas" if _on_tpu() else "pallas-interpret") if use \
        else "xla"
    return use


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    force: str | None = None):
    if _use_pallas("flash_attention", force):
        return flash_attention_pallas(q, k, v, causal=causal,
                                      q_offset=q_offset,
                                      interpret=not _on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal, q_offset=q_offset)


def ell_spmv(neighbors, mask, weights, x, *, force: str | None = None):
    if _use_pallas("ell_spmv", force):
        return ell_spmv_pallas(neighbors, mask, weights, x,
                               interpret=not _on_tpu())
    return ref.ell_spmv_ref(neighbors, mask, x, weights)


def ell_spmm(neighbors, mask, weights, x, *, threshold=None,
             force: str | None = None, block_n: int = 256):
    """Batched (B, n) pull-form SpMM; ``threshold`` fuses FORA's push
    condition into the gather (see ell_spmv.ell_spmm_pallas). ``block_n``
    is the Pallas row-tile (autotunable, numerics-neutral — DESIGN.md §15);
    the jnp oracle ignores it."""
    if _use_pallas("ell_spmm", force):
        return ell_spmm_pallas(neighbors, mask, weights, x, threshold,
                               block_n=block_n, interpret=not _on_tpu())
    return ref.ell_spmm_ref(neighbors, mask, x, weights, threshold)


def ell_spmm_sliced(neighbors, mask, weights, row_map, x, *, threshold=None,
                    force: str | None = None, block_n: int = 256):
    """Sliced-ELL batched SpMM: virtual rows (n_virtual, W) with the
    ``row_map`` fold fused in-kernel (DESIGN.md §8, §15); drop-in for
    :func:`ell_spmm` on graphs whose dense (n, k_max) table would not fit
    memory. ``block_n`` tiles virtual rows (autotunable, numerics-neutral);
    the jnp oracle ignores it."""
    if _use_pallas("ell_spmm_sliced", force):
        return ell_spmm_sliced_pallas(neighbors, mask, weights, row_map, x,
                                      threshold, block_n=block_n,
                                      interpret=not _on_tpu())
    return ref.ell_spmm_sliced_ref(neighbors, mask, x, weights, threshold,
                                   row_map)


def ell_spmm_shard(neighbors, mask, weights, x, *, axis_name: str,
                   threshold=None, force: str | None = None,
                   block_n: int = 256):
    """Per-shard dense SpMM under ``shard_map`` (DESIGN.md §9): each shard
    holds a contiguous block of destination rows; gather indices are global
    node ids and ``x``/``threshold`` are replicated, so the local block is a
    plain :func:`ell_spmm`. The (B, rows_local) blocks are reassembled in row
    order with one tiled all-gather — returns (B, num_shards * rows_local);
    the caller slices off any row padding."""
    local = ell_spmm(neighbors, mask, weights, x, threshold=threshold,
                     force=force, block_n=block_n)
    return jax.lax.all_gather(local, axis_name, axis=1, tiled=True)


def ell_spmm_sliced_shard(neighbors, mask, weights, row_map, x, *,
                          axis_name: str, threshold=None,
                          force: str | None = None, block_n: int = 256):
    """Per-shard sliced SpMM under ``shard_map`` (DESIGN.md §9): the table is
    sharded by *virtual* row, so each shard folds its local slice partials
    onto the full (B, n) frame through its local ``row_map`` in-kernel fold
    (:func:`ell_spmm_sliced` unchanged — ids are global), and the partial
    frames combine with one ``psum`` all-reduce. Returns (B, n)."""
    partial = ell_spmm_sliced(neighbors, mask, weights, row_map, x,
                              threshold=threshold, force=force,
                              block_n=block_n)
    return jax.lax.psum(partial, axis_name)


def walk_endpoint_gather(endpoints, budget, starts, weights, *,
                         force: str | None = None):
    """Index-backed walk-phase aggregation (DESIGN.md §11): serve each
    covered lane's endpoint from the pre-drawn (n, W) table and fold the
    residual-weighted endpoint mass onto the (B, n) PPR frame — the walk
    phase without walking. Lanes whose start node's stored ``budget`` does
    not cover them contribute zero (the live shortfall draw owns them)."""
    if _use_pallas("walk_endpoint_gather", force):
        return walk_endpoint_gather_pallas(endpoints, budget, starts,
                                           weights, interpret=not _on_tpu())
    return ref.walk_endpoint_gather_ref(endpoints, budget, starts, weights)


def embedding_bag(table, ids, weights, *, force: str | None = None):
    if _use_pallas("embedding_bag", force):
        return embedding_bag_pallas(table, ids, weights,
                                    interpret=not _on_tpu())
    return ref.embedding_bag_ref(table, ids, weights)


# ---------------------------------------------------------------------------
# dynamic-graph delta application (DESIGN.md §16)
#
# Both ops below run entirely device-side: the host uploads only the small
# per-batch delta arrays (padded to fixed caps so repeat batches hit the jit
# cache) and the O(table) rewrite happens on device — the residency is never
# re-uploaded between compactions. Free/padding slots carry the sentinel
# row_map/src value ``n``: the sliced SpMM's segment fold drops ids >= n
# (ref path: out-of-range segment ids are dropped; Pallas path: they land in
# the (n+1)-row dump block), so spare capacity is numerically inert.


@jax.jit
def push_delta_apply(neighbors, mask, row_map, inv_out,
                     add_nbr, add_mask, add_rm,
                     rem_src, rem_dst, deg_nodes, deg_inv, cursor):
    """Apply one edge-update batch to the sliced pull-form push table.

    State (capacity C >= used rows, ascending ``row_map`` with sentinel-``n``
    free rows at the tail): ``neighbors``/``mask`` (C, W), ``row_map`` (C,),
    ``inv_out`` (n,) f32 = 1/max(deg_out, 1) per node. Delta (fixed caps):
    ``add_*`` (A, W)/(A,) new virtual rows written at ``cursor`` (padding
    rows: mask False, row_map n); ``rem_src``/``rem_dst`` (R,) removed edges
    (padding -1, never matches); ``deg_nodes``/``deg_inv`` (R2,) scatter of
    host-recomputed inverse out-degrees (padding index n, dropped).

    Removals weight-zero their cells (mask off), additions append virtual
    rows, then a stable re-sort by ``row_map`` restores the ascending
    contract every sliced-SpMM consumer assumes, and the full weight table
    is re-derived as ``inv_out[neighbors] * mask`` — the same gather-multiply
    ``Graph.ell_in_sliced`` runs in numpy, so unchanged cells keep their
    fresh-build bits exactly.
    """
    inv_out = inv_out.at[deg_nodes].set(deg_inv, mode="drop")

    def drop_one(k, m):
        hit = (row_map == rem_dst[k])[:, None] & (neighbors == rem_src[k])
        return m & ~hit

    mask = jax.lax.fori_loop(0, rem_src.shape[0], drop_one, mask)
    neighbors = jax.lax.dynamic_update_slice(neighbors, add_nbr, (cursor, 0))
    mask = jax.lax.dynamic_update_slice(mask, add_mask, (cursor, 0))
    row_map = jax.lax.dynamic_update_slice(row_map, add_rm, (cursor,))
    order = jnp.argsort(row_map, stable=True)
    neighbors = neighbors[order]
    mask = mask[order]
    row_map = row_map[order]
    weights = inv_out[neighbors] * mask
    return neighbors, mask, weights, row_map, inv_out


@partial(jax.jit, static_argnames=("n",))
def walk_delta_apply(edge_src, edge_dst, alive,
                     add_src, add_dst, add_alive,
                     rem_src, rem_dst, cursor, *, n: int):
    """Apply one edge-update batch to the CSR walk view, device-side.

    State (capacity E >= live edges): ``edge_src``/``edge_dst`` (E,) int32
    with an ``alive`` (E,) mask — removed edges are tombstoned in place,
    additions written at ``cursor`` (padding slots: src n, alive False).
    A two-pass stable argsort (by dst, then by src-with-dead-keyed-to-``n``)
    re-groups the LIVE edges exactly as ``Graph.from_edges`` lays them out:
    grouped by source, destination-ascending within each group, dead and
    spare slots pushed past the live prefix. Because the live (src, dst)
    pairs are duplicate-free, that order is unique — the live prefix of
    ``edge_dst`` is bit-identical to a fresh host build, so uniform
    out-neighbor sampling (``edge_dst[offsets[v] + u % deg(v)]``) draws the
    SAME walks a rebuilt-from-scratch graph would.

    Returns (edge_src, edge_dst, alive, out_offsets (n+1,), out_degree (n,)).
    """
    hit = ((edge_src[:, None] == rem_src[None, :]) &
           (edge_dst[:, None] == rem_dst[None, :]))
    alive = alive & ~hit.any(axis=1)
    edge_src = jax.lax.dynamic_update_slice(edge_src, add_src, (cursor,))
    edge_dst = jax.lax.dynamic_update_slice(edge_dst, add_dst, (cursor,))
    alive = jax.lax.dynamic_update_slice(alive, add_alive, (cursor,))
    key_src = jnp.where(alive, edge_src, n)
    o1 = jnp.argsort(edge_dst, stable=True)
    o2 = jnp.argsort(key_src[o1], stable=True)
    order = o1[o2]
    edge_src = edge_src[order]
    edge_dst = edge_dst[order]
    alive = alive[order]
    out_degree = jnp.zeros((n,), jnp.int32).at[edge_src].add(
        alive.astype(jnp.int32), mode="drop")
    out_offsets = jnp.zeros((n + 1,), jnp.int32).at[1:].set(
        jnp.cumsum(out_degree))
    return edge_src, edge_dst, alive, out_offsets, out_degree
