"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are the *semantic definitions*; kernels must match them over the test
sweep (shapes x dtypes). They are also the CPU fallback used by ops.py when
no TPU is present.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q, k, v, *, causal: bool = True, q_offset: int = 0):
    """Naive softmax(QK^T/sqrt(d))V with GQA head folding. fp32 internals."""
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    group = Hq // Hkv
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(Dh)
    if causal:
        qi = jnp.arange(Sq) + q_offset
        ki = jnp.arange(Skv)
        s = jnp.where(qi[:, None] >= ki[None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def ell_spmv_ref(neighbors, mask, x, weights=None):
    """Pull-form ELL SpMV: y[i] = sum_j mask[i,j] * w[i,j] * x[neighbors[i,j]].

    neighbors/mask: (n, K); x: (n,) or (n, c); weights: (n, K) or None (=1).
    This is FORA's push relaxation read as a gather (DESIGN.md §5): with
    neighbors = in-edge lists and w = 1/deg_out(src), y = P^T x.
    """
    gathered = x[neighbors]                       # (n, K) or (n, K, c)
    w = mask.astype(x.dtype)
    if weights is not None:
        w = w * weights.astype(x.dtype)
    if gathered.ndim == 3:
        return jnp.einsum("nk,nkc->nc", w, gathered)
    return jnp.sum(w * gathered, axis=1)


def ell_spmm_ref(neighbors, mask, x, weights=None, threshold=None):
    """Batched pull-form ELL SpMM: the (B, n) generalisation of ell_spmv_ref.

        y[b, i] = sum_j mask[i,j] * w[i,j] * f(x[b, neighbors[i,j]])

    where f is identity, or — with ``threshold`` (n,) — FORA's fused push
    selection f(v) = v * [v > threshold[src]] (DESIGN.md §7): feeding the raw
    residual r and the per-node push threshold yields P^T (r * front) without
    materialising the frontier between sweeps. Here the selection is
    applied to x before the gather — the same values, with one (n, K)
    gather per call instead of two.
    """
    if threshold is not None:
        x = jnp.where(x > threshold[None, :], x, 0.0)
    gathered = x[:, neighbors]                    # (B, n, K)
    w = mask.astype(x.dtype)
    if weights is not None:
        w = w * weights.astype(x.dtype)
    return jnp.einsum("nk,bnk->bn", w, gathered)


def ell_spmm_sliced_ref(neighbors, mask, x, weights=None, threshold=None,
                        row_map=None):
    """Sliced-ELL pull-form SpMM (DESIGN.md §8): virtual-row partials via
    :func:`ell_spmm_ref` (gather indices are global node ids, so the dense
    oracle applies row-wise unchanged), folded onto the real rows with a
    ``segment_sum`` over ``row_map``.

        y[b, i] = sum_{v: row_map[v]=i} sum_j mask[v,j]*w[v,j]*f(x[b, nbr[v,j]])

    neighbors/mask/weights: (n_virtual, W); row_map: (n_virtual,) int32
    ascending; x: (B, n). Returns (B, n).
    """
    if row_map is None:
        raise ValueError("row_map is required for the sliced oracle")
    partials = ell_spmm_ref(neighbors, mask, x, weights, threshold)  # (B, nv)
    folded = jax.ops.segment_sum(partials.T, row_map,
                                 num_segments=x.shape[1],
                                 indices_are_sorted=True)
    return folded.T


def walk_endpoint_gather_ref(endpoints, budget, starts, weights):
    """Index-backed walk aggregation (DESIGN.md §11): lane i of query b reads
    the stored endpoint ``endpoints[starts[b,i], i]`` and scatters its
    residual weight onto that node, provided the node's stored budget covers
    the lane:

        out[b, t] = sum_i w[b,i] * [i < budget[starts[b,i]]]
                              * [endpoints[starts[b,i], i] == t]

    endpoints: (n, W) int32; budget: (n,) int32; starts: (B, L<=W) int32;
    weights: (B, L) f32. Returns (B, n) f32.
    """
    n = endpoints.shape[0]
    L = starts.shape[1]
    lane = jnp.arange(L, dtype=jnp.int32)
    e = endpoints[starts, lane[None, :]]            # (B, L)
    valid = lane[None, :] < budget[starts]
    w = weights.astype(jnp.float32) * valid
    return jax.vmap(lambda eb, wb: jax.ops.segment_sum(
        wb, eb, num_segments=n))(e, w)


def embedding_bag_ref(table, ids, weights=None):
    """EmbeddingBag(sum): out[b] = sum_l w[b,l] * table[ids[b,l]].

    table: (V, d); ids: (B, L); weights: (B, L) or None. The DIN interest
    pooling op (taxonomy §RecSys: jnp.take + weighted segment reduction)."""
    rows = jnp.take(table, ids, axis=0)           # (B, L, d)
    if weights is None:
        return rows.sum(axis=1)
    return jnp.einsum("bl,bld->bd", weights.astype(table.dtype), rows)
