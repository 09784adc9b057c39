"""Pallas TPU walk-endpoint gather — index-backed FORA walks (DESIGN.md §11).

The :class:`repro.index.WalkIndex` stores, per node, a budgeted table of
pre-drawn random-walk endpoints (``endpoints (n, W) int32``, per-node valid
lane count ``budget (n,)``). At query time the fused FORA path samples walk
*starts* from the push residual exactly as the live path does, then — instead
of stepping L transitions through the CSR arrays — serves each covered lane
from the table and aggregates the endpoint mass:

    out[b, t] = sum_i  weights[b, i]
                       * [i < budget[starts[b, i]]]
                       * [endpoints[starts[b, i], i] == t]

``weights`` carry FORA's residual weighting (r_sum / w_eff on active lanes),
so this op IS the walk phase for index-covered lanes. Lanes failing the
budget test contribute zero here; the caller routes them through the live
shortfall draw (:func:`repro.ppr.random_walk.walk_endpoints`).

Kernel shape: the per-lane table row gather (an XLA gather, grid-invariant)
happens in the wrapper; the Pallas body does the scatter-free aggregation —
output rows are VMEM-tiled in blocks of ``block_n`` and each block
accumulates a compare-and-sum one-hot contraction over 128-lane chunks
(endpoint ids vs the block's node iota), keeping the (B, chunk, block_n)
compare/multiply on the VPU instead of serialising a segment scatter.
Lane chunks are sliced from the refs (``pl.ds``), which Mosaic lowers; the
kernel compiles for v5e at Table-I n (``tests/test_tpu_compile.py``).
Validated in interpret mode against :func:`repro.kernels.ref.walk_endpoint_gather_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gather_kernel(e_ref, w_ref, out_ref, *, l_chunks: int, chunk: int,
                   bn: int):
    # e_ref: (B, Lp) int32 endpoints; w_ref: (B, Lp) f32 weights
    base = pl.program_id(0) * bn
    # node ids of this output block, on the lane axis of the compare
    t_ids = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bn), 2)

    def body(c, acc):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        ec = e_ref[:, lanes]
        wc = w_ref[:, lanes]
        onehot = (ec[:, :, None] == t_ids).astype(jnp.float32)  # (B, c, bn)
        return acc + jnp.sum(wc[:, :, None] * onehot, axis=1)

    acc0 = jnp.zeros((e_ref.shape[0], bn), jnp.float32)
    out_ref[...] = jax.lax.fori_loop(0, l_chunks, body, acc0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def walk_endpoint_gather_pallas(endpoints, budget, starts, weights, *,
                                interpret: bool, block_n: int = 256):
    """Aggregate stored walk endpoints weighted by push residuals.

    endpoints: (n, W) int32 pre-drawn endpoint table; budget: (n,) int32
    valid lane count per node; starts: (B, L) int32 walk start nodes
    (L <= W, lane i reads table column i); weights: (B, L) f32 residual
    weights. Returns (B, n) f32 endpoint mass; lanes with
    ``i >= budget[start]`` contribute zero (the caller's live-draw
    fallback owns them).
    """
    n = endpoints.shape[0]
    B, L = starts.shape
    lane = jnp.arange(L, dtype=jnp.int32)
    e = endpoints[starts, lane[None, :]]            # (B, L) stored endpoints
    valid = lane[None, :] < budget[starts]
    w = weights.astype(jnp.float32) * valid

    chunk = 128
    Lp = -(-L // chunk) * chunk
    if Lp != L:
        # padding lanes: weight 0, endpoint 0 — contribute nothing
        e = jnp.pad(e, ((0, 0), (0, Lp - L)))
        w = jnp.pad(w, ((0, 0), (0, Lp - L)))
    bn = min(block_n, n)
    nb = -(-n // bn)

    kernel = functools.partial(_gather_kernel, l_chunks=Lp // chunk,
                               chunk=chunk, bn=bn)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((B, Lp), lambda i: (0, 0)),   # endpoints resident
            pl.BlockSpec((B, Lp), lambda i: (0, 0)),   # weights resident
        ],
        out_specs=pl.BlockSpec((B, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, nb * bn), jnp.float32),
        interpret=interpret,
    )(e, w)
    return out[:, :n]
