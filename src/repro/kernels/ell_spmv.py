"""Pallas TPU ELL SpMV/SpMM — FORA's push relaxation as a gather kernel.

Pull formulation (DESIGN.md §5): the frontier-synchronous push
``r' = P^T (spread)`` becomes, per destination node i,

    y[i] = sum_j  mask[i,j] * w[i,j] * x[neighbors[i,j]]

over the padded in-neighbor table (n, K). Rows are VMEM-tiled in blocks of
``block_n`` (sublane axis) with the full K width resident (lane axis, padded
to 128); the source vector x stays VMEM-resident per block step — on TPU the
graph is node-sharded so each shard's x slice is its local residual
(<= a few MB), which is what makes the gather a VMEM-local dynamic-index
load rather than an HBM scatter. One fori_loop accumulates K in chunks of
128 lanes, keeping the (block_n, 128) gather/multiply on the VPU.

``ell_spmm_pallas`` is the batched generalisation serving the fused FORA hot
path (DESIGN.md §7): x is a (B, n) residual block, carried through the kernel
transposed as (n, B) so the query batch rides the lane axis while rows stay
on the sublane axis. It optionally fuses FORA's push condition: with a
per-source ``threshold`` vector, gathered values x[nbr] are zeroed unless
x[nbr] > threshold[nbr], i.e. the kernel consumes the *raw* residual and
applies front/spread selection in-register instead of materialising
``r * front`` in HBM between sweeps.

``ell_spmm_sliced_pallas`` is the power-law-safe variant (DESIGN.md §8): the
same kernel body runs over *virtual* rows of a sliced ELL table (high-degree
rows split into width-<=W slices by ``Graph.ell_in_sliced``), and the slice
partials are folded back onto real rows INSIDE the kernel (DESIGN.md §15):
``row_map`` is sorted ascending, so a sequential per-row accumulate over the
grid's virtual-row blocks is the same ascending left-fold a sorted
``segment_sum`` performs — bit-identical to the former host-side fold, with
no (n_virtual, B) partial frame ever materialised in HBM. Gather indices are
global node ids, so the resident source vector, the fused threshold
semantics and the partial computation are identical to the dense variant —
only the row axis is virtualised.

Also used by the GNN SpMM regime (GCN's \\hat{A} X when X is a vector batch).
Validated in interpret mode against ref.ell_spmv_ref / ref.ell_spmm_ref.

These kernels do not lower for the TPU yet: Mosaic refuses the in-kernel
``jnp.take`` gather from the VMEM-resident x (at any n), and at Table-I n
that resident x would not fit VMEM anyway (ROADMAP S0). So
:mod:`repro.kernels.ops` runs the SpMMs as XLA on the TPU, and these bodies
run only interpreted, under ``force="pallas"`` off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_chunk(c, chunk: int):
    """Columns [c*chunk, (c+1)*chunk) of a (rows, Kp) block, as a ref slice."""
    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _ell_kernel(nbr_ref, mask_ref, w_ref, x_ref, y_ref, *, k_chunks: int,
                chunk: int):
    x = x_ref[...]                                    # (n,) f32 (vector)

    def body(c, acc):
        cols = _lane_chunk(c, chunk)
        idx = nbr_ref[:, cols]                        # (bn, chunk) int32
        vals = jnp.take(x, idx, axis=0)               # VMEM gather
        wts = w_ref[:, cols] * mask_ref[:, cols].astype(vals.dtype)
        return acc + jnp.sum(vals * wts, axis=1)

    acc0 = jnp.zeros((nbr_ref.shape[0],), jnp.float32)
    y_ref[...] = jax.lax.fori_loop(0, k_chunks, body, acc0)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret"))
def ell_spmv_pallas(neighbors, mask, weights, x, *, interpret: bool,
                    block_n: int = 256):
    """y[i] = sum_j mask*w*x[neighbors[i,j]].  neighbors/mask/weights: (n,K);
    x: (n,) float32. Returns (n,) float32."""
    n, K = neighbors.shape
    chunk = 128
    Kp = -(-K // chunk) * chunk
    bn = min(block_n, n)
    nb = -(-n // bn)
    n_pad = nb * bn - n
    if Kp != K:
        neighbors = jnp.pad(neighbors, ((0, 0), (0, Kp - K)))
        mask = jnp.pad(mask, ((0, 0), (0, Kp - K)))
        weights = jnp.pad(weights, ((0, 0), (0, Kp - K)))
    if n_pad:
        neighbors = jnp.pad(neighbors, ((0, n_pad), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad), (0, 0)))
        weights = jnp.pad(weights, ((0, n_pad), (0, 0)))

    kernel = functools.partial(_ell_kernel, k_chunks=Kp // chunk, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),       # x resident per step
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb * bn,), jnp.float32),
        interpret=interpret,
    )(neighbors, mask, weights.astype(jnp.float32), x.astype(jnp.float32))
    return y[:n]


def _spmm_partials(nbr_ref, mask_ref, w_ref, xT_ref, thr_ref, *,
                   k_chunks: int, chunk: int, fuse_threshold: bool):
    """(bn, B) per-row partial sums — the shared SpMM body. Bit-identical
    between the dense and sliced-fold kernels by construction (DESIGN.md §15:
    the in-kernel fold only changes where partials land, never their value)."""
    xT = xT_ref[...]                                  # (n, B) f32, B on lanes

    def body(c, acc):
        cols = _lane_chunk(c, chunk)
        idx = nbr_ref[:, cols]                        # (bn, chunk) int32
        vals = jnp.take(xT, idx, axis=0)              # (bn, chunk, B) gather
        if fuse_threshold:
            thr = jnp.take(thr_ref[...], idx, axis=0)  # (bn, chunk)
            vals = jnp.where(vals > thr[..., None], vals, 0.0)
        wts = w_ref[:, cols] * mask_ref[:, cols].astype(vals.dtype)
        return acc + jnp.sum(vals * wts[..., None], axis=1)

    acc0 = jnp.zeros((nbr_ref.shape[0], xT.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, k_chunks, body, acc0)


def _ell_spmm_kernel(nbr_ref, mask_ref, w_ref, xT_ref, thr_ref, yT_ref, *,
                     k_chunks: int, chunk: int, fuse_threshold: bool):
    yT_ref[...] = _spmm_partials(nbr_ref, mask_ref, w_ref, xT_ref, thr_ref,
                                 k_chunks=k_chunks, chunk=chunk,
                                 fuse_threshold=fuse_threshold)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret"))
def ell_spmm_pallas(neighbors, mask, weights, x, threshold=None, *,
                    interpret: bool, block_n: int = 256):
    """Batched pull-form SpMM: y[b, i] = sum_j mask*w*x[b, neighbors[i,j]].

    neighbors/mask/weights: (n, K); x: (B, n) float32 — the batch rides the
    lane axis inside the kernel as x^T (n, B). With ``threshold`` (n,) the
    FORA push condition is fused: gathered x[b, src] contributes only where
    it exceeds threshold[src]. Returns (B, n) float32.
    """
    n = neighbors.shape[0]
    yT = _spmm_virtual_rows(neighbors, mask, weights, x, threshold,
                            block_n=block_n, interpret=interpret)
    return yT[:n].T


def _spmm_virtual_rows(neighbors, mask, weights, x, threshold, *,
                       block_n: int, interpret: bool):
    """The (B, n_rows) SpMM over an arbitrary row table whose gather indices
    address the full (n,)-resident x — shared by the dense and sliced
    wrappers. Returns yT (n_rows_padded, B) float32 (padding rows trail)."""
    n_rows, K = neighbors.shape
    n = x.shape[1]
    B = x.shape[0]
    chunk = 128
    Kp = -(-K // chunk) * chunk
    bn = min(block_n, n_rows)
    nb = -(-n_rows // bn)
    n_pad = nb * bn - n_rows
    if Kp != K:
        neighbors = jnp.pad(neighbors, ((0, 0), (0, Kp - K)))
        mask = jnp.pad(mask, ((0, 0), (0, Kp - K)))
        weights = jnp.pad(weights, ((0, 0), (0, Kp - K)))
    if n_pad:
        neighbors = jnp.pad(neighbors, ((0, n_pad), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad), (0, 0)))
        weights = jnp.pad(weights, ((0, n_pad), (0, 0)))

    fuse = threshold is not None
    if not fuse:
        threshold = jnp.zeros((n,), jnp.float32)
    kernel = functools.partial(_ell_spmm_kernel, k_chunks=Kp // chunk,
                               chunk=chunk, fuse_threshold=fuse)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((n, B), lambda i: (0, 0)),   # x^T resident per step
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bn, B), jnp.float32),
        interpret=interpret,
    )(neighbors, mask, weights.astype(jnp.float32),
      x.astype(jnp.float32).T, threshold.astype(jnp.float32))


def _ell_spmm_fold_kernel(nbr_ref, mask_ref, w_ref, rm_ref, xT_ref, thr_ref,
                          yT_ref, part_ref, *, k_chunks: int, chunk: int,
                          fuse_threshold: bool, bn: int):
    """Sliced-ELL SpMM with the virtual-row fold fused in (DESIGN.md §15).

    The (n+1, B) output block has a constant index map, so it stays resident
    across the sequential grid steps: step 0 zeroes it, every step adds its
    block's per-virtual-row partials (staged in the ``part_ref`` scratch)
    onto real rows one virtual row at a time, in ascending virtual-row
    order. ``row_map`` is sorted ascending, so this is the exact f32
    left-fold a sorted ``segment_sum`` performs — bit-identical to the
    former host-side fold. Padded virtual rows carry row_map == n and land
    on the dump row the wrapper slices off.
    """
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        yT_ref[...] = jnp.zeros(yT_ref.shape, jnp.float32)

    part_ref[...] = _spmm_partials(nbr_ref, mask_ref, w_ref, xT_ref, thr_ref,
                                   k_chunks=k_chunks, chunk=chunk,
                                   fuse_threshold=fuse_threshold)

    def fold(j, carry):
        row = rm_ref[j]                               # ascending real row
        yT_ref[pl.ds(row, 1), :] += part_ref[pl.ds(j, 1), :]
        return carry

    jax.lax.fori_loop(0, bn, fold, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret"))
def ell_spmm_sliced_pallas(neighbors, mask, weights, row_map, x,
                           threshold=None, *, interpret: bool,
                           block_n: int = 256):
    """Sliced-ELL pull-form SpMM with in-kernel fold (DESIGN.md §8, §15).

    neighbors/mask/weights: (n_virtual, W) — virtual rows from
    ``Graph.ell_in_sliced``; ``row_map`` (n_virtual,) int32 (ascending) maps
    each virtual row to its real row; x: (B, n). The kernel computes per-
    virtual-row partials exactly like :func:`ell_spmm_pallas` and folds them
    onto real rows in-register, accumulating into an output block kept
    resident across grid steps — no (n_virtual, B) partial frame in HBM and
    no separate ``segment_sum`` pass. Bit-identical to the former
    partials-then-host-``segment_sum`` path (pinned by tests); parity with
    the jnp oracle ``ref.ell_spmm_sliced_ref`` is allclose, as for every
    Pallas kernel (chunked f32 reduction order differs). Returns (B, n).
    """
    n_virtual, K = neighbors.shape
    n = x.shape[1]
    B = x.shape[0]
    chunk = 128
    Kp = -(-K // chunk) * chunk
    bn = min(block_n, n_virtual)
    nb = -(-n_virtual // bn)
    n_pad = nb * bn - n_virtual
    if Kp != K:
        neighbors = jnp.pad(neighbors, ((0, 0), (0, Kp - K)))
        mask = jnp.pad(mask, ((0, 0), (0, Kp - K)))
        weights = jnp.pad(weights, ((0, 0), (0, Kp - K)))
    if n_pad:
        neighbors = jnp.pad(neighbors, ((0, n_pad), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad), (0, 0)))
        weights = jnp.pad(weights, ((0, n_pad), (0, 0)))
        row_map = jnp.pad(row_map, (0, n_pad), constant_values=n)  # dump row

    fuse = threshold is not None
    if not fuse:
        threshold = jnp.zeros((n,), jnp.float32)
    kernel = functools.partial(_ell_spmm_fold_kernel, k_chunks=Kp // chunk,
                               chunk=chunk, fuse_threshold=fuse, bn=bn)
    yT = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bn,), lambda i: (i,)),      # row_map block
            pl.BlockSpec((n, B), lambda i: (0, 0)),   # x^T resident per step
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        # constant index map: the accumulator block is revisited (stays
        # resident) across every sequential grid step; row n is the dump row
        out_specs=pl.BlockSpec((n + 1, B), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + 1, B), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, B), jnp.float32)],
        interpret=interpret,
    )(neighbors, mask, weights.astype(jnp.float32),
      row_map.astype(jnp.int32), x.astype(jnp.float32).T,
      threshold.astype(jnp.float32))
    return yT[:n].T
