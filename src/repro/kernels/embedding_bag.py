"""Pallas TPU embedding-bag — DIN's weighted history pooling.

    out[b] = sum_l weights[b, l] * table[ids[b, l]]

Grid over batch blocks; the (per-shard) embedding table is VMEM-resident
(production tables are row-sharded over the model axis, so each shard holds
vocab/16 rows; the DIN config's 10M x 18 f32 table shards to ~45MB in HBM
with the hot rows streamed — the kernel models the VMEM-tile case, which is
exact for the reduced per-shard vocabulary the tests sweep). The L axis is
reduced with a fori_loop of VMEM gathers, (block_b, d) accumulate on the VPU.

Validated in interpret mode against ref.embedding_bag_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bag_kernel(table_ref, ids_ref, w_ref, out_ref, *, L: int):
    table = table_ref[...]                           # (V, d)
    ids = ids_ref[...]                               # (bb, L)
    w = w_ref[...]                                   # (bb, L)

    def body(l, acc):
        idx = jax.lax.dynamic_index_in_dim(ids, l, axis=1, keepdims=False)
        wl = jax.lax.dynamic_index_in_dim(w, l, axis=1, keepdims=False)
        rows = jnp.take(table, idx, axis=0)          # (bb, d) VMEM gather
        return acc + rows * wl[:, None]

    acc0 = jnp.zeros((ids.shape[0], table.shape[1]), jnp.float32)
    out_ref[...] = jax.lax.fori_loop(0, L, body, acc0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def embedding_bag_pallas(table, ids, weights, *, interpret: bool,
                         block_b: int = 128):
    """table: (V, d) f32; ids: (B, L) int32; weights: (B, L). -> (B, d)."""
    B, L = ids.shape
    V, d = table.shape
    bb = min(block_b, B)
    nb = -(-B // bb)
    pad = nb * bb - B
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))

    kernel = functools.partial(_bag_kernel, L=L)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((V, d), lambda i: (0, 0)),   # table resident
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, d), table.dtype),
        interpret=interpret,
    )(table, ids, weights.astype(jnp.float32))
    return out[:B]
