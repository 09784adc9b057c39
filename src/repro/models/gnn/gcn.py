"""GCN (Kipf & Welling, arXiv:1609.02907) — gcn-cora config: 2 layers, d=16,
symmetric normalisation, mean-field SpMM via segment_sum."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...distributed.ctx import constrain
from ..common import dense_init
from .common import GraphBatch, scatter_sum, sym_norm_coeff


@dataclass(frozen=True)
class GCNConfig:
    name: str = "gcn-cora"
    n_layers: int = 2
    d_hidden: int = 16
    d_in: int = 1433
    n_classes: int = 7
    dropout: float = 0.5       # applied only in train_step with rng
    dtype: str = "float32"


def init(key: jax.Array, cfg: GCNConfig):
    dt = jnp.dtype(cfg.dtype)
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    keys = jax.random.split(key, len(dims) - 1)
    return {"layers": [{"w": dense_init(k, di, do, dt),
                        "b": jnp.zeros((do,), dt)}
                       for k, di, do in zip(keys, dims[:-1], dims[1:])]}


def apply(params, cfg: GCNConfig, batch: GraphBatch):
    n = batch.node_feat.shape[0]
    h = batch.node_feat
    coeff = sym_norm_coeff(batch.edge_index, batch.edge_mask, n)
    src, dst = batch.edge_index[0], batch.edge_index[1]
    for i, layer in enumerate(params["layers"]):
        h = constrain(h, "data", None)
        h = h @ layer["w"] + layer["b"]           # XW first (d_in -> d_hidden)
        msg = h[src] * coeff[:, None]
        agg = scatter_sum(msg, dst, n) + h        # Â = A_norm + I (self loop)
        h = constrain(agg, "data", None)
        if i < len(params["layers"]) - 1:
            h = jax.nn.relu(h)
    return h                                       # (N, n_classes) logits


def loss_fn(params, cfg: GCNConfig, batch: GraphBatch):
    logits = apply(params, cfg, batch)
    labels = batch.labels
    mask = batch.node_mask.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].clip(0), axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn_owner_computes(params, cfg: GCNConfig, batch: GraphBatch, mesh):
    """Owner-computes full-batch GCN (§Perf G1) via shard_map over "data".

    INPUT CONTRACT: edges are dst-partition-aligned — shard k holds exactly
    the edges whose destination lies in its node range (a partitioner
    guarantee; `Graph` sorted by dst then block-split provides it). Then the
    scatter of messages is purely local and the only collective is the
    all-gather of the (already projected, d_hidden-narrow) source features —
    replacing GSPMD's per-layer psum/permute storm over (n, d) scatters.
    """
    from jax.sharding import PartitionSpec as P

    D = mesh.shape["data"]
    n = batch.node_feat.shape[0]
    n_loc = n // D

    def kernel(x_loc, ei_loc, emask_loc, nmask_loc, labels_loc):
        my = jax.lax.axis_index("data")
        src_g, dst_g = ei_loc[0], ei_loc[1]
        dst_l = jnp.clip(dst_g - my * n_loc, 0, n_loc - 1)
        ok = jnp.logical_and(emask_loc,
                             (dst_g // n_loc) == my)      # contract check
        w_e = jnp.ones_like(src_g, jnp.float32)

        # degrees: local in-degree per dst; gathered for src normalisation
        ones = jnp.where(ok, 1.0, 0.0)
        deg_loc = jax.ops.segment_sum(ones, dst_l, num_segments=n_loc)
        deg_full = jax.lax.all_gather(deg_loc, "data", tiled=True)   # (n,)
        deg_full = jnp.maximum(deg_full, 1.0)
        coeff = jax.lax.rsqrt(deg_full[src_g]) \
            * jax.lax.rsqrt(jnp.maximum(deg_loc[dst_l], 1.0)) * w_e
        coeff = jnp.where(ok, coeff, 0.0)

        h = x_loc
        for i, layer in enumerate(params["layers"]):
            h = h @ layer["w"] + layer["b"]               # local projection
            h_full = jax.lax.all_gather(h, "data", tiled=True)  # THE collective
            msg = h_full[src_g] * coeff[:, None]
            agg = jax.ops.segment_sum(msg, dst_l, num_segments=n_loc)
            h = agg + h                                   # Â + I, all local
            if i < len(params["layers"]) - 1:
                h = jax.nn.relu(h)
        m = nmask_loc.astype(jnp.float32)
        logp = jax.nn.log_softmax(h.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels_loc[:, None].clip(0),
                                   axis=-1)[:, 0]
        num = jax.lax.psum((nll * m).sum(), "data")
        den = jax.lax.psum(m.sum(), "data")
        return (num / jnp.maximum(den, 1.0))[None]

    loss = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P("data", None), P(None, "data"), P("data"), P("data"),
                  P("data")),
        out_specs=P("data"),
        check_vma=False,
    )(batch.node_feat, batch.edge_index, batch.edge_mask, batch.node_mask,
      batch.labels)
    return loss.mean()
