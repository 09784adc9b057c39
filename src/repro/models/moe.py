"""Mixture-of-Experts FFN (moonshot 64e/top-6, qwen2-moe 60e/top-4+4 shared).

TPU-native dispatch: sort-by-expert with static capacity (MegaBlocks-style
grouped GEMM realised as one batched einsum over (E, C, d) — JAX has no
ragged GEMM, so tokens are bucketed into per-expert capacity slots via a
stable argsort; overflow tokens beyond capacity C are dropped (standard
Switch/GShard semantics, capacity_factor controls the drop rate).

The (E, C, d) buffers are sharded over the ``expert`` logical axis (= the
mesh's model axis), so under pjit the gather/scatter become the MoE
all-to-all; token activations stay on ``batch``. Router runs in fp32.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..distributed.ctx import constrain
from .common import act_fn, dense_init


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0
    d_ff_shared: int = 0          # 0 -> same as d_ff_expert
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    act: str = "silu"
    # "gather": global sort + capacity buckets, GSPMD-placed collectives
    #           (paper-faithful baseline; hits the scatter-merge all-reduce)
    # "local_select": shard_map expert parallelism — x is model-replicated,
    #           so each expert shard selects its own tokens locally and the
    #           only collective is ONE psum of the combined output (§Perf M4)
    ep_mode: str = "gather"

    @property
    def shared_ff(self) -> int:
        return self.d_ff_shared or self.d_ff_expert

    def capacity(self, num_tokens: int) -> int:
        c = int(num_tokens * self.top_k * self.capacity_factor
                / self.num_experts) + 1
        return max(8, -(-c // 8) * 8)   # pad to lane multiple


def moe_init(key: jax.Array, d_model: int, cfg: MoEConfig, dtype):
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    E, F = cfg.num_experts, cfg.d_ff_expert
    params = {
        "router": dense_init(k_r, d_model, E, jnp.float32),
        "w_gate": dense_init(k_g, d_model, E * F, dtype).reshape(d_model, E, F
                                                                 ).transpose(1, 0, 2),
        "w_up": dense_init(k_u, d_model, E * F, dtype).reshape(d_model, E, F
                                                               ).transpose(1, 0, 2),
        "w_down": dense_init(k_d, E * F, d_model, dtype).reshape(E, F, d_model),
    }
    if cfg.num_shared:
        Fs = cfg.shared_ff * cfg.num_shared
        ks1, ks2, ks3 = jax.random.split(k_s, 3)
        params["shared"] = {
            "w_gate": dense_init(ks1, d_model, Fs, dtype),
            "w_up": dense_init(ks2, d_model, Fs, dtype),
            "w_down": dense_init(ks3, Fs, d_model, dtype),
        }
    return params


def moe_apply(params, cfg: MoEConfig, x: jax.Array):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar). Dispatch mode per
    cfg.ep_mode; local_select falls back to gather when no mesh is active
    or the expert count does not divide the model axis."""
    if cfg.ep_mode == "local_select":
        from ..distributed.ctx import active_mesh
        mesh = active_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and cfg.num_experts % mesh.shape["model"] == 0:
            return _moe_apply_local_select(params, cfg, x, mesh)
    return _moe_apply_gather(params, cfg, x)


def _moe_apply_local_select(params, cfg: MoEConfig, x: jax.Array, mesh):
    """shard_map expert parallelism (§Perf M4).

    Layout facts this exploits: token activations are sharded over the batch
    axes and REPLICATED over the model axis; experts are sharded over the
    model axis. So each model shard already holds every token of its data
    row — "dispatch" is a purely local top-k selection of the entries routed
    to the shard's own experts, and the only cross-shard communication is a
    single psum of the combined output (each token's k expert contributions
    live on at most k shards). No all-to-all, no scatter-merge all-reduce.
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    M = mesh.shape["model"]
    E_loc = E // M
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    D = 1
    for a in batch_axes:
        D *= mesh.shape[a]
    T_loc = (B // D) * S
    # local capacity: this shard's expected share of (token, k) entries
    C = max(8, -(-int(T_loc * K * cfg.capacity_factor) // (M * E_loc) // 8) * 8)

    def kernel(x_blk, router, wg, wu, wd):
        # x_blk (B_loc, S, d) replicated over model; wg/wu/wd (E_loc, d, F)
        Bl, Sl, dl = x_blk.shape
        T = Bl * Sl
        xt = x_blk.reshape(T, dl)
        logits = xt.astype(jnp.float32) @ router              # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        my = jax.lax.axis_index("model")
        flat_e = gate_i.reshape(T * K)
        flat_w = gate_w.reshape(T * K)
        local_e = flat_e - my * E_loc                          # local expert id
        mine = jnp.logical_and(local_e >= 0, local_e < E_loc)
        # bucket my entries by local expert with capacity C
        sort_key = jnp.where(mine, local_e, E_loc)             # strangers last
        order = jnp.argsort(sort_key, stable=True)
        sorted_e = sort_key[order]
        counts = jnp.zeros((E_loc + 1,), jnp.int32).at[sort_key].add(1)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(T * K, dtype=jnp.int32) - offsets[sorted_e]
        keep = jnp.logical_and(sorted_e < E_loc, rank < C)
        slot = jnp.where(keep, sorted_e * C + rank, E_loc * C)
        tok_idx = order // K

        src = xt[tok_idx]
        buf = jnp.zeros((E_loc * C + 1, dl), x_blk.dtype).at[slot].set(src)
        expert_in = buf[: E_loc * C].reshape(E_loc, C, dl)
        h = act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", expert_in, wg)) \
            * jnp.einsum("ecd,edf->ecf", expert_in, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)

        flat_out = jnp.concatenate(
            [out.reshape(E_loc * C, dl), jnp.zeros((1, dl), x_blk.dtype)])
        per_entry = flat_out[slot] * flat_w[order][:, None].astype(x_blk.dtype)
        per_entry = jnp.where(keep[:, None], per_entry, 0.0)
        y_partial = jax.ops.segment_sum(per_entry, tok_idx, num_segments=T)
        y = jax.lax.psum(y_partial, "model")                   # THE collective
        # Switch aux loss (identical on every model shard -> already replicated)
        dispatch_frac = jnp.zeros((E,), jnp.float32).at[flat_e].add(
            1.0 / (T * K))
        aux = E * jnp.sum(dispatch_frac * probs.mean(axis=0))
        return y.reshape(Bl, Sl, dl), aux[None]

    b_spec = batch_axes if batch_axes else None
    y, aux = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(b_spec, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(b_spec, None, None), P(b_spec)),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    y = constrain(y, "batch", None, None)
    if "shared" in params:
        sp = params["shared"]
        hs = act_fn(cfg.act)(x @ sp["w_gate"]) * (x @ sp["w_up"])
        y = y + hs @ sp["w_down"]
    return y, aux.mean()


def _moe_apply_gather(params, cfg: MoEConfig, x: jax.Array):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.top_k
    C = cfg.capacity(T)
    xt = x.reshape(T, d)

    # --- routing (fp32) ----------------------------------------------------
    logits = xt.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                     # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e f_e * p_e
    dispatch_frac = jnp.zeros((E,), jnp.float32).at[gate_i.reshape(-1)].add(
        1.0 / (T * K))
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(dispatch_frac * mean_prob)

    # --- capacity bucketing via stable sort ---------------------------------
    flat_e = gate_i.reshape(T * K)                               # expert per entry
    order = jnp.argsort(flat_e, stable=True)                     # (T*K,)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * K, dtype=jnp.int32) - offsets[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)           # E*C = trash row
    token_idx = order // K                                       # source token

    # --- dispatch: gather tokens into (E, C, d) ------------------------------
    src = xt[token_idx]                                          # (T*K, d)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(src)
    expert_in = buf[: E * C].reshape(E, C, d)
    expert_in = constrain(expert_in, "expert", None, None)

    # --- expert GLU FFN (batched GEMM over experts) --------------------------
    h = act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
    h = constrain(h, "expert", None, None)
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out = constrain(out, "expert", None, None)

    # --- combine: gather back per (token, k) and weight-sum -------------------
    flat_out = jnp.concatenate(
        [out.reshape(E * C, d), jnp.zeros((1, d), x.dtype)])
    per_entry = flat_out[slot]                                   # (T*K, d)
    w_sorted = gate_w.reshape(T * K)[order].astype(x.dtype)
    contrib = per_entry * w_sorted[:, None]
    y = jax.ops.segment_sum(contrib, token_idx, num_segments=T)
    y = constrain(y.reshape(B, S, d), "batch", None, None)

    # --- shared experts (dense path) ------------------------------------------
    if "shared" in params:
        sp = params["shared"]
        hs = act_fn(cfg.act)(x @ sp["w_gate"]) * (x @ sp["w_up"])
        y = y + hs @ sp["w_down"]
    return y, aux
