"""Pallas TPU flash attention (FlashAttention-2 schedule, GQA-aware).

Grid (B*Hq, num_q_blocks, num_kv_blocks); the kv dimension is the minor
(sequential) grid axis, so VMEM scratch accumulators (running max / sum /
output) persist across kv steps for a fixed (bh, q-block) — the standard TPU
online-softmax pattern. Block shapes are MXU-aligned (q/kv blocks multiples
of 128 lanes; head_dim is the lane axis of the QK^T matmul).

VMEM working set per program:
    q (bq, d) + k (bk, d) + v (bk, d) + acc (bq, d) + m/l (bq, 128)
    = (bq + 2*bk + bq) * d * 4B + small  ->  bq=bk=128, d<=256: ~0.5 MB.

Causal masking uses global indices (q_offset supports decode/chunked
prefill). GQA folds the query-head axis: kv block index = qh // group.

Validated in interpret mode against kernels/ref.py (the pure-jnp oracle) —
this container is CPU-only; TPU is the target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, q_offset: int, block_q: int,
                  block_k: int, kv_len: int, num_kv_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)

    iq = pl.program_id(1)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_idx < kv_len
    if causal:
        q_idx = (q_offset + iq * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        valid = jnp.logical_and(valid, q_idx >= k_idx)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...][:, :1]                        # (bq, 1)
    l_prev = l_scr[...][:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)        # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)

    acc = acc_scr[...]
    acc = acc * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))               # (bq, d)
    acc_scr[...] = acc
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, interpret: bool, causal: bool = True,
                           q_offset: int = 0, block_q: int = 128,
                           block_k: int = 128):
    """q: (B, Sq, Hq, Dh); k/v: (B, Skv, Hkv, Dh); Hq % Hkv == 0.

    Returns (B, Sq, Hq, Dh) in q.dtype. Sq/Skv are padded to block multiples
    internally; kv padding is masked, q padding sliced off.
    """
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = float(1.0 / np.sqrt(Dh))

    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, max(8, Skv))
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_k)
    pad_q = nq * block_q - Sq
    pad_k = nk * block_k - Skv

    # (B*H, S, D) layout
    qf = jnp.moveaxis(q, 2, 1).reshape(B * Hq, Sq, Dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Skv, Dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Skv, Dh)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=Skv, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, nq * block_q, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :Sq, :].reshape(B, Hq, Sq, Dh)
    return jnp.moveaxis(out, 1, 2)
