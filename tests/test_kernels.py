"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ell_spmv import ell_spmm_pallas, ell_spmv_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,Dh,causal,off", [
    (1, 128, 128, 2, 2, 64, True, 0),
    (2, 100, 100, 4, 2, 32, True, 0),        # GQA + ragged block tail
    (1, 1, 256, 4, 1, 64, True, 255),        # decode shape (MQA)
    (2, 64, 192, 8, 8, 128, False, 0),       # cross, no mask
    (1, 37, 53, 2, 1, 16, True, 16),         # odd everything + offset
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Skv, Hq, Hkv, Dh, causal, off, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, q_offset=off,
                                 block_q=32, block_k=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("n,K,block_n", [
    (64, 4, 32), (100, 7, 64), (512, 16, 256), (300, 130, 128),
    (1000, 33, 512),
])
def test_ell_spmv_sweep(n, K, block_n):
    ks = jax.random.split(KEY, 4)
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.7, (n, K))
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(ks[3], (n,))
    out = ell_spmv_pallas(nbr, msk, w, x, block_n=block_n, interpret=True)
    expect = ref.ell_spmv_ref(nbr, msk, x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,n,K,block_n", [
    (1, 100, 7, 64),       # K far from a 128 multiple, ragged row tail
    (5, 130, 130, 64),     # K just past one 128 chunk, n % block_n != 0
    (5, 300, 33, 128),     # multi-block grid, odd K
    (1, 64, 200, 32),      # K spanning two chunks at B=1
    (5, 257, 8, 256),      # single ragged tail row in its own block
])
def test_ell_spmm_sweep(B, n, K, block_n):
    """Batched kernel vs oracle at awkward shapes, incl. zero-degree rows."""
    ks = jax.random.split(KEY, 4)
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.7, (n, K))
    msk = msk.at[0].set(False).at[n // 2].set(False)   # zero-degree rows
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(ks[3], (B, n))
    out = ell_spmm_pallas(nbr, msk, w, x, block_n=block_n,
                          interpret=True)
    expect = ref.ell_spmm_ref(nbr, msk, x, w)
    assert out.shape == (B, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(out)[:, [0, n // 2]]).max() == 0.0


@pytest.mark.parametrize("B", [1, 5])
def test_ell_spmm_fused_threshold(B):
    """threshold fuses FORA's push condition: only x[src] > thr[src]
    contributes — parity vs oracle and vs explicit masking."""
    n, K = 150, 19
    ks = jax.random.split(KEY, 5)
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.8, (n, K))
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(ks[3], (B, n))
    thr = jnp.abs(jax.random.normal(ks[4], (n,))) * 0.5
    out = ell_spmm_pallas(nbr, msk, w, x, thr, block_n=64,
                          interpret=True)
    expect = ref.ell_spmm_ref(nbr, msk, x, w, threshold=thr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)
    masked = jnp.where(x > thr[None, :], x, 0.0)
    explicit = ref.ell_spmm_ref(nbr, msk, masked, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(explicit),
                               atol=1e-4, rtol=1e-4)


def test_ell_spmm_batch1_matches_spmv():
    """The B=1 row of the batched kernel is exactly the SpMV kernel."""
    n, K = 96, 11
    ks = jax.random.split(KEY, 4)
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.7, (n, K))
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(ks[3], (n,))
    spmm = ell_spmm_pallas(nbr, msk, w, x[None, :], block_n=32,
                           interpret=True)
    spmv = ell_spmv_pallas(nbr, msk, w, x, block_n=32, interpret=True)
    np.testing.assert_allclose(np.asarray(spmm[0]), np.asarray(spmv),
                               atol=1e-5, rtol=1e-5)


def test_ops_ell_spmm_dispatch():
    from repro.kernels import ops
    n, K, B = 80, 9, 3
    ks = jax.random.split(KEY, 4)
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.7, (n, K))
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(ks[3], (B, n))
    out = ops.ell_spmm(nbr, msk, w, x)               # CPU -> oracle path
    out_forced = ops.ell_spmm(nbr, msk, w, x, force="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_forced),
                               atol=1e-4, rtol=1e-4)


def test_ell_spmv_is_push_relaxation():
    """ELL SpMV over in-neighbor lists with w=1/deg_out == one frontier
    relaxation of forward push (DESIGN.md §5)."""
    from repro.ppr import small_test_graph
    g = small_test_graph(n=48, avg_deg=4, seed=2)
    # in-neighbor ELL: rows indexed by dst
    order = np.argsort(g.edge_dst, kind="stable")
    dst_sorted = g.edge_dst[order]
    src_sorted = g.edge_src[order]
    in_deg = np.bincount(dst_sorted, minlength=g.n)
    K = int(in_deg.max())
    nbr = np.zeros((g.n, K), np.int32)
    msk = np.zeros((g.n, K), bool)
    off = np.zeros(g.n + 1, np.int64)
    np.cumsum(in_deg, out=off[1:])
    pos = np.arange(g.m) - off[dst_sorted]
    nbr[dst_sorted, pos] = src_sorted
    msk[dst_sorted, pos] = True
    w = (1.0 / np.maximum(g.out_degree, 1))[nbr] * msk
    x = np.random.default_rng(0).random(g.n).astype(np.float32)
    got = ell_spmv_pallas(jnp.asarray(nbr), jnp.asarray(msk),
                          jnp.asarray(w.astype(np.float32)), jnp.asarray(x),
                          interpret=True)
    # reference: dense P^T x via segment sum
    contrib = x[g.edge_src] / np.maximum(g.out_degree, 1)[g.edge_src]
    expect = np.zeros(g.n, np.float32)
    np.add.at(expect, g.edge_dst, contrib)
    np.testing.assert_allclose(np.asarray(got), expect, atol=1e-5)


@pytest.mark.parametrize("V,d,B,L,block_b", [
    (100, 8, 16, 5, 8), (1000, 18, 64, 100, 32), (64, 32, 300, 7, 128),
    (50_000, 16, 128, 64, 64),
])
def test_embedding_bag_sweep(V, d, B, L, block_b):
    ks = jax.random.split(KEY, 3)
    table = jax.random.normal(ks[0], (V, d))
    ids = jax.random.randint(ks[1], (B, L), 0, V)
    w = jax.random.uniform(ks[2], (B, L))
    out = embedding_bag_pallas(table, ids, w, block_b=block_b,
                               interpret=True)
    expect = ref.embedding_bag_ref(table, ids, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


def test_embedding_bag_matches_din_interest_pooling():
    """The kernel computes exactly DIN's weighted history sum."""
    ks = jax.random.split(KEY, 3)
    B, L, V, d = 4, 10, 50, 6
    table = jax.random.normal(ks[0], (V, d))
    ids = jax.random.randint(ks[1], (B, L), 0, V)
    w = jax.random.uniform(ks[2], (B, L))
    hist = jnp.take(table, ids, axis=0)
    expect = jnp.einsum("bl,bld->bd", w, hist)
    got = embedding_bag_pallas(table, ids, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)


def test_ops_dispatch_cpu_fallback():
    from repro.kernels import ops
    q = jax.random.normal(KEY, (1, 8, 2, 16))
    out = ops.flash_attention(q, q, q)          # CPU -> oracle path
    assert out.shape == q.shape
    out_forced = ops.flash_attention(q, q, q, force="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_forced),
                               atol=3e-5, rtol=3e-5)
