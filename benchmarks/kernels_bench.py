"""Kernel micro-benchmarks: Pallas vs jnp-oracle parity + timing.

Wall times on CPU measure the oracle path (the deployment path off-TPU);
the Pallas kernels run interpreted there and validate numerics at benchmark
shapes. On a TPU they compile instead (a kernel never runs interpreted on
the chip), and the ELL SpMV/SpMM kernels do not lower there yet (ROADMAP S0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.ell_spmv import (_spmm_virtual_rows, ell_spmm_pallas,
                                    ell_spmm_sliced_pallas, ell_spmv_pallas)
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.walk_gather import walk_endpoint_gather_pallas
from repro.ppr.graph import Graph

from .common import emit, timed, timed_aot


def run() -> None:
    interpret = jax.default_backend() != "tpu"
    key = jax.random.PRNGKey(0)
    # flash attention at a serving-ish shape
    B, Sq, Skv, Hq, Hkv, Dh = 2, 256, 256, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh))
    refo, us = timed(lambda: np.asarray(ref.flash_attention_ref(q, k, v)))
    pal = flash_attention_pallas(q, k, v,
                                 interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/flash_attention", us, f"maxerr={err:.2e};shape=B{B}S{Sq}H{Hq}")

    # ell spmv at a push-sweep shape
    n, K = 4096, 32
    nbr = jax.random.randint(ks[0], (n, K), 0, n)
    msk = jax.random.bernoulli(ks[1], 0.8, (n, K))
    w = jax.random.normal(ks[2], (n, K))
    x = jax.random.normal(key, (n,))
    refo, us = timed(lambda: np.asarray(ref.ell_spmv_ref(nbr, msk, x, w)))
    pal = ell_spmv_pallas(nbr, msk, w, x, interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/ell_spmv", us, f"maxerr={err:.2e};n={n};K={K}")

    # batched ell spmm at the fused push shape (query batch on the lane axis)
    Bq = 8
    xb = jax.random.normal(key, (Bq, n))
    refo, us = timed(lambda: np.asarray(ref.ell_spmm_ref(nbr, msk, xb, w)))
    pal = ell_spmm_pallas(nbr, msk, w, xb, interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/ell_spmm", us, f"maxerr={err:.2e};n={n};K={K};B={Bq}")
    # device-time row (jax.profiler-backed AOT harness, DESIGN.md §15):
    # steady-state us on the compiled dispatch, compile cost split out
    spmm_fn = jax.jit(lambda a, b, c, d: ops.ell_spmm(a, b, c, d))
    _, dev_us, comp_us = timed_aot(spmm_fn, nbr, msk, w, xb)
    emit("kernels/ell_spmm_dev", dev_us,
         f"compile_us={comp_us:.0f};n={n};K={K};B={Bq}")

    # fused push-threshold variant (the forward_push inner loop)
    thr = jnp.abs(jax.random.normal(ks[1], (n,))) * 0.1
    refo, us = timed(lambda: np.asarray(
        ref.ell_spmm_ref(nbr, msk, xb, w, threshold=thr)))
    pal = ell_spmm_pallas(nbr, msk, w, xb, thr,
                          interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/ell_spmm_fused_push", us,
         f"maxerr={err:.2e};n={n};K={K};B={Bq}")

    # sliced ELL at a power-law shape (hub in-degree ~ n): the web-scale
    # serving layout (DESIGN.md §8). Also reports the resident ELL bytes —
    # dense (n, k_max) vs sliced (n_virtual, W) — so layout regressions
    # (e.g. a worse width heuristic) fail the tolerance gate on peak MiB.
    rng = np.random.default_rng(0)
    n_pl = 4096
    src = np.concatenate([np.arange(1, n_pl),
                          rng.integers(0, n_pl, 4 * n_pl)])
    dst = np.concatenate([np.zeros(n_pl - 1, np.int64),
                          rng.integers(0, n_pl, 4 * n_pl)])
    g = Graph.from_edges(n_pl, src, dst, name="powerlaw-bench")
    sl = g.ell_in_sliced()
    xp = jax.random.normal(key, (Bq, n_pl))
    s_nbr, s_msk = jnp.asarray(sl.neighbors), jnp.asarray(sl.mask)
    s_w, s_map = jnp.asarray(sl.weights), jnp.asarray(sl.row_map)
    refo, us = timed(lambda: np.asarray(ref.ell_spmm_sliced_ref(
        s_nbr, s_msk, xp, s_w, row_map=s_map)))
    pal = ell_spmm_sliced_pallas(s_nbr, s_msk, s_w, s_map, xp,
                                 interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/ell_spmm_sliced", us,
         f"maxerr={err:.2e};n={n_pl};W={sl.width};nv={sl.n_virtual};B={Bq}")
    sliced_oracle_us = us

    # in-kernel fused fold (DESIGN.md §15): the sliced kernel now folds its
    # virtual-row partials into true rows inside the Pallas grid instead of a
    # host-side segment_sum pass. Parity bar is bit-exactness against the
    # former two-pass path (identical partials, identical ascending fold
    # order), plus speedup vs the eager oracle row above. Timing is AOT
    # device time on the jitted dispatch — compile cost is its own field.
    yT_part = _spmm_virtual_rows(s_nbr, s_msk, s_w, xp, None,
                                 block_n=256, interpret=interpret)
    old_fold = jax.ops.segment_sum(
        yT_part[:sl.n_virtual], s_map, num_segments=n_pl,
        indices_are_sorted=True).T
    bit_exact = bool(np.array_equal(np.asarray(pal), np.asarray(old_fold)))
    fold_fn = jax.jit(lambda a, b, c, d, e: ops.ell_spmm_sliced(a, b, c, d, e))
    _, dev_us, comp_us = timed_aot(fold_fn, s_nbr, s_msk, s_w, s_map, xp)
    emit("kernels/ell_spmm_sliced_fused_fold", dev_us,
         f"bit_exact_vs_host_fold={int(bit_exact)};"
         f"speedup_vs_host_fold={sliced_oracle_us / max(dev_us, 1e-9):.2f}x;"
         f"compile_us={comp_us:.0f};n={n_pl};W={sl.width};B={Bq}")
    dense_mib = g.ell_in_dense_nbytes() / 2**20
    sliced_mib = sl.nbytes / 2**20
    emit("kernels/ell_peak_mib", sliced_mib * 1e3,   # milli-MiB for precision
         f"sliced_MiB={sliced_mib:.2f};dense_MiB={dense_mib:.2f};"
         f"ratio={dense_mib / sliced_mib:.0f}x;n={n_pl};W={sl.width}")

    # embedding bag at a DIN-ish shape
    V, d, Bb, L = 50_000, 18, 512, 100
    table = jax.random.normal(ks[0], (V, d))
    ids = jax.random.randint(ks[1], (Bb, L), 0, V)
    wts = jax.random.uniform(ks[2], (Bb, L))
    refo, us = timed(lambda: np.asarray(ref.embedding_bag_ref(table, ids, wts)))
    pal = embedding_bag_pallas(table, ids, wts,
                               interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/embedding_bag", us, f"maxerr={err:.2e};V={V};B={Bb};L={L}")

    # walk-endpoint gather at the index-backed fused walk shape
    # (DESIGN.md §11): n nodes x W stored lanes, one query block of Bq rows
    n_wi, W_wi = 4096, 256
    endpoints = jax.random.randint(ks[0], (n_wi, W_wi), 0, n_wi)
    budget = jax.random.randint(ks[1], (n_wi,), 0, W_wi + 1)
    starts = jax.random.randint(ks[2], (Bq, W_wi), 0, n_wi)
    w_lanes = jax.random.uniform(key, (Bq, W_wi))
    refo, us = timed(lambda: np.asarray(ref.walk_endpoint_gather_ref(
        endpoints, budget, starts, w_lanes)))
    pal = walk_endpoint_gather_pallas(endpoints, budget, starts, w_lanes,
                                      interpret=interpret)
    err = float(jnp.abs(pal - refo).max())
    emit("kernels/walk_endpoint_gather", us,
         f"maxerr={err:.2e};n={n_wi};W={W_wi};B={Bq}")
    gather_fn = jax.jit(
        lambda a, b, c, d: ops.walk_endpoint_gather(a, b, c, d))
    _, dev_us, comp_us = timed_aot(gather_fn, endpoints, budget, starts,
                                   w_lanes)
    emit("kernels/walk_endpoint_gather_dev", dev_us,
         f"compile_us={comp_us:.0f};n={n_wi};W={W_wi};B={Bq}")
