"""Autotuned kernel layer (DESIGN.md §15): in-kernel sliced fold parity,
tuning-cache round-trip + cold-cache bit-identity, tuned residency (lookup
strictly at build time — pinned under a transfer guard), the AOT device-time
harness, and cost-model seeding from measured kernel times."""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.estimator import CacheAwareCostModel
from repro.kernels import autotune, ops, ref
from repro.kernels.autotune import (TunedConfig, TuningCache, measure_compiled,
                                    shape_bucket, sweep_sliced)
from repro.kernels.ell_spmv import _spmm_virtual_rows, ell_spmm_sliced_pallas
from repro.ppr.fora import ForaParams, fora_fused
from repro.ppr.graph import DeviceGraph, Graph


@pytest.fixture(autouse=True)
def _cold_cache():
    """Every test starts AND ends with no active tuning cache — the
    process-global `_ACTIVE` must never leak tuned configs across tests."""
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _powerlaw_graph(n: int, seed: int, hub_fanin: int | None = None) -> Graph:
    rng = np.random.default_rng(seed)
    hub_fanin = n - 1 if hub_fanin is None else hub_fanin
    src = np.concatenate([rng.choice(n, size=hub_fanin, replace=False),
                          rng.integers(0, n, 3 * n)])
    dst = np.concatenate([np.zeros(hub_fanin, np.int64),
                          rng.integers(0, n, 3 * n)])
    return Graph.from_edges(n, src, dst, name=f"pl{n}s{seed}")


def _old_path(sl, x, threshold=None, block_n: int = 256):
    """The pre-§15 two-pass result: Pallas partials + host segment_sum."""
    yT = _spmm_virtual_rows(jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
                            jnp.asarray(sl.weights), x,
                            None if threshold is None
                            else jnp.asarray(threshold),
                            block_n=block_n, interpret=True)
    return jax.ops.segment_sum(yT[:sl.n_virtual], jnp.asarray(sl.row_map),
                               num_segments=sl.n, indices_are_sorted=True).T


# ---------------------------------------------------------------------------
# in-kernel fold parity


@pytest.mark.parametrize("seed,n,B,width,pad_multiple,thr,block_n", [
    (0, 97, 1, None, None, False, 256),
    (1, 128, 3, None, None, True, 256),
    (2, 200, 8, 4, 1, False, 32),      # block_n << n_virtual: many grid steps
    (3, 64, 2, 1, 1, True, 16),        # W=1: every edge its own virtual row
    (4, 300, 4, 16, 8, False, 64),
])
def test_fold_bit_identical_to_host_segment_sum(seed, n, B, width,
                                                pad_multiple, thr, block_n):
    """The fused in-kernel fold is BIT-exact vs the former partials-then-
    host-segment_sum path: identical partials (shared `_spmm_partials`
    body), identical ascending per-virtual-row accumulation order."""
    g = _powerlaw_graph(n, seed)
    sl = g.ell_in_sliced(width=width, pad_multiple=pad_multiple)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random((B, n), dtype=np.float32))
    threshold = (rng.random(n).astype(np.float32) * 0.1) if thr else None

    new = ell_spmm_sliced_pallas(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
        jnp.asarray(sl.weights), jnp.asarray(sl.row_map), x,
        None if threshold is None else jnp.asarray(threshold),
        block_n=block_n, interpret=True)
    old = _old_path(sl, x, threshold, block_n=block_n)
    assert np.array_equal(np.asarray(new), np.asarray(old)), \
        "in-kernel fold diverged bitwise from the host segment_sum fold"
    # and numerically matches the jnp oracle (different reduction order)
    want = ref.ell_spmm_sliced_ref(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask), x,
        jnp.asarray(sl.weights), row_map=jnp.asarray(sl.row_map),
        threshold=None if threshold is None else jnp.asarray(threshold))
    np.testing.assert_allclose(np.asarray(new), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_fold_single_virtual_row_per_real_row():
    """Degenerate case: no row splits at all (width >= max in-degree) — the
    fold is a pure permutation-free copy and must still be bit-exact."""
    g = _powerlaw_graph(50, 7, hub_fanin=4)
    sl = g.ell_in_sliced(width=64, pad_multiple=1)
    assert sl.n_virtual <= g.n
    x = jnp.asarray(np.random.default_rng(7).random((2, g.n),
                                                    dtype=np.float32))
    new = ell_spmm_sliced_pallas(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
        jnp.asarray(sl.weights), jnp.asarray(sl.row_map), x, interpret=True)
    assert np.array_equal(np.asarray(new), np.asarray(_old_path(sl, x)))


def test_fold_block_n_is_numerics_neutral():
    """block_n retiles the grid but partials are per-virtual-row and the
    fold order is ascending regardless — every tiling gives the same bits.
    This is the invariant that makes block_n safe to autotune."""
    g = _powerlaw_graph(150, 11)
    sl = g.ell_in_sliced()
    x = jnp.asarray(np.random.default_rng(11).random((3, g.n),
                                                     dtype=np.float32))
    outs = [np.asarray(ell_spmm_sliced_pallas(
        jnp.asarray(sl.neighbors), jnp.asarray(sl.mask),
        jnp.asarray(sl.weights), jnp.asarray(sl.row_map), x, block_n=bn,
        interpret=True))
        for bn in (16, 64, 256)]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# tuning cache


def test_cache_round_trip_and_atomicity(tmp_path):
    path = tmp_path / "tune.json"
    cache = TuningCache(path=path)
    cfg = TunedConfig(block_n=128, pad_multiple=8, width=16,
                      device_us=42.5, compile_us=1000.0)
    cache.record("cpu", "sliced", "n512_d4", cfg)
    cache.record("cpu", "walk", "n512_d4", TunedConfig(device_us=7.0))
    cache.save()

    loaded = TuningCache.load(path)
    assert loaded.entries == cache.entries
    assert loaded.lookup("cpu", "sliced", "n512_d4") == cfg
    assert loaded.lookup("tpu", "sliced", "n512_d4") is None
    # atomic write: no tmp droppings next to the cache file
    assert [p.name for p in tmp_path.iterdir()] == ["tune.json"]


def test_cache_schema_mismatch_raises(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({"schema": 999, "entries": {}}))
    with pytest.raises(ValueError, match="schema"):
        TuningCache.load(path)


def test_cache_env_activation(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    cache = TuningCache(path=path)
    cache.record("cpu", "sliced", "n64_d2", TunedConfig(block_n=64))
    cache.save()
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_cache()                    # re-arm the lazy env pickup
    active = autotune.get_cache()
    assert active is not None
    assert active.lookup("cpu", "sliced", "n64_d2").block_n == 64


def test_shape_bucket_pow2_ceiling():
    assert shape_bucket(512, 2048) == "n512_d4"
    assert shape_bucket(513, 2052) == "n1024_d4"
    assert shape_bucket(1, 0) == "n1_d1"
    # nearby shapes share a bucket — the property the serving runtime needs
    assert shape_bucket(4000, 20_000) == shape_bucket(4096, 20_480)


# ---------------------------------------------------------------------------
# residency: cold bit-identity, tuned override, build-time-only lookup


def test_cold_cache_residency_is_default():
    """No active cache ⇒ the resolved layout equals the hardcoded defaults
    (the acceptance bar: a cold-cache run reproduces today's numbers)."""
    g = _powerlaw_graph(120, 3)
    dg = DeviceGraph.from_graph(g, layout="sliced")
    assert dg.block_n == 256
    assert dg.ell_width == g.sliced_ell_width()


def test_tuned_residency_overrides_unpinned_params():
    g = _powerlaw_graph(120, 3)
    backend = autotune.current_backend()
    bucket = shape_bucket(g.n, g.m)
    cold = DeviceGraph.from_graph(g, layout="sliced")
    tuned_w = cold.ell_width * 2
    cache = TuningCache()
    cache.record(backend, "sliced", bucket,
                 TunedConfig(block_n=64, pad_multiple=1, width=tuned_w,
                             device_us=1.0))
    autotune.set_cache(cache)
    dg = DeviceGraph.from_graph(g, layout="sliced")
    assert dg.block_n == 64
    assert dg.ell_width == tuned_w
    # pinned values always beat the cache — the caller knows best
    pinned = DeviceGraph.from_graph(g, layout="sliced", width=8,
                                    pad_multiple=1, block_n=512)
    assert pinned.block_n == 512 and pinned.ell_width == 8

    # tuned vs cold answers: same query, allclose (width changes the fold
    # association, so bit-equality is not the contract here)
    params = ForaParams(alpha=0.2, epsilon=0.5)
    src = np.array([0, 5], np.int32)
    res_t = fora_fused(dg, src, params, jax.random.PRNGKey(0),
                       num_walks=1024)
    autotune.clear_cache()
    res_c = fora_fused(cold, src, params, jax.random.PRNGKey(0),
                       num_walks=1024)
    np.testing.assert_allclose(np.asarray(res_t.pi), np.asarray(res_c.pi),
                               atol=1e-4)


def test_tuned_lookup_happens_at_build_time_only():
    """The cache is consulted when the residency is BUILT (host-side); the
    fused query loop itself stays transfer-free — same contract as
    test_fora_fused_no_host_transfer, now with a tuned cache active."""
    g = _powerlaw_graph(120, 5)
    backend = autotune.current_backend()
    cache = TuningCache()
    cache.record(backend, "sliced", shape_bucket(g.n, g.m),
                 TunedConfig(block_n=64, pad_multiple=1, width=8,
                             device_us=1.0))
    autotune.set_cache(cache)
    dg = DeviceGraph.from_graph(g, layout="sliced")
    assert dg.block_n == 64
    params = ForaParams(alpha=0.2, epsilon=0.5)
    fora_fused(dg, jnp.asarray(np.array([0, 5], np.int32)), params,
               jax.random.PRNGKey(0), num_walks=1024)          # warm/compile
    srcs = jnp.asarray(np.array([3, 9], np.int32))
    key = jax.random.PRNGKey(1)
    with jax.transfer_guard("disallow"):
        res = fora_fused(dg, srcs, params, key, num_walks=1024)
    pi = np.asarray(res.pi)                    # readout outside the guard
    assert np.allclose(pi.sum(axis=1), 1.0, atol=1e-3)


# ---------------------------------------------------------------------------
# measurement harness + sweep


def test_measure_compiled_splits_compile_from_steady_state():
    def f(a, b):
        return jnp.tanh(a) @ b

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.random((64, 64), dtype=np.float32))
    b = jnp.asarray(rng.random((64, 64), dtype=np.float32))
    out, dev_us, comp_us = measure_compiled(f, a, b, repeats=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(a, b)),
                               rtol=1e-6)
    assert dev_us > 0.0 and np.isfinite(dev_us)
    assert comp_us > 0.0
    # steady state excludes compilation: a compiled 64x64 matmul cannot
    # plausibly take as long as its own XLA compile
    assert dev_us < comp_us


def test_sweep_sliced_records_winner(tmp_path):
    g = _powerlaw_graph(96, 9)
    cache = TuningCache(path=tmp_path / "tune.json")
    best = sweep_sliced(g, B=2, block_ns=(32, 64), repeats=1, cache=cache)
    assert best.block_n in (32, 64)
    assert best.device_us > 0.0
    key_hit = cache.lookup(autotune.current_backend(), "sliced",
                           shape_bucket(g.n, g.m))
    assert key_hit == best
    cache.save()
    assert TuningCache.load(cache.path).entries == cache.entries


# ---------------------------------------------------------------------------
# cost-model seeding


def test_seeded_from_tuning_prices_walk_share():
    cache = TuningCache()
    cache.record("cpu", "sliced", "n512_d4",
                 TunedConfig(device_us=300.0, compile_us=9e6))
    cache.record("cpu", "walk", "n512_d4",
                 TunedConfig(device_us=100.0, compile_us=9e6))
    model = CacheAwareCostModel.seeded_from_tuning(cache, backend="cpu")
    assert model.walk_share == pytest.approx(0.25)   # 100/(100+300)

    # compile_us must never leak into the share (device_us identical)
    cache2 = TuningCache()
    cache2.record("cpu", "sliced", "n512_d4", TunedConfig(device_us=300.0))
    cache2.record("cpu", "walk", "n512_d4", TunedConfig(device_us=100.0))
    assert CacheAwareCostModel.seeded_from_tuning(
        cache2, backend="cpu").walk_share == pytest.approx(0.25)


def test_seeded_from_tuning_cold_and_explicit():
    default = CacheAwareCostModel()
    assert CacheAwareCostModel.seeded_from_tuning(
        None).walk_share == default.walk_share
    assert CacheAwareCostModel.seeded_from_tuning(
        TuningCache(), backend="cpu").walk_share == default.walk_share
    cache = TuningCache()
    cache.record("cpu", "sliced", "n512_d4", TunedConfig(device_us=300.0))
    cache.record("cpu", "walk", "n512_d4", TunedConfig(device_us=100.0))
    assert CacheAwareCostModel.seeded_from_tuning(
        cache, backend="cpu", walk_share=0.9).walk_share == 0.9
    # a push entry without a walk twin (or wrong backend) seeds nothing
    lonely = TuningCache()
    lonely.record("cpu", "sliced", "n512_d4", TunedConfig(device_us=300.0))
    assert CacheAwareCostModel.seeded_from_tuning(
        lonely, backend="cpu").walk_share == default.walk_share
    assert CacheAwareCostModel.seeded_from_tuning(
        cache, backend="tpu").walk_share == default.walk_share
