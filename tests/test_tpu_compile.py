"""Compile-only checks of the chip path for a described v5e (no chip needed).

The TPU compiler is installed with JAX, and it compiles for a chip whose
topology is described, not attached. These tests compile, at web-Stanford's
published n, what ``chip_smoke.py`` runs on the chip: the walk-index gather
kernel, the one-chip fused FORA step, and the node-sharded step on a 2x2
mesh; and flash attention, the other kernel in ``ops.TPU_KERNELS``.
Nothing runs, so they show only that the compiler accepts the
programs, which implementation each op lowers to, and what each would hold
in device memory.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.walk_gather import walk_endpoint_gather_pallas
from repro.ppr import ForaParams, load
from repro.ppr.fora import _fora_fused_donating, _fora_fused_sharded_exe
from repro.ppr.random_walk import walk_length_for_tail

V5E_HBM_BYTES = 16 * 10**9        # one v5e chip (Google Cloud, "TPU v5e")
WALKS = 1 << 22                   # ForaParams.max_walks: the chip path's lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def web_stanford():
    """Shapes of the chip path's web-Stanford residency: the sliced table at
    the lane floor the TPU resolves while its push SpMM runs as XLA."""
    g = load("web-stanford", scale=1)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        width, cells = g._sliced_width_cells()
    rp = ForaParams(alpha=0.2, epsilon=0.5).resolve(g)
    return dict(n=g.n, m=g.m, rows=cells // width, width=width, rp=rp,
                steps=walk_length_for_tail(rp.alpha, rp.walk_tail))


@pytest.fixture()
def tpu_dispatch(monkeypatch):
    """Steer ops' dispatch down its TPU branch while a test traces."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "IMPLS", {})


def _scopes(compiled) -> set[str]:
    """The program's ``fora.*`` layer scopes found in the compiled ops'
    metadata: what a profiler trace of the chip names its ops by."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {s for n in names for s in re.findall(r"fora\.\w+", n)}


def _sort_shapes(compiled) -> set[tuple[int, ...]]:
    """The shapes of the operands the compiled program sorts."""
    shapes = re.findall(r"= \(\w+\[([\d,]*)\][^=]*\bsort\(",
                        compiled.as_text())
    return {tuple(int(d) for d in s.split(",")) for s in shapes}


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def test_walk_gather_kernel_compiles_at_web_stanford_n(topo, web_stanford):
    one = SingleDeviceSharding(topo.devices[0])
    n, lanes, batch = web_stanford["n"], 128, 8

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    compiled = jax.jit(lambda e, b, s, w: walk_endpoint_gather_pallas(
        e, b, s, w, interpret=False)).lower(
            shape((n, lanes), jnp.int32), shape((n,), jnp.int32),
            shape((batch, lanes), jnp.int32),
            shape((batch, lanes), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM_BYTES


def test_flash_attention_kernel_compiles(topo):
    """The other member of ``ops.TPU_KERNELS``: it must lower too."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)

    compiled = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False)).lower(
            shape(1, 256, 8, 128), shape(1, 256, 2, 128),
            shape(1, 256, 2, 128)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_for_one_chip(topo, web_stanford, tpu_dispatch):
    """The served step (one query per call) with the TPU's dispatch: the
    sliced push SpMM must lower as XLA, not as a Pallas kernel."""
    one = SingleDeviceSharding(topo.devices[0])
    ws, rp = web_stanford, web_stanford["rp"]
    n, rows, width, batch = ws["n"], ws["rows"], ws["width"], 1

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    compiled = _fora_fused_donating.lower(
        shape((rows, width), jnp.int32), shape((rows, width), jnp.bool_),
        shape((rows, width), jnp.float32), shape((rows,), jnp.int32),
        shape((ws["m"],), jnp.int32), shape((n + 1,), jnp.int32),
        shape((n,), jnp.int32), shape((batch,), jnp.int32),
        shape((2,), jnp.uint32), None, None, None,
        shape((batch,), jnp.int32),
        alpha=rp.alpha, rmax=rp.rmax, omega=rp.omega, n=n, num_walks=WALKS,
        num_steps=ws["steps"], max_push_iters=10_000, force=None,
        index_lanes=0, index_partial=False, bulk_rng=False,
        block_n=256).compile()
    assert ops.IMPLS == {"ell_spmm_sliced": "xla"}
    assert "tpu_custom_call" not in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM_BYTES
    assert _scopes(compiled) == {"fora.push", "fora.walk_starts",
                                 "fora.walk_steps"}
    # a batch of one walks on its row (random_walk.per_query): the walk
    # starts' sorts are 1-D, not (1, W + n) operands tiled (1, 128)
    assert _sort_shapes(compiled) == {(WALKS + n,)}


def test_sharded_step_compiles_for_2x2(topo, web_stanford, tpu_dispatch):
    """``serve --devices 4``: the push table sharded by virtual row over a
    2x2 mesh, partial frames combined by one all-reduce per sweep."""
    ws, rp = web_stanford, web_stanford["rp"]
    n, width, k, batch = ws["n"], ws["width"], 4, 8
    rows = -(-ws["rows"] // k) * k
    mesh = Mesh(np.array(topo.devices[:k]), ("shard",))
    row, rep = NamedSharding(mesh, P("shard", None)), NamedSharding(mesh, P())

    def shape(s, d, sharding=rep):
        return jax.ShapeDtypeStruct(s, d, sharding=sharding)

    exe = _fora_fused_sharded_exe(
        mesh, "shard", k, True, True, rp.alpha, rp.rmax, rp.omega, n,
        WALKS, ws["steps"], 10_000, None, False, 256, True)
    compiled = exe.lower(
        shape((rows, width), jnp.int32, row),
        shape((rows, width), jnp.bool_, row),
        shape((rows, width), jnp.float32, row),
        shape((rows,), jnp.int32, NamedSharding(mesh, P("shard"))),
        shape((ws["m"],), jnp.int32), shape((n + 1,), jnp.int32),
        shape((n,), jnp.int32), shape((batch,), jnp.int32),
        shape((2,), jnp.uint32), shape((batch,), jnp.int32)).compile()
    assert ops.IMPLS == {"ell_spmm_sliced": "xla"}
    assert "all-reduce" in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM_BYTES       # per device
    assert _scopes(compiled) == {"fora.push", "fora.walk_starts",
                                 "fora.walk_steps"}
    # a batch of eight sorts its walk starts' rows together
    assert (batch, WALKS + n) in _sort_shapes(compiled)
