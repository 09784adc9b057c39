"""The FORA path's tracing: the device scopes in its executables, the work
counters it returns beside ``push_iters``, and the executor's host spans.

- Scopes: every executable of ``fora_fused`` (one device, four devices,
  walk-index backed) names its layers ``fora.push``, ``fora.walk_starts``
  and ``fora.walk_steps`` in its ops' metadata, inside the nested jits'
  names (``jit(forward_push)``, ``jit(residual_walks)``) a trace reduction
  may also read.
- Counters: ``front_arcs`` against a numpy recount of each sweep's
  frontier, ``walk_steps_live`` against a host re-simulation on the same
  draws, ``walk_steps_run`` against lanes x steps, and the sharded counts
  against the one-device counts on four virtual devices.
- Spans: ``fora.call``/``fora.stage``/``fora.enqueue``/``fora.wait`` and
  the ``fora.warmup`` family land in a profiler trace, nested as named;
  ``serve --profile`` writes one.

The four-device cases run in a child process, because the device count is
fixed when JAX starts.
"""

from __future__ import annotations

import gzip
import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import WalkIndex
from repro.ppr import (ForaExecutor, ForaParams, PprWorkload,
                       small_test_graph)
from repro.ppr.forward_push import forward_push
from repro.ppr.random_walk import WalkMass, lane_streams, residual_walks
from test_sliced_ell import powerlaw_graph

fora = importlib.import_module("repro.ppr.fora")

ROOT = Path(__file__).resolve().parents[1]
PARAMS = ForaParams(alpha=0.2, epsilon=0.5)
SCOPES = ("fora.push", "fora.walk_starts", "fora.walk_steps")
SOURCES = np.array([0, 7, 42], np.int32)


def _graph(kind: str):
    if kind == "dense":
        return small_test_graph(n=120, avg_deg=6, seed=0)
    return powerlaw_graph(300, seed=4)


def op_names(run) -> set[str]:
    """The ``op_name`` metadata of the compiled executable that ``run``
    (``fora._stage``'s bound call) would execute."""
    text = run.func.lower(*run.args, **run.keywords).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _staged(dg, num_walks=256, index=None, bulk_rng=None):
    run, _ = fora._stage(dg, SOURCES, PARAMS, jax.random.PRNGKey(5),
                         num_walks=num_walks, force=None, index=index,
                         query_seeds=SOURCES, bulk_rng=bulk_rng)
    return run


def _held(names, part):
    return [n for n in names if part in n]


def _alive_steps(us: np.ndarray, weighted: np.ndarray, alpha: float) -> int:
    """Host re-simulation: lane-steps begun alive by a weighted lane, under
    the step draws ``us`` (steps, lanes) — a lane dies at the first draw
    under floor(alpha * 2^30)."""
    bound = int(np.floor(alpha * (1 << 30)))
    alive = np.ones(us.shape[1], bool)
    live = 0
    for u in us:
        live += int((alive & weighted).sum())
        alive &= u >= bound
    return live


# ---------------------------------------------------------------------------
# scopes


@pytest.mark.parametrize("kind", ["dense", "sliced"])
def test_single_device_executable_names_its_layers(kind):
    names = op_names(_staged(_graph(kind).device()))
    for part in SCOPES + ("jit(forward_push)", "jit(residual_walks)"):
        assert _held(names, part), part
    # the scopes wrap the nested jits: the old names stay, inside the new
    assert all("fora.push" in n for n in _held(names, "jit(forward_push)"))
    inner = [n.split("jit(residual_walks)", 1)[1].lstrip(")/")
             for n in _held(names, "jit(residual_walks)")]
    inner = [rest for rest in inner if rest]
    assert inner and all(rest.startswith("fora.walk_") for rest in inner)
    assert any(n.endswith("/sort") for n in _held(names, "fora.walk_starts"))
    assert not _held(names, "fora.walk_starts/fora.walk_steps")


def test_index_backed_executable_names_its_layers():
    g = _graph("dense")
    rp = PARAMS.resolve(g)
    idx = WalkIndex.build(g.device(), width=64, alpha=rp.alpha,
                          walk_tail=rp.walk_tail, seed=3)
    names = op_names(_staged(g.device(), index=idx))
    for part in SCOPES + ("jit(forward_push)",):
        assert _held(names, part), part
    # the table gather and the live shortfall are walk steps
    steps = _held(names, "fora.walk_steps")
    assert any(n.endswith("/gather") for n in steps)
    assert any(n.endswith("/while") for n in steps)
    # this path samples its starts without residual_walks
    assert not _held(names, "jit(residual_walks)")


# ---------------------------------------------------------------------------
# counters


@pytest.mark.parametrize("kind", ["dense", "sliced"])
def test_front_arcs_equal_a_numpy_recount(kind):
    g = _graph(kind)
    dg = g.device()
    rp = PARAMS.resolve(g)
    seeds = np.zeros((3, g.n), np.float32)
    seeds[np.arange(3), SOURCES] = 1.0

    def push(sweeps):
        return forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                            dg.out_degree, jnp.asarray(seeds),
                            alpha=rp.alpha, rmax=rp.rmax, n=g.n,
                            max_iters=sweeps, row_map=dg.in_row_map)

    full = push(10_000)
    deg = np.asarray(dg.out_degree).astype(np.float32)
    threshold = np.float32(rp.rmax) * np.maximum(deg, np.float32(1.0))
    want = np.zeros(3)
    for k in range(int(full.iters)):
        r = np.asarray(push(k).r)       # the residual sweep k starts from
        want += ((r > threshold[None, :]) * deg[None, :]).sum(axis=1)
    assert want.min() > 0
    np.testing.assert_array_equal(np.asarray(full.front_arcs), want)


@pytest.mark.parametrize("bulk", [True, False])
@pytest.mark.parametrize("active", [None, 100])
def test_walk_steps_live_equal_a_host_resimulation(bulk, active):
    g = _graph("dense")
    dg = g.device()
    lanes, steps = 256, 42
    residual = jnp.asarray(
        np.random.default_rng(0).random(g.n).astype(np.float32))
    key = jax.random.PRNGKey(11)
    out = residual_walks(
        dg.edge_dst, dg.out_offsets, dg.out_degree, residual, key,
        alpha=PARAMS.alpha, n=g.n, num_walks=lanes, num_steps=steps,
        active_walks=None if active is None else jnp.int32(active),
        bulk_rng=bulk)
    assert isinstance(out, WalkMass)
    _, k_walk = jax.random.split(key)
    if bulk:
        us = np.asarray(jax.random.randint(k_walk, (steps, lanes), 0,
                                           1 << 30))
    else:
        us = np.stack([np.asarray(jax.random.randint(k, (lanes,), 0, 1 << 30))
                       for k in jax.random.split(k_walk, steps)])
    weighted = np.arange(lanes) < (lanes if active is None else active)
    assert float(out.steps_live) == _alive_steps(us, weighted, PARAMS.alpha)
    assert float(out.steps_run) == lanes * steps


def test_fused_counters_are_their_parts():
    """``fora_fused``'s counters per row: the push's ``front_arcs``, the
    walks' live steps under each row's effective budget, and lanes x
    steps run."""
    g = _graph("sliced")
    dg = g.device()
    rp = PARAMS.resolve(g)
    lanes = 512
    res = fora.fora_fused(dg, SOURCES, PARAMS, jax.random.PRNGKey(5),
                          num_walks=lanes, query_seeds=SOURCES, bulk_rng=True)
    seeds = np.zeros((3, g.n), np.float32)
    seeds[np.arange(3), SOURCES] = 1.0
    push = forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                        dg.out_degree, jnp.asarray(seeds), alpha=rp.alpha,
                        rmax=rp.rmax, n=g.n, row_map=dg.in_row_map)
    np.testing.assert_array_equal(np.asarray(res.front_arcs),
                                  np.asarray(push.front_arcs))
    steps = fora.walk_length_for_tail(rp.alpha, rp.walk_tail)
    np.testing.assert_array_equal(np.asarray(res.walk_steps_run),
                                  np.full(3, lanes * steps, np.float32))
    live = np.asarray(res.walk_steps_live)
    w_eff = np.asarray(res.walks_effective)
    for i, q in enumerate(SOURCES):
        k_walk = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(5),
                                                     q))[1]
        us = np.asarray(jax.random.randint(k_walk, (steps, lanes), 0,
                                           1 << 30))
        assert live[i] == _alive_steps(us, np.arange(lanes) < w_eff[i],
                                       rp.alpha)
    share = np.asarray(res.front_arcs) / (int(res.push_iters) * g.m)
    assert ((0 < share) & (share <= 1)).all()
    assert ((0 < live / lanes / steps) & (live / lanes / steps < 1)).all()


def test_index_backed_counters_count_only_live_lanes():
    g = _graph("dense")
    rp = PARAMS.resolve(g)
    idx = WalkIndex.build(g.device(), width=64, alpha=rp.alpha,
                          walk_tail=rp.walk_tail, seed=3)
    lanes = 256
    res = fora.fora_fused(g.device(), SOURCES, PARAMS, jax.random.PRNGKey(5),
                          num_walks=lanes, index=idx, query_seeds=SOURCES)
    steps = idx.num_steps
    assert not idx.partial
    np.testing.assert_array_equal(
        np.asarray(res.walk_steps_run),
        np.full(3, (lanes - 64) * steps, np.float32))
    us = np.asarray(lane_streams(idx.key, jnp.arange(64, lanes), steps))
    w_eff = np.asarray(res.walks_effective)
    for i in range(3):
        weighted = np.arange(64, lanes) < w_eff[i]
        assert float(res.walk_steps_live[i]) == _alive_steps(
            us, weighted, rp.alpha)


def test_a_walk_phase_that_returns_its_mass_alone_counts_no_steps():
    mass = jnp.ones((3, 10), jnp.float32)
    parts = fora._walk_parts(mass)
    assert parts.mass is mass
    np.testing.assert_array_equal(np.asarray(parts.steps_live), np.zeros(3))
    np.testing.assert_array_equal(np.asarray(parts.steps_run), np.zeros(3))


# ---------------------------------------------------------------------------
# four virtual devices, in a child process

CHILD = """
import importlib, json, re, sys
sys.path.insert(0, {tests!r})
import jax, numpy as np
from jax.sharding import Mesh
from repro.ppr import ForaParams, ShardedDeviceGraph
from test_fora_tracing import PARAMS, SOURCES, _graph, op_names

fora = importlib.import_module("repro.ppr.fora")
assert len(jax.devices()) == 4
mesh = Mesh(np.array(jax.devices()), ("shard",))
out = {{}}
for kind in ("dense", "sliced"):
    g = _graph(kind)
    sdg = ShardedDeviceGraph.from_graph(g, mesh)
    runs = {{}}
    for name, dg in (("one", g.device()), ("four", sdg)):
        res = fora.fora_fused(dg, SOURCES, PARAMS, jax.random.PRNGKey(5),
                              num_walks=512, query_seeds=SOURCES,
                              bulk_rng=False)
        runs[name] = {{k: np.asarray(getattr(res, k)).tolist() for k in (
            "push_iters", "front_arcs", "walk_steps_live",
            "walk_steps_run")}}
    run, _ = fora._stage(sdg, SOURCES, PARAMS, jax.random.PRNGKey(5),
                         num_walks=512, force=None, index=None,
                         query_seeds=SOURCES, bulk_rng=False)
    text = run.func.lower(*run.args).compile().as_text()
    exchanges = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in text.splitlines()
                 if re.search(r" (all-reduce|all-gather)(-start)?\\(", line)
                 and "op_name=" in line]
    out[kind] = dict(runs=runs, layout=sdg.layout,
                     names=sorted(op_names(run)), exchanges=exchanges)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    code = CHILD.format(tests=str(ROOT / "tests"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["dense", "sliced"])
def test_sharded_executable_names_its_layers(four_devices, kind):
    found = four_devices[kind]
    assert found["layout"] == kind
    names = found["names"]
    for part in SCOPES + ("jit(forward_push)", "jit(residual_walks)"):
        assert _held(names, part), part
    # the push's per-sweep exchange sits in fora.push, the endpoint psum
    # in fora.walk_steps
    exchanges = found["exchanges"]
    assert any("fora.push" in n for n in exchanges), exchanges
    assert any("fora.walk_steps" in n for n in exchanges), exchanges


@pytest.mark.parametrize("kind", ["dense", "sliced"])
def test_sharded_counts_equal_single_device_counts(four_devices, kind):
    runs = four_devices[kind]["runs"]
    assert runs["four"] == runs["one"]
    steps = fora.walk_length_for_tail(PARAMS.alpha, PARAMS.walk_tail)
    assert runs["four"]["walk_steps_run"] == [512.0 * steps] * 3


# ---------------------------------------------------------------------------
# host spans


def _spans(log_dir: Path) -> list[tuple[str, float, float, dict]]:
    files = sorted(log_dir.rglob("*.trace.json.gz"))
    assert files, f"no trace under {log_dir}"
    with gzip.open(files[0], "rt") as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    return sorted(((e["name"], float(e["ts"]), float(e["ts"]) + e["dur"],
                    e.get("args", {}))
                   for e in events
                   if e.get("ph") == "X" and e["name"].startswith("fora.")),
                  key=lambda s: s[1])


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_executor_spans_land_in_the_profiler_trace(tmp_path):
    g = _graph("dense")
    ex = ForaExecutor(workload=PprWorkload(graph=g, num_queries=16, seed=0),
                      params=PARAMS)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True,
                             profiler_options=options)
    try:
        ex([3, 5])
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert len(by["fora.warmup"]) == 1
    for name in ("fora.upload", "fora.calibrate", "fora.probe"):
        assert all(_inside(s, by["fora.warmup"]) for s in by[name]), name
    probes = len(ex._probe_qids())
    assert len(by["fora.probe"]) == probes
    calls = by["fora.call"]
    assert len(calls) == probes + 2
    served = [s for s in calls if not _inside(s, by["fora.warmup"])]
    assert [(a["qid"], a["size"]) for *_, a in served] == [("3", "1"),
                                                          ("5", "1")]
    for name in ("fora.stage", "fora.enqueue", "fora.wait"):
        assert all(_inside(s, calls) for s in by[name]), name
    assert len(by["fora.enqueue"]) == len(by["fora.wait"]) == len(calls)
    for call in served:
        kids = [s for s in spans if s[0] != "fora.call" and _inside(s, [call])]
        order = [s[0] for s in kids]
        assert order[0] == "fora.stage" and order[-2:] == ["fora.enqueue",
                                                           "fora.wait"]


def test_serve_profile_writes_a_trace(tmp_path, capsys):
    from repro.launch import serve

    serve.main(["--workload", "ppr", "--dataset", "web-stanford",
                "--scale", "1024", "--queries", "8", "--deadline", "600",
                "--profile", str(tmp_path)])
    assert "profile: trace written under" in capsys.readouterr().out
    names = {s[0] for s in _spans(tmp_path)}
    assert {"fora.warmup", "fora.call", "fora.stage", "fora.enqueue",
            "fora.wait"} <= names
