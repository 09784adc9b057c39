"""The compacted walk phase against the lockstep walks it replaces.

``residual_walks`` steps its lanes in stages that halve as walks stop, and
draws each lane's step draws by its global id (``walk_draws``). Every
lane must still take its lockstep trajectory:

- the masses and ``steps_live`` equal a lockstep reference bit for bit
  (``counted_walk_endpoints`` on the explicitly drawn step draws, then
  ``segment_sum``), and ``steps_run`` equals a host recount of the stage
  schedule, for lane counts above the 1024-lane floor, both streams,
  effective budgets that keep or drop lanes at the first check, and a
  vmapped batch;
- ``walk_draws`` equals ``jax.random.randint(...)[ids]`` for both streams,
  which pins the JAX internal it relies on;
- on four virtual devices, each shard's mass equals the single device's
  over its lanes, the live steps add up to the single device's, and the
  steps run to the sum of the shards' recounts;
- the fused answer equals the fused answer with the lockstep oracle
  ``lockstep_residual_walks`` in place of the compacted walks.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ppr import ForaParams, small_test_graph
from repro.ppr.random_walk import (counted_walk_endpoints,
                                   lockstep_residual_walks, residual_walks,
                                   sample_walk_starts, walk_draws)

fora = importlib.import_module("repro.ppr.fora")

ROOT = Path(__file__).resolve().parents[1]
ALPHA = 0.2
STEPS = 42
FLOOR = 1024


def _graph():
    return small_test_graph(n=300, avg_deg=6, seed=0)


def _residual(seed: int, n: int) -> jax.Array:
    return jnp.asarray(np.random.default_rng(seed).random(n)
                       .astype(np.float32))


def _draws(k_walk, lanes: int, bulk: bool) -> jax.Array:
    """The (STEPS, lanes) step draws as the lockstep walks draw them."""
    if bulk:
        return jax.random.randint(k_walk, (STEPS, lanes), 0, 1 << 30)
    return jnp.stack([jax.random.randint(k, (lanes,), 0, 1 << 30)
                      for k in jax.random.split(k_walk, STEPS)])


def _weights(r_sum, lanes: int, active):
    lane = jnp.arange(lanes)
    if active is None:
        return jnp.full((lanes,), r_sum / lanes, jnp.float32), None
    act = jnp.float32(active)
    weighted = lane < act
    return jnp.where(weighted, r_sum / act, 0.0), weighted


def _reference(dg, residual, key, lanes: int, active, bulk: bool):
    """Lockstep walks on the explicitly drawn step draws: the endpoints,
    the mass, the live lane-steps, the draws and the weighted lanes."""
    starts, r_sum = sample_walk_starts(residual, key, num_walks=lanes,
                                       n=residual.shape[0])
    us = _draws(jax.random.split(key)[1], lanes, bulk)
    weights, weighted = _weights(r_sum, lanes, active)
    endpos, live = counted_walk_endpoints(
        dg.edge_dst, dg.out_offsets, dg.out_degree, starts, us,
        alpha=ALPHA, weighted=weighted)
    mass = jax.ops.segment_sum(weights, endpos, num_segments=residual.shape[0])
    lit = (np.ones(lanes, bool) if weighted is None
           else np.asarray(weighted))
    return dict(endpos=np.asarray(endpos), weights=np.asarray(weights),
                mass=np.asarray(mass), live=float(live), us=np.asarray(us),
                weighted=lit)


def _schedule(us: np.ndarray, weighted: np.ndarray) -> tuple[int, int]:
    """Host recount of the staged walk on the draws ``us`` (steps, lanes):
    the lane-steps begun by a live, weighted lane, and the lane-steps run
    by stages of capacity lanes, lanes/2, ... down to the 1024-lane floor,
    each stepping until its live lanes fit half of it (the last one to the
    end), none past the last step."""
    bound = int(np.floor(ALPHA * (1 << 30)))
    live = weighted.copy()
    counts = []
    for u in us:
        counts.append(int(live.sum()))
        live &= u >= bound
    caps = [us.shape[1]]
    while caps[-1] // 2 >= FLOOR:
        caps.append(caps[-1] // 2)
    t = ran = 0
    for i, cap in enumerate(caps):
        last = i + 1 == len(caps)
        while t < len(us) and (last or counts[t] > cap // 2):
            ran += cap
            t += 1
    return sum(counts), ran


# ---------------------------------------------------------------------------
# the compacted walks against the lockstep reference

CASES = [(lanes, bulk, active, batch)
         for lanes in (4096, 16384)
         for bulk in (True, False)
         for active, batch in ((None, 1), (0.7, 1), (0.5, 1), (0.3, 1),
                               ((1.0, 0.7, 0.3), 3))]


@pytest.mark.parametrize(
    "lanes,bulk,active,batch", CASES,
    ids=[f"{lanes}-{'bulk' if bulk else 'keyed'}-active"
         f"{'/'.join(map(str, np.atleast_1d(active)))}-B{batch}"
         for lanes, bulk, active, batch in CASES])
def test_compacted_walks_equal_lockstep_walks(lanes, bulk, active, batch):
    g = _graph()
    dg = g.device()
    fracs = active if batch > 1 else (active,)
    acts = [None if f is None else int(f * lanes) for f in fracs]
    residuals = jnp.stack([_residual(i, g.n) for i in range(batch)])
    keys = jax.random.split(jax.random.PRNGKey(11), batch)
    kw = dict(alpha=ALPHA, n=g.n, num_walks=lanes, num_steps=STEPS,
              bulk_rng=bulk)
    if batch == 1:
        a = None if acts[0] is None else jnp.int32(acts[0])
        outs = [residual_walks(dg.edge_dst, dg.out_offsets, dg.out_degree,
                               residuals[0], keys[0], active_walks=a, **kw)]
        oracle = [lockstep_residual_walks(
            dg.edge_dst, dg.out_offsets, dg.out_degree, residuals[0],
            keys[0], active_walks=a, **kw)]
    else:
        both = jax.vmap(lambda r, k, a: (
            residual_walks(dg.edge_dst, dg.out_offsets, dg.out_degree, r, k,
                           active_walks=a, **kw),
            lockstep_residual_walks(dg.edge_dst, dg.out_offsets,
                                    dg.out_degree, r, k, active_walks=a,
                                    **kw)))
        got, want = both(residuals, keys, jnp.asarray(acts, jnp.int32))
        outs = [jax.tree.map(lambda x, i=i: x[i], got) for i in range(batch)]
        oracle = [jax.tree.map(lambda x, i=i: x[i], want)
                  for i in range(batch)]
    for i, act in enumerate(acts):
        ref = _reference(dg, residuals[i], keys[i], lanes, act, bulk)
        live, ran = _schedule(ref["us"], ref["weighted"])
        assert ref["live"] == live
        np.testing.assert_array_equal(np.asarray(outs[i].mass), ref["mass"])
        assert float(outs[i].steps_live) == live
        assert float(outs[i].steps_run) == ran
        # the stages step a small part of the lockstep lane-steps; lanes
        # past a budget of half of them leave at the first check
        assert ran < lanes * STEPS
        if act is not None and act <= lanes // 2:
            assert ran <= lanes // 2 * STEPS
        np.testing.assert_array_equal(np.asarray(oracle[i].mass),
                                      ref["mass"])
        assert float(oracle[i].steps_live) == live
        assert float(oracle[i].steps_run) == lanes * STEPS


# ---------------------------------------------------------------------------
# the draw by lane id


def _ids(kind: str, lanes: int) -> np.ndarray:
    rng = np.random.default_rng(1)
    if kind == "unsorted":
        return rng.permutation(lanes)[: lanes // 3].astype(np.int32)
    sparse = rng.choice(lanes, size=37, replace=False)
    return np.concatenate([[lanes - 1], sparse, [0]]).astype(np.int32)


@pytest.mark.parametrize("kind", ["unsorted", "sparse"])
@pytest.mark.parametrize("bulk,lanes", [(True, 1 << 16), (False, 1 << 20)])
def test_walk_draws_equal_randint_at_the_lane_ids(bulk, lanes, kind):
    k_walk = jax.random.split(jax.random.PRNGKey(2024))[1]
    ids = _ids(kind, lanes)
    draw = jax.jit(functools.partial(walk_draws, num_walks=lanes,
                                     num_steps=STEPS, bulk=bulk))
    if bulk:
        table = np.asarray(jax.random.randint(k_walk, (STEPS, lanes), 0,
                                              1 << 30))
    step_keys = jax.random.split(k_walk, STEPS)
    for t in (0, 17, STEPS - 1):
        want = (table[t] if bulk else np.asarray(
            jax.random.randint(step_keys[t], (lanes,), 0, 1 << 30)))[ids]
        got = np.asarray(draw(k_walk, jnp.int32(t), jnp.asarray(ids)))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the fused answer against the fused answer on the lockstep oracle


def _fused(dg, walks, num_walks, sources, bulk):
    """``fora_fused``'s program traced afresh with ``walks`` as its walk
    phase (a new function object, so no earlier trace is reused)."""
    run, _ = fora._stage(dg, sources, ForaParams(alpha=ALPHA),
                         jax.random.PRNGKey(5), num_walks=num_walks,
                         force=None, index=None, query_seeds=sources,
                         bulk_rng=bulk)
    impl = functools.wraps(fora._fora_fused_impl)(
        lambda *a, **k: fora._fora_fused_impl(*a, **k))
    saved = fora.residual_walks
    fora.residual_walks = walks
    try:
        return jax.jit(impl, static_argnames=fora._FUSED_STATICS)(
            *run.args, **run.keywords)
    finally:
        fora.residual_walks = saved


@pytest.mark.parametrize("bulk", [True, False])
def test_fused_answer_equals_the_lockstep_oracle_answer(bulk):
    g = _graph()
    dg = g.device()
    sources = np.array([0, 7, 42], np.int32)
    got = _fused(dg, residual_walks, 4096, sources, bulk)
    want = _fused(dg, jax.jit(lockstep_residual_walks, static_argnames=(
        "n", "num_walks", "num_steps", "bulk_rng", "lanes")),
        4096, sources, bulk)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[5]), np.asarray(want[5]))
    assert (np.asarray(got[6]) < np.asarray(want[6])).all()


# ---------------------------------------------------------------------------
# four virtual devices, in a child process (the device count is fixed when
# JAX starts)

LANES_PER_SHARD = 4096

CHILD = """
import importlib, json, sys
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.ppr import ForaParams, ShardedDeviceGraph
from repro.ppr.random_walk import residual_walks
from test_walk_compaction import (ALPHA, LANES_PER_SHARD, STEPS, _draws,
                                  _graph, _reference, _residual, _schedule)

fora = importlib.import_module("repro.ppr.fora")
assert len(jax.devices()) == 4
mesh = Mesh(np.array(jax.devices()), ("shard",))
g = _graph()
dg = g.device()
lanes, k = 4 * LANES_PER_SHARD, LANES_PER_SHARD
active = int(0.7 * lanes)          # shards 0-1 weighted, 2 in part, 3 not
r, key = _residual(3, g.n), jax.random.PRNGKey(9)


def shard(r, key):
    out = residual_walks(dg.edge_dst, dg.out_offsets, dg.out_degree, r, key,
                         alpha=ALPHA, n=g.n, num_walks=lanes,
                         num_steps=STEPS, active_walks=jnp.int32(active),
                         bulk_rng=False, lanes=k,
                         lane_offset=jax.lax.axis_index("shard") * k)
    return jax.tree.map(lambda x: x[None], out)


parts = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(P(), P()),
                              out_specs=P("shard"), check_vma=False))(r, key)
ref = _reference(dg, r, key, lanes, active, bulk=False)
out = dict(shards=[])
for s in range(4):
    sl = slice(s * k, (s + 1) * k)
    mass = jax.ops.segment_sum(ref["weights"][sl], ref["endpos"][sl],
                               num_segments=g.n)
    live, ran = _schedule(ref["us"][:, sl], ref["weighted"][sl])
    out["shards"].append(dict(
        mass_equal=bool(np.array_equal(np.asarray(parts.mass[s]),
                                       np.asarray(mass))),
        live=float(parts.steps_live[s]), live_want=live,
        run=float(parts.steps_run[s]), run_want=ran))
out["live_one"] = ref["live"]

sources = np.array([0, 7, 42], np.int32)
fused = {{}}
for name, d in (("one", dg), ("four", ShardedDeviceGraph.from_graph(g, mesh))):
    res = fora.fora_fused(d, sources, ForaParams(alpha=ALPHA),
                          jax.random.PRNGKey(5), num_walks=lanes,
                          query_seeds=sources, bulk_rng=False)
    fused[name] = dict(pi=np.asarray(res.pi),
                       live=np.asarray(res.walk_steps_live).tolist(),
                       run=np.asarray(res.walk_steps_run).tolist(),
                       w_eff=np.asarray(res.walks_effective))
run_want = []
for i, q in enumerate(sources):
    k_walk = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(5),
                                                 int(q)))[1]
    us = np.asarray(_draws(k_walk, lanes, bulk=False))
    weighted = np.arange(lanes) < fused["four"]["w_eff"][i]
    run_want.append(sum(_schedule(us[:, s * k:(s + 1) * k],
                                  weighted[s * k:(s + 1) * k])[1]
                        for s in range(4)))
out["fused"] = dict(
    live_one=fused["one"]["live"], live_four=fused["four"]["live"],
    run_four=fused["four"]["run"], run_want=run_want,
    pi_gap=float(np.abs(fused["one"]["pi"] - fused["four"]["pi"]).max()))
print(json.dumps(out))
"""


def test_sharded_compaction_equals_the_single_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    code = CHILD.format(tests=str(ROOT / "tests"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for s in out["shards"]:
        assert s["mass_equal"], s
        assert s["live"] == s["live_want"] and s["run"] == s["run_want"], s
    assert sum(s["live"] for s in out["shards"]) == out["live_one"]
    # the last shard carries no weight: it steps only its last stage
    assert out["shards"][3]["live"] == 0
    assert out["shards"][3]["run"] == FLOOR * STEPS
    fused = out["fused"]
    assert fused["live_four"] == fused["live_one"]
    assert fused["run_four"] == fused["run_want"]
    # the psum adds the shards' partial masses in another order
    assert fused["pi_gap"] < 1e-6
