"""Chip smoke test: the served PPR path on one TPU at web-Stanford's size.

Everything runs in this one process:

1. **Main path.** The one-shot D&A_REAL pipeline of ``repro.launch.serve``
   (``serve.main``) on the Table-I web-Stanford graph at its published n
   (281,903 nodes, ``--scale 1``): it samples queries, sizes the cores with
   Lemmas 1-2 and runs every query through ``ForaExecutor``'s fused FORA
   step, under a deadline the chip meets.
2. **Kernel.** The walk-index gather, the Pallas kernel the TPU runs
   compiled, against its XLA oracle at the same n.
3. **Answers.** FORA rows for ``CHECKED`` of the workload's sources (the
   fused call behind ``ForaExecutor.answer_chunk``) against the float32
   power-iteration reference, a COO ``segment_sum`` that shares no code with
   the ELL push or the walks. Every source must meet FORA's guarantee: a
   relative error of at most eps = 0.5 on every target with pi >= 1/n.
   Beside each, the share of the push's arc reads that left the frontier
   and the share of the walks' lane-steps a live, weighted lane began.
4. **Lockstep.** The same sources, one per call, through the fused program
   with the lockstep oracle (``lockstep_residual_walks``: every lane
   stepped every step on draws drawn whole) in place of the compacted
   walks. The compaction must change no bit: the largest |dpi| reads 0.
5. **Walk starts.** The searches that turn a post-push residual's cumsum
   and the lanes' draws into start nodes, at the served lane count, timed
   alone on the residuals of ``STARTS_SOURCES`` of the workload's sources:
   seconds per call (median of ``STARTS_CALLS`` after a warm-up), compile
   seconds, and the lanes whose start differs from ``jnp.searchsorted``'s:
   each must lie on a dip of the cumsum, where the binary search answers
   by the order of its probes. ``merge_sort_back`` is the program's own
   (``random_walk._count_below``, which ``random_walk.per_query`` runs on
   the row of a batch of one), and ``_vmapped`` runs it under ``vmap``
   over one query, on (1, W + n) operands.

With ``--chips 4`` it runs only the node-sharded path (``serve --devices
4``, DESIGN.md §9) and, for the same sources, the one-chip answers it is
compared with; both are checked against the reference, and the sharded
answers against the lockstep oracle. The sharded answers
need not be bit-identical to the one-chip ones on the TPU: the ``psum``
adds the shards' partial frames in another order, so each is held to the
reference at eps (two answers within eps of pi are within 2*eps*pi of each
other), and their max abs difference is printed.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, 2x2

A passing run ends with one JSON line naming the device. Without a TPU, or
when any phase fails, it exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import importlib
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "web-stanford"
SCALE = 1            # the published n of SNAP web-Stanford (paper Table I)
QUERIES = 32
DEADLINE_S = 600.0   # D&A's deadline T for the QUERIES
EPSILON = 0.5        # FORA's eps; delta = p_f = 1/n
CHECKED = 4          # sources compared with the reference
SEED = 0
STARTS_SOURCES = 8   # residuals the walk-start searches are compared on
STARTS_CALLS = 10    # timed calls per search


def _compile_clock() -> dict:
    """Count backend compiles (and persistent-cache hits) from here on."""
    import jax

    clock = {"compiles": 0, "seconds": 0.0, "cache_hits": 0}

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            clock["compiles"] += 1
            clock["seconds"] += secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            clock["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return clock


def _print_impls() -> None:
    """Each dispatched op and the implementation its trace chose (on the
    TPU ``ops`` never picks ``pallas-interpret``)."""
    from repro.kernels import ops

    print("ops: " + ", ".join(f"{op} -> {impl}"
                              for op, impl in sorted(ops.IMPLS.items())))


def _serve(devices: int):
    """The main path, through the launcher a user calls."""
    from repro.launch import serve

    t0 = time.perf_counter()
    executor, res = serve.main([
        "--workload", "ppr", "--dataset", DATASET, "--scale", str(SCALE),
        "--queries", str(QUERIES), "--deadline", str(DEADLINE_S),
        "--epsilon", str(EPSILON), "--seed", str(SEED),
        "--devices", str(devices)])
    wall = time.perf_counter() - t0
    graph, dg = executor.workload.graph, executor._device_graph
    resident = sum(v.nbytes for v in vars(dg).values()
                   if hasattr(v, "nbytes") and hasattr(v, "sharding"))
    print(f"graph: {graph.name} n={graph.n} m={graph.m} layout={dg.layout} "
          f"width={dg.ell_width} push_table_MiB={dg.ell_nbytes / 2**20:.1f} "
          f"resident_MiB={resident / 2**20:.1f} devices={devices}")
    print(f"D&A: cores={res.cores} lemma2_bound_cores="
          f"{res.bounds.lemma2_cores} accepted={res.accepted} "
          f"completion_s={res.completion_time:.3f} deadline_s={DEADLINE_S} "
          f"t_pre_s={res.preprocess_time:.3f} queries={QUERIES} "
          f"wall_s={wall:.1f}")
    if not res.accepted:
        raise SystemExit("FAIL: D&A did not meet the deadline")
    return executor


def _check_answers(executor, qids: list[int], label: str) -> np.ndarray:
    """FORA rows for ``qids`` against the power-iteration reference."""
    from repro.ppr.power_iteration import ppr_power_iteration

    graph = executor.workload.graph
    res = executor.chunk_result(qids)
    k = len(qids)
    pi = np.asarray(res.pi, np.float64)[:k]
    r_sum = np.asarray(res.residual_mass, np.float64)[:k]
    lanes = np.asarray(res.walks_effective)[:k]
    sweeps = int(res.push_iters)
    arcs = np.asarray(res.front_arcs, np.float64)[:k]
    live = np.asarray(res.walk_steps_live, np.float64)[:k]
    ran = np.asarray(res.walk_steps_run, np.float64)[:k]
    sources = np.array([executor.workload.source_of(q) for q in qids])
    want = np.asarray(ppr_power_iteration(graph, sources, alpha=0.2),
                      np.float64)
    omega = executor.params.resolve(graph).omega
    worst = 0.0
    for i, src in enumerate(sources):
        big = want[i] >= 1.0 / graph.n
        err = float((np.abs(pi[i] - want[i])[big] / want[i][big]).max())
        worst = max(worst, err)
        budget = math.ceil(r_sum[i] * omega)
        short = " (under FORA's budget)" if lanes[i] < budget else ""
        print(f"check[{label}] source={src} r_sum={r_sum[i]:.6g} "
              f"walk_lanes={lanes[i]} fora_budget={budget}{short} "
              f"targets_pi>=1/n={int(big.sum())} max_rel_err={err:.4f}")
        # how much of the work carried weight: the arcs leaving the
        # frontier of the push_iters * m the sweeps read, and the walk
        # lane-steps begun by a live, weighted lane
        print(f"work[{label}] source={src} sweeps={sweeps} "
              f"front_arcs={arcs[i]:.0f} "
              f"frontier_arc_share={arcs[i] / (sweeps * graph.m):.4f} "
              f"walk_steps_live={live[i]:.0f} walk_steps_run={ran[i]:.0f} "
              f"live_step_share={live[i] / ran[i]:.4f}")
    verdict = "PASS" if worst <= EPSILON else "FAIL"
    print(f"check[{label}] {verdict}: max_rel_err={worst:.4f} "
          f"eps={EPSILON} over {k} sources")
    if worst > EPSILON:
        raise SystemExit(f"FAIL: {label} answers miss eps={EPSILON}")
    return pi


@contextlib.contextmanager
def _lockstep_walks():
    """``fora_fused`` traced afresh with the lockstep oracle as its walk
    phase: new executables (a new function object for each, so no earlier
    trace is reused) over ``lockstep_residual_walks``."""
    import jax

    from repro.ppr.random_walk import lockstep_residual_walks

    fora = importlib.import_module("repro.ppr.fora")

    names = ("residual_walks", "_fora_fused", "_fora_fused_donating",
             "_fora_fused_sharded_exe")
    saved = {name: getattr(fora, name) for name in names}
    impl = functools.wraps(fora._fora_fused_impl)(
        lambda *a, **k: fora._fora_fused_impl(*a, **k))
    fora.residual_walks = lockstep_residual_walks
    fora._fora_fused = jax.jit(impl, static_argnames=fora._FUSED_STATICS)
    fora._fora_fused_donating = jax.jit(
        impl, static_argnames=fora._FUSED_STATICS,
        donate_argnames=("sources",))
    fora._fora_fused_sharded_exe = saved["_fora_fused_sharded_exe"].__wrapped__
    try:
        yield
    finally:
        for name, value in saved.items():
            setattr(fora, name, value)


def _check_lockstep(executor, qids: list[int], label: str) -> None:
    """Each source's fused answer (one per call, as served) against the
    same call on the lockstep oracle, with the share of the compacted
    walks' lane-steps that a live, weighted lane began."""
    from repro.ppr.random_walk import walk_length_for_tail

    fora = importlib.import_module("repro.ppr.fora")

    def answer(q: int):
        return fora.fora_fused(
            executor._device_graph,
            np.array([executor.workload.source_of(q)], np.int32),
            executor.params, executor._base_key(),
            num_walks=executor._num_walks,
            query_seeds=np.array([q], np.int32),
            bulk_rng=executor._bulk_rng)

    got = [answer(q) for q in qids]
    with _lockstep_walks():
        want = [answer(q) for q in qids]
    # the oracle steps every lane every step: proof that it ran
    steps = walk_length_for_tail(executor.params.alpha,
                                 executor.params.walk_tail)
    if any(float(ref.walk_steps_run[0]) != ref.walks_budget * steps
           for ref in want):
        raise SystemExit(f"FAIL: {label} lockstep oracle did not run")
    worst = 0.0
    for q, res, ref in zip(qids, got, want):
        gap = float(np.abs(np.asarray(res.pi) - np.asarray(ref.pi)).max())
        worst = max(worst, gap)
        live = float(res.walk_steps_live[0])
        ran = float(res.walk_steps_run[0])
        print(f"lockstep[{label}] source={executor.workload.source_of(q)} "
              f"walk_lanes={int(res.walks_effective[0])} "
              f"max_abs_dpi={gap:.6g} walk_steps_live={live:.0f} "
              f"walk_steps_run={ran:.0f} live_step_share={live / ran:.4f}")
    print(f"lockstep[{label}] max_abs_dpi={worst:.6g} over {len(qids)} "
          f"sources")
    if worst != 0.0:
        raise SystemExit(f"FAIL: {label} answers differ from the lockstep "
                         f"walks")


def _check_walk_gather(n: int) -> None:
    """The walk-index gather kernel against its oracle at the graph's n."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    width, batch = 128, 8
    k = jax.random.split(jax.random.PRNGKey(SEED), 4)
    endpoints = jax.random.randint(k[0], (n, width), 0, n, jnp.int32)
    budget = jax.random.randint(k[1], (n,), 0, width + 1, jnp.int32)
    starts = jax.random.randint(k[2], (batch, width), 0, n, jnp.int32)
    weights = jax.random.uniform(k[3], (batch, width), jnp.float32)
    got = jax.jit(ops.walk_endpoint_gather)(endpoints, budget, starts,
                                            weights)
    want = jax.jit(ref.walk_endpoint_gather_ref)(endpoints, budget, starts,
                                                 weights)
    err = float(jnp.abs(got - want).max())
    print(f"walk_endpoint_gather: n={n} lanes={width} batch={batch} "
          f"max_abs_err={err:.3g}")
    if not np.allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                       atol=1e-6):
        raise SystemExit("FAIL: walk_endpoint_gather differs from its oracle")


def _start_searches() -> dict:
    """Candidate searches for the walk starts: (csum, draws) -> each draw's
    start before the clip to n - 1."""
    import jax
    import jax.numpy as jnp

    from repro.ppr import random_walk as rw

    def merge_permute_back(csum, u):
        # the merge of _count_below, then the counts scattered back to lane
        # order (cumsum entries to the slots past the lanes)
        lanes = u.shape[0]
        tags = jnp.concatenate([jnp.arange(lanes, dtype=jnp.int32),
                                jnp.full(csum.shape, lanes, jnp.int32)])
        _, tags = jax.lax.sort(
            (rw._sort_key(jnp.concatenate([u, csum])), tags), num_keys=1,
            is_stable=True)
        entry = tags == lanes
        below = rw._rank(entry)
        dest = jnp.where(entry, lanes + below - 1, tags)
        return rw._permute(below, dest)[:lanes]

    def one_row(search):
        # under vmap over a batch of one query, as the walks ran before
        # per_query: every operand (1, W + n)
        return lambda c, u: jax.vmap(search)(c[None], u[None])[0]

    return {
        "searchsorted_scan": lambda c, u: jnp.searchsorted(c, u),
        "searchsorted_sort": lambda c, u: jnp.searchsorted(c, u,
                                                           method="sort"),
        "merge_sort_back": rw._count_below,
        "merge_permute_back": merge_permute_back,
        "merge_sort_back_vmapped": one_row(rw._count_below),
    }


def _off_dips(csum: np.ndarray, got: np.ndarray, want: np.ndarray) -> int:
    """Lanes whose two starts differ with no cumsum dip (csum[d + 1] <
    csum[d]) for d in [min - 1, max] of the two: a search at fault."""
    dips = np.append(np.flatnonzero(np.diff(csum) < 0), csum.size)
    lo, hi = np.minimum(got, want), np.maximum(got, want)
    lo, hi = lo[got != want] - 1, hi[got != want]
    return int((dips[np.searchsorted(dips, lo)] > hi).sum())


def _bench_walk_starts(dg, params, sources, key, num_walks: int) -> None:
    """Time each walk-start search on post-push residuals of ``sources``
    (source i as query i: its draws from ``fold_in(key, i)``, as served),
    and count the lanes whose start differs from the binary search's."""
    import jax
    import jax.numpy as jnp

    from repro.ppr.forward_push import forward_push

    rp = params.resolve(dg)
    k = len(sources)
    seeds = jnp.zeros((k, dg.n), jnp.float32).at[
        jnp.arange(k), jnp.asarray(sources)].set(1.0)
    push = forward_push(dg.in_neighbors, dg.in_mask, dg.in_weights,
                        dg.out_degree, seeds, alpha=rp.alpha, rmax=rp.rmax,
                        n=dg.n, row_map=dg.in_row_map, block_n=dg.block_n)

    @jax.jit
    def inputs(r, q):
        # sample_walk_starts' cumsum and draws
        k_start, _ = jax.random.split(jax.random.fold_in(key, q))
        u = jax.random.uniform(k_start, (num_walks,)) * r.sum()
        return jnp.cumsum(r), u

    rows = [inputs(push.r[i], i) for i in range(k)]
    dips = sum(int(jnp.sum(jnp.diff(c) < 0)) for c, _ in rows)
    print(f"walk_starts: lanes={num_walks} n={dg.n} sources={k} "
          f"cumsum_dips={dips}")
    want = None
    for name, fn in _start_searches().items():
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*rows[0]).compile()
        compile_s = time.perf_counter() - t0
        got = [exe(c, u).block_until_ready() for c, u in rows]
        times = []
        for _ in range(STARTS_CALLS):
            t0 = time.perf_counter()
            exe(*rows[0]).block_until_ready()
            times.append(time.perf_counter() - t0)
        if want is None:
            want = got
        differ = [int(jnp.sum(g != w)) for g, w in zip(got, want)]
        off = sum(_off_dips(*(np.asarray(x) for x in (c, g, w)))
                  for (c, _), g, w in zip(rows, got, want))
        print(f"walk_starts[{name}] s_per_call={np.median(times):.6f} "
              f"min_s={min(times):.6f} compile_s={compile_s:.2f} "
              f"lanes_differing={differ} off_dips={off}")
        if off:
            raise SystemExit(f"FAIL: walk starts of {name} differ from the "
                             f"binary search's away from any cumsum dip")


def _timed(phase: str, fn, *args):
    """Run one phase and print how long it took."""
    t0 = time.perf_counter()
    out = fn(*args)
    print(f"phase {phase}: {time.perf_counter() - t0:.1f} s")
    return out


def run(chips: int) -> None:
    """The phases for one chip, or the sharded comparison for ``chips``."""
    qids = list(range(CHECKED))
    if chips == 1:
        executor = _timed("serve", _serve, 1)
        _timed("walk_gather", _check_walk_gather, executor.workload.graph.n)
        _print_impls()
        _timed("check", _check_answers, executor, qids, "1-chip")
        _timed("lockstep", _check_lockstep, executor, qids, "1-chip")
        _timed("walk_starts", _bench_walk_starts, executor._device_graph,
               executor.params,
               [executor.workload.source_of(q) for q in range(STARTS_SOURCES)],
               executor._base_key(), executor._num_walks)
        return
    sharded = _timed("serve", _serve, chips)
    _print_impls()
    pi_k = _timed("check", _check_answers, sharded, qids, f"{chips}-chip")
    _timed("lockstep", _check_lockstep, sharded, qids, f"{chips}-chip")
    one_chip = dataclasses.replace(sharded, devices=1)
    pi_1 = _timed("check", _check_answers, one_chip, qids, "1-chip")
    print(f"sharded vs 1-chip: max_abs_diff={np.abs(pi_k - pi_1).max():.6g} "
          f"bit_identical={bool(np.array_equal(pi_k, pi_1))}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the node-sharded path and its one-chip "
                         "comparison only")
    args = ap.parse_args(argv)
    # a run cut short still shows how far it got
    sys.stdout.reconfigure(line_buffering=True)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import cache_entries, enable_compilation_cache

    cache = enable_compilation_cache()
    entries = cache_entries(cache)
    clock = _compile_clock()
    t0 = time.perf_counter()
    run(args.chips)
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in devices[:args.chips])
    print(f"compile: {clock['compiles']} backend compiles, "
          f"{clock['seconds']:.1f} s, {clock['cache_hits']} persistent-cache "
          f"hits")
    print(f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB)")
    print(f"compile cache: {cache} entries {entries} -> "
          f"{cache_entries(cache)}")
    print(f"total_s={time.perf_counter() - t0:.1f}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
