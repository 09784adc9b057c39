"""On-chip benchmark of the PPR service's served query path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>
.json``: one graph at its published size, mapped onto 1 or 4 chips) and a
traffic mix (``bench/mixes/<name>.json``). A run:

1. refuses to run without a TPU, or with fewer chips than the cell asks for;
2. turns JAX's persistent compilation cache on (``<checkout>/.jax_cache``,
   or ``$JAX_COMPILATION_CACHE_DIR``);
3. builds the graph from the benchmark's own generator (its seed is the
   configuration's, not the run's) and the job's query sources from the
   mix (its seed is the mix's; ``bench/sources/<kind>.py`` draws them);
   ``--seed`` draws the order of service;
4. builds ``ForaExecutor`` as ``launch/serve.py::serve_ppr`` does and warms
   it up;
5. serves queries in a closed loop, one fused call in flight, for
   ``--seconds``; the call in flight at the end is finished;
6. keeps every answer the timed calls produced, by wrapping the
   ``fora_fused`` that the executor resolves at call time (a reference to
   the device arrays, no sync);
7. after the window, holds a sample of those answers, drawn from the seed,
   to the plain references, and every answer to FORA's walk budget
   (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``checks``,
each number compared beside its limit. With ``--trace 0`` the metrics are
the cell's end-to-end ones, each read by its own file
``bench/end_to_end/<name>.py``; with ``--trace 1`` the window runs under the
profiler and the metrics are the cell's per-layer ones, each read by its own
file ``bench/metrics/<name>.py``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # the harness's start: set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))   # the system under test

from bench import check, graphgen, plugin, reference, roofline  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench import traffic  # noqa: E402

CHECK_ANSWERS = 8   # window answers held to the references in each run


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """Find a cell of ``BENCHMARK.json`` and the files it names."""
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = traffic.load_mix(HERE / "mixes" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [x for x in metrics if name in x.get("workloads", [name])]

    e2e = mine(bench["end_to_end"])
    per_layer = mine(bench["per_layer"])
    for metric in e2e:             # an unknown metric fails here, not later
        host_reader(metric["name"])
    for metric in per_layer:
        reader(metric["name"])
    return Cell(name, cell["chips"], config, mix, e2e, per_layer)


def reader(metric: str):
    """The ``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    return plugin.find("metrics", metric, "read", "per-layer metric")


def host_reader(metric: str):
    """The ``read(window)`` of ``bench/end_to_end/<metric>.py``."""
    return plugin.find("end_to_end", metric, "read", "end-to-end metric")


class CompileClock:
    """Counts backend compiles from its creation until ``close``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.compiles += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class Keeper:
    """Keeps what each ``fora_fused`` call returns while ``on``: the sources
    it was given and its device-resident result, nothing copied."""

    def __init__(self, module):
        self.module, self.orig = module, module.fora_fused
        self.on, self.kept = False, []

    def __call__(self, dg, sources, *args, **kwargs):
        res = self.orig(dg, sources, *args, **kwargs)
        if self.on:
            self.kept.append((np.array(sources, np.int64).reshape(-1), res))
        return res

    def __enter__(self):
        self.module.fora_fused = self
        return self

    def __exit__(self, *exc):
        self.module.fora_fused = self.orig


@contextmanager
def profiled(log_dir: Path | None):
    """The profiler on around the block, writing under ``log_dir``; off
    when there is none. Python frames are not traced: only the harness's
    spans and the device."""
    if log_dir is None:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), create_perfetto_trace=True,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def build(cell: Cell):
    """The generator's edge list and the warmed-up executor."""
    from repro.ppr.executor import ForaExecutor, PprWorkload
    from repro.ppr.fora import ForaParams
    from repro.ppr.graph import Graph

    cfg = cell.config
    n, directed = cfg["n"], cfg["directed"]
    edges = cfg["m"] if directed else cfg["m"] // 2
    src, dst = graphgen.generate(n, edges, directed=directed,
                                 seed=cfg["graph_seed"],
                                 max_in_degree=cfg["max_in_degree"])
    graph = Graph.from_edges(n, src, dst, directed=directed, name=cfg["name"])
    if graph.m != cfg["m"]:
        raise RuntimeError(f"{cfg['name']}: built {graph.m} arcs, the "
                           f"configuration states {cfg['m']}")
    arc_src, _ = reference.arcs(n, src, dst, directed)
    sources = traffic.job_sources(cell.mix, n,
                                  np.bincount(arc_src, minlength=n))
    workload = PprWorkload(graph=graph, num_queries=sources.size,
                           seed=cell.mix["job_seed"])
    workload.sources = sources
    executor = ForaExecutor(
        workload=workload,
        params=ForaParams(alpha=cfg["alpha"], epsilon=cfg["epsilon"]),
        block_size=cell.mix["block_size"], fused=True, ell_layout="auto",
        walk_safety=cfg["walk_safety"], devices=cfg["devices"],
        index_budget=0)
    executor.warmup()
    return (src, dst), executor


def serve(executor, order, block: int,
          seconds: float) -> tuple[list, float, float]:
    """The closed loop: one call of ``block`` queries, the next in
    ``order``, in flight until ``seconds`` have passed. Returns each
    answer's latency, the window's start and the last completion
    (perf_counter seconds)."""
    latencies, at = [], 0
    start = time.perf_counter()
    deadline = start + seconds
    done = start
    with jax.profiler.TraceAnnotation("bench.window"):
        while done < deadline:
            if at + block > len(order):
                raise RuntimeError(f"the window served all {len(order)} "
                                   "queries")
            ids = [int(q) for q in order[at:at + block]]
            at += block
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.answer"):
                executor(ids)
            done = time.perf_counter()
            latencies.extend([done - t] * block)
    return latencies, start, done


def answers_of(kept) -> list[dict]:
    """One record per answer of the window, in order (host scalars)."""
    out = []
    for call, (sources, res) in enumerate(kept):
        r_sum = np.asarray(res.residual_mass, np.float64)
        lanes = np.asarray(res.walks_effective)
        sweeps = int(res.push_iters)
        for i, s in enumerate(sources):
            out.append(dict(call=call, row=i, source=int(s), r_sum=r_sum[i],
                            lanes=int(lanes[i]), sweeps=sweeps,
                            batch=sources.size))
    return out


def sample(answers: list[dict], size: int, seed: int) -> list[int]:
    """Indices of the answers to check: the one with the most push sweeps
    (the hardest source), then others drawn from the seed."""
    hardest = max(range(len(answers)), key=lambda i: answers[i]["sweeps"])
    rest = [i for i in range(len(answers)) if i != hardest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(size, len(answers)) - 1,
                      replace=False)
    return sorted([hardest] + [rest[i] for i in pick])


def judge(cell: Cell, edges, answers, kept, seed: int, rmax: float) -> dict:
    """The check's numbers, over a sample of the window's answers."""
    cfg = cell.config
    n = cfg["n"]
    picked = sample(answers, CHECK_ANSWERS, seed)
    rows = [answers[i] for i in picked]
    pi_hat = np.stack([np.asarray(kept[a["call"]][1].pi[a["row"]],
                                  np.float64) for a in rows])
    r_sum = np.array([a["r_sum"] for a in rows])
    sources = np.array([a["source"] for a in rows])
    src, dst = reference.arcs(n, *edges, cfg["directed"])
    pt = reference.transition_t(n, src, dst)
    pi = reference.exact_ppr(pt, sources, alpha=cfg["alpha"])
    r_ref, _ = reference.push_reference(
        pt, np.bincount(src, minlength=n), sources, alpha=cfg["alpha"],
        rmax=rmax)
    return check.numbers(pi_hat, r_sum, pi, r_ref, delta=1.0 / n)


def run(cell: Cell, *, seed: int, seconds: float, traced: bool) -> dict:
    """One run of ``cell``; returns the result line's object."""
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.ppr import executor as executor_module

    enable_compilation_cache()
    clock = CompileClock()
    log_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if traced \
        else None
    try:
        with jax.profiler.TraceAnnotation("bench.setup"):
            edges, executor = build(cell)
        devices = jax.devices()[:cell.chips]
        cfg = cell.config
        rmax, omega = reference.fora_params(cfg["n"], cfg["m"],
                                            cfg["epsilon"])
        with Keeper(executor_module) as keeper:
            before = clock.compiles
            setup_s = time.perf_counter() - T0
            with profiled(log_dir):
                keeper.on = True
                latencies, start, last = serve(
                    executor, traffic.order(cell.mix, seed),
                    cell.mix["block_size"], seconds)
                keeper.on = False
            window_compiles = clock.compiles - before
        stats = [d.memory_stats() or {} for d in devices]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        answers = answers_of(keeper.kept)
        print(f"window: {len(answers)} answers in {last - start:.3f} s, "
              f"{window_compiles} backend compiles inside it, {before} in "
              f"set-up ({setup_s:.3f} s)")
        r_sums = [a["r_sum"] for a in answers]
        lanes = [a["lanes"] for a in answers]
        for line in check.walk_budget_lines([a["source"] for a in answers],
                                            r_sums, lanes, omega):
            print(line)
        with jax.profiler.TraceAnnotation("bench.check"):
            values = judge(cell, edges, answers, keeper.kept, seed, rmax)
        values["walks_short"] = check.walks_short(r_sums, lanes, omega)
        print(f"walks: {values['walks_short']} of {len(answers)} answers "
              "drew fewer walk lanes than FORA's budget ceil(r_sum * omega)")
        limits = cfg["limits"]
        correct = check.verdict(values, limits)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(peak)}
        result = {"correct": correct, "attempted": len(answers), "failed": 0}
        if traced:
            result.update(per_layer(cell, log_dir, answers, omega,
                                    devices[0].device_kind, device))
        else:
            window = Window(latencies, start, last, setup_s)
            result["metrics"] = {
                m["name"]: {"value": host_reader(m["name"])(window),
                            "unit": m["unit"]}
                for m in cell.end_to_end}
        result["device"] = device
        result["checks"] = {k: {"value": values[k], "limit": limits[k]}
                            for k in check.NAMES}
        for k in check.NAMES:
            print(f"check {k} = {values[k]!r} (limit {limits[k]!r})",
                  file=sys.stderr)
        return result
    finally:
        clock.close()
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)


@dataclass
class Window:
    """What an end-to-end metric's reader is given (perf_counter seconds)."""

    latencies: list[float]    # per answer, dispatch to readout
    start: float              # the window's start
    last: float               # the last completion
    setup_s: float            # the harness's start to the window's


@dataclass
class Context:
    """What a per-layer metric's reader is given."""

    trace: tracing.Trace
    lo: float                 # the traced window, microseconds
    hi: float
    answers: int
    calls: list[dict]         # per fused call: sweeps, batch, r_sum of rows
    n: int
    m: int
    alpha: float
    omega: float
    chips: int
    peaks: dict


def per_layer(cell: Cell, log_dir: Path, answers, omega: float, kind: str,
              device: dict) -> dict:
    """The per-layer metrics of a traced window, and its busy share."""
    tr = tracing.load(log_dir)
    lo, hi = tr.window()
    calls: dict[int, dict] = {}
    for a in answers:
        c = calls.setdefault(a["call"], dict(sweeps=a["sweeps"],
                                             batch=a["batch"], r_sum=[]))
        c["r_sum"].append(a["r_sum"])
    cfg = cell.config
    ctx = Context(tr, lo, hi, len(answers), list(calls.values()), cfg["n"],
                  cfg["m"], cfg["alpha"], omega, cell.chips,
                  roofline.peaks(kind))
    metrics = {}
    for metric in cell.per_layer:
        value = reader(metric["name"])(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    device["busy_s"] = tracing.busy_s(tr, lo, hi)
    device["window_s"] = (hi - lo) / 1e6
    return {"metrics": metrics, "breakdown": tracing.breakdown(tr, lo, hi)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    cell = load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    result = run(cell, seed=args.seed, seconds=args.seconds,
                 traced=bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
