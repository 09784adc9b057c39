"""Readings of the control, for setting the check's limits.

    python3 bench/control.py --config web-stanford --seeds 11 12 13

For each seed it takes the first ``--answers`` sources that a run's window
serves, computes the plain references of ``reference.py`` (float64, on the
host) and the control (the same references in bfloat16, with JAX on the
device, put in the program's place), and prints the check's numbers for the
control as one JSON line per seed, with the verdict that the configuration's
limits give. With ``--float32`` it reads the same references computed in
float32 as well: a witness that the comparison passes a sound computation
at the configuration's precision. It exits non-zero where the control comes
out correct on any seed, or the witness does not. The benchmark's runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from bench import check, graphgen, reference, traffic  # noqa: E402

CONTROL = "bfloat16"


def readings(config: dict, seed: int, answers: int,
             dtypes: tuple[str, ...]) -> dict:
    """The check's numbers of the references computed in each of
    ``dtypes``, against the float64 references, for one seed's sources,
    each with the verdict of the configuration's limits."""
    n, directed = config["n"], config["directed"]
    edges = config["m"] if directed else config["m"] // 2
    src, dst = graphgen.generate(n, edges, directed=directed,
                                 seed=config["graph_seed"],
                                 max_in_degree=config["max_in_degree"])
    src, dst = reference.arcs(n, src, dst, directed)
    mix = traffic.load_mix(HERE / "mixes" / "uniform.json")
    job = traffic.job_sources(mix, n, np.bincount(src, minlength=n))
    sources = job[traffic.order(mix, seed)[:answers]]
    alpha, eps = config["alpha"], config["epsilon"]
    rmax, _ = reference.fora_params(n, src.size, eps)
    pt = reference.transition_t(n, src, dst)
    pi = reference.exact_ppr(pt, sources, alpha=alpha)
    r_sum, _ = reference.push_reference(
        pt, np.bincount(src, minlength=n), sources, alpha=alpha, rmax=rmax)
    out = {"seed": seed, "sources": sources.tolist()}
    for dtype in dtypes:
        t = time.perf_counter()
        got, got_r = reference.control_answers(
            n, src, dst, sources, alpha=alpha, rmax=rmax,
            steps=reference.iterations(alpha, 1e-12), dtype=dtype)
        # the references compute each row exactly and sample no walks, so
        # they draw no fewer than FORA's budget
        values = dict(check.numbers(got, got_r, pi, r_sum, delta=1.0 / n),
                      walks_short=0)
        out[dtype] = dict(values,
                          correct=check.verdict(values, config["limits"]))
        out[dtype + "_s"] = time.perf_counter() - t
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--answers", type=int, default=8)
    ap.add_argument("--float32", action="store_true")
    args = ap.parse_args(argv)
    config = json.loads((HERE / "configs" / f"{args.config}.json")
                        .read_text())
    import jax

    print(f"control: {args.config} on {jax.devices()[0].device_kind}",
          flush=True)
    failed = []
    for i, seed in enumerate(args.seeds):
        dtypes = (CONTROL, "float32") if args.float32 and i == 0 \
            else (CONTROL,)
        got = readings(config, seed, args.answers, dtypes)
        print(json.dumps(got), flush=True)
        if got[CONTROL]["correct"]:
            failed.append(f"seed {seed}: the {CONTROL} control is correct")
        if "float32" in got and not got["float32"]["correct"]:
            failed.append(f"seed {seed}: the float32 witness is not correct")
    for line in failed:
        print(f"control: {line}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
