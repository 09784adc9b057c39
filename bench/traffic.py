"""The one traffic generator: a mix file's parameters and a seed give the
query stream.

A mix (``bench/mixes/<name>.json``) states:

- ``sources``: how the job's query sources are drawn over the graph's n
  nodes: ``{"kind": <kind>, ...}`` names the generator
  ``bench/sources/<kind>.py``, whose ``draw(rng, size, *, n, out_degree,
  **params)`` is given the object's other keys. ``{"kind": "uniform"}`` is
  the paper's draw, and ``PprWorkload``'s.
- ``queries``: how many queries the job holds, more than any window serves.
- ``job_seed``: the seed of the job's sources. The job is fixed, as a
  deployment's graph is: the executor sizes its walk lanes from a sample of
  the job's queries, so a job drawn anew for every run would change the
  work per answer from run to run.
- ``block_size``: queries per fused call (the executor's ``block_size``).

A run's seed draws the order in which the job's queries are served. The
harness serves them in a closed loop: the next call is made when the last
one has returned.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import plugin

KEYS = {"sources", "queries", "job_seed", "block_size"}


def generator(kind: str):
    """The ``draw`` of ``bench/sources/<kind>.py``."""
    return plugin.find("sources", kind, "draw", "source kind")


def load_mix(path: Path) -> dict:
    """Read and validate a mix file."""
    mix = json.loads(Path(path).read_text())
    if set(mix) != KEYS:
        raise ValueError(f"{path}: a mix has exactly the keys {sorted(KEYS)}")
    generator(mix["sources"].get("kind", ""))   # an unknown kind fails here
    for key in ("queries", "block_size"):
        if not isinstance(mix[key], int) or mix[key] < 1:
            raise ValueError(f"{path}: {key} must be a positive integer")
    if not isinstance(mix["job_seed"], int) or mix["job_seed"] < 0:
        raise ValueError(f"{path}: job_seed must be a whole number >= 0")
    return mix


def job_sources(mix: dict, n: int, out_degree: np.ndarray) -> np.ndarray:
    """The source of each of the job's queries, by query id."""
    params = dict(mix["sources"])
    draw = generator(params.pop("kind"))
    rng = np.random.default_rng([mix["job_seed"], 0])
    sources = np.asarray(draw(rng, mix["queries"], n=n,
                              out_degree=out_degree, **params), np.int64)
    if sources.shape != (mix["queries"],) or sources.min() < 0 \
            or sources.max() >= n:
        raise ValueError(f"source kind {mix['sources']['kind']!r} drew "
                         "sources outside the graph")
    return sources


def order(mix: dict, seed: int) -> np.ndarray:
    """The query ids in the order the seed's run serves them."""
    return np.random.default_rng([seed, 0]).permutation(mix["queries"])
