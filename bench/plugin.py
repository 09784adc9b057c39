"""Finds a part of the benchmark by its name: a file of its own under a
directory of ``bench/``, so that a later PR adds a metric or a kind of
traffic by adding a file, and edits none.

- ``bench/metrics/<name>.py``: a per-layer metric's ``read(ctx)``;
- ``bench/end_to_end/<name>.py``: an end-to-end metric's ``read(window)``;
- ``bench/sources/<kind>.py``: a mix's query-source generator, ``draw``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def find(directory: str, name: str, attr: str, what: str):
    """``attr`` of ``bench/<directory>/<name>.py``; a ``KeyError`` that
    names ``what`` where there is no such file."""
    path = HERE / directory / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"unknown {what} {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{directory}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, attr)
