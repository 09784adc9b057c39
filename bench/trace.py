"""Reduction of a profiler trace to the benchmark's device numbers.

The profiler writes a Chrome-format ``*.trace.json.gz`` beside its
``.xplane.pb``. Each chip is a process named ``/device:TPU:<k>``; its
``XLA Ops`` thread holds one event per executed HLO op, in microseconds on
the host's clock, and each op's ``tf_op`` argument carries its JAX name
stack, e.g. ``jit(_fora_fused_impl)/jit(forward_push)/while/body/gather``.
Control-flow ops (``while``) enclose the ops of their body, so every time
below is the length of a union of intervals, never a sum. The harness's
own spans (``bench.*``) are on the host's threads.
"""

from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_THREAD = "XLA Ops"
SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")   # ops that enclose others
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@dataclass(frozen=True)
class Op:
    start: float   # microseconds
    end: float
    name: str      # the HLO op, e.g. "fusion.95"
    scope: str     # its JAX name stack ("tf_op"), "" if the trace has none


@dataclass
class Trace:
    ops: dict[int, list[Op]] = field(default_factory=dict)   # per device
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        """The first span called ``name``: the traced window."""
        for span, lo, hi in self.spans:
            if span == name:
                return lo, hi
        raise ValueError(f"the trace has no {name!r} span")


def from_events(events: list[dict]) -> Trace:
    """Build a :class:`Trace` from Chrome trace events."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    trace = Trace()
    for e in events:
        if e.get("ph") != "X":
            continue
        start = float(e["ts"])
        end = start + float(e.get("dur", 0.0))
        dev = DEVICE.match(procs.get(e["pid"], ""))
        if dev and threads.get((e["pid"], e["tid"])) == OPS_THREAD:
            op = Op(start, end, e["name"], e.get("args", {}).get("tf_op", ""))
            trace.ops.setdefault(int(dev.group(2)), []).append(op)
        elif not dev and e["name"].startswith(SPAN_PREFIX):
            trace.spans.append((e["name"], start, end))
    trace.spans.sort(key=lambda s: s[1])
    return trace


def load(log_dir: Path) -> Trace:
    """Read the trace the profiler wrote under ``log_dir``."""
    files = sorted(Path(log_dir).rglob("*.trace.json.gz"))
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {log_dir}")
    with gzip.open(files[0], "rt") as f:
        data = json.load(f)
    return from_events(data["traceEvents"] if isinstance(data, dict)
                       else data)


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint ones, in order."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def length(merged, lo: float = -float("inf"),
           hi: float = float("inf")) -> float:
    """Total length of disjoint intervals inside [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def op_time_s(trace: Trace, match, lo: float, hi: float) -> float | None:
    """Seconds in [lo, hi] during which an op with ``match(op)`` ran, the
    mean over devices. None when no op matches."""
    per_device = []
    for ops in trace.ops.values():
        hits = [(o.start, o.end) for o in ops if match(o)]
        per_device.append(length(union(hits), lo, hi) if hits else None)
    if all(t is None for t in per_device):
        return None
    return sum(t or 0.0 for t in per_device) / len(per_device) / 1e6


def in_scope(part: str):
    """Matcher of ops whose name stack holds ``part``."""
    return lambda op: part in op.scope


def is_collective(op: Op) -> bool:
    """An exchange between chips: by its HLO op or by its name stack."""
    return op.name.startswith(COLLECTIVES) or bool(
        re.search(r"/(psum|all_gather|ppermute|all_to_all)\b", op.scope))


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which any op ran, the mean over devices."""
    t = op_time_s(trace, lambda op: True, lo, hi)
    return 0.0 if t is None else t


def host_s(trace: Trace, span: str, lo: float, hi: float) -> float:
    """Seconds of the ``span`` spans in [lo, hi] during which no op ran
    (busy time taken as the mean over devices)."""
    busy = [union((o.start, o.end) for o in ops)
            for ops in trace.ops.values()] or [[]]
    total = 0.0
    for name, a, b in trace.spans:
        if name != span:
            continue
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        idle = [(b - a) - length(m, a, b) for m in busy]
        total += sum(idle) / len(idle)
    return total / 1e6


def _label(op: Op) -> str:
    base = re.sub(r"\.\d+$", "", op.name)
    scope = re.sub(r"^jit\([^)]*\)/", "", op.scope.rstrip(":"))
    return f"{scope} [{base}]" if scope else base


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The ops that took most device time, and the idle time by the host
    span it fell in, each as [[name, seconds], ...] (mean over devices)."""
    ndev = max(1, len(trace.ops))
    ops: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    spans = [s for s in trace.spans if s[0] != "bench.window"]
    for dev_ops in trace.ops.values():
        for o in dev_ops:
            inside = length([(o.start, o.end)], lo, hi)
            if inside and not o.name.startswith(CONTAINERS):
                ops[_label(o)] += inside / 1e6
        edges = [lo] + [x for iv in union((o.start, o.end) for o in dev_ops)
                        for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, lo), min(b, hi)
            cuts = sorted({a, b} | {x for _, s, e in spans for x in (s, e)
                                    if a < x < b})
            for c, d in zip(cuts, cuts[1:]):
                mid = (c + d) / 2
                host = [n for n, s, e in spans if s <= mid <= e]
                gaps[host[-1] if host else "between spans"] += (d - c) / 1e6
    return {key: [[k, v / ndev] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:top]]
        for key, table in (("device_ops", ops), ("idle_gaps", gaps))}
