"""Reduction of a profiler trace to the numbers the metrics read.

A trace is read into plain planes -> lines -> events (``planes_of``), so
the reduction runs the same on a trace written by ``jax.profiler`` and
on the small recorded excerpt the tests keep (``load_json``).

* device planes are named ``/device:<PLATFORM>:<n>``; their ``XLA Ops``
  line holds every operation that ran (busy time is the union of those
  intervals) and their ``XLA Modules`` line one event per executed
  program;
* the benchmark's host spans are ``jax.profiler.TraceAnnotation``s named
  ``cb:<what>``, on the host plane and on the same clock.

All times are in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Tuple

import numpy as np

SPAN_PREFIX = "cb:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device -> operations
    modules: Dict[str, List[Event]]    # device -> program executions
    spans: List[Event]                 # the benchmark's host spans

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def from_planes(planes: List[dict]) -> Trace:
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in planes:
        device = _is_device(plane["name"])
        for line in plane["lines"]:
            evs = [Event(e["name"], float(e["start_ns"]),
                         float(e["duration_ns"]), e.get("stats", {}))
                   for e in line["events"]]
            if device and line["name"] == OPS_LINE:
                ops.setdefault(plane["name"], []).extend(evs)
            elif device and line["name"] == MODULES_LINE:
                modules.setdefault(plane["name"], []).extend(evs)
            elif not device:
                spans.extend(e for e in evs
                             if e.name.startswith(SPAN_PREFIX))
    for d in ops:
        ops[d].sort(key=lambda e: e.start)
    for d in modules:
        modules[d].sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def _stat_value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def planes_of(xplane_path: str) -> List[dict]:
    """The planes of an ``.xplane.pb`` file as plain dicts."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for e in line.events:
                keep = _is_device(plane.name) or e.name.startswith(
                    SPAN_PREFIX)
                if not keep:
                    continue
                stats = {k: _stat_value(v) for k, v in e.stats} \
                    if _is_device(plane.name) else {}
                evs.append({"name": e.name, "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns, "stats": stats})
            lines.append({"name": line.name, "events": evs})
        out.append({"name": plane.name, "lines": lines})
    return out


def read_dir(trace_dir: str) -> Trace:
    """The trace a profiler wrote under ``trace_dir``."""
    return from_planes(planes_of(find_xplane(trace_dir)))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_json(path: str) -> Trace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return from_planes(json.load(f))


# ---------------------------------------------------------------- intervals


def union(events: List[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                   float]]:
    """Merged intervals of ``events`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def length(intervals) -> float:
    return float(sum(t - s for s, t in intervals))


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < t:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for s, t in intervals:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Busy time in [lo, hi], averaged over the devices."""
    if not trace.ops:
        return 0.0
    return float(np.mean([length(union(trace.ops[d], lo, hi))
                          for d in trace.devices]))


# --------------------------------------------------------------- breakdown


CONTAINERS = (" while(", " conditional(", " call(")


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TYPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def op_label(text: str) -> str:
    """A short label for an operation: a TPU trace names each by its
    whole HLO line (``%fusion.3 = bf16[8,128]{...} fusion(...), ...``);
    keep the name, the (first) result type and the opcode."""
    name, _, rhs = text.partition(" = ")
    if not rhs:
        return text[:100]
    result = _TYPE.search(rhs)
    opcode = _OPCODE.search(" " + rhs)
    return " ".join([name] + [m.group(m.lastindex or 0)
                              for m in (result, opcode) if m])


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10):
    """[[label, seconds]] of the operations that took the most device
    time in [lo, hi], summed by label over the devices and divided by
    their number.  Control flow (a while loop's own event spans its
    body's) is left out."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for e in trace.ops[d]:
            if any(c in e.name for c in CONTAINERS):
                continue
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                label = op_label(e.name)
                tot[label] = tot.get(label, 0.0) + (t - s)
    n = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[label, ns / n / 1e9] for label, ns in best]


def pair_from_end(spans: List[Event], runs: List[Event]):
    """Pair each span with the device's execution of the program it
    launched.  A device runs one process's programs in order and every
    span's program has finished before the trace stops, so the two lists
    align from their ends (executions from before the first span drop
    out)."""
    n = min(len(spans), len(runs))
    return list(zip(spans[len(spans) - n:], runs[len(runs) - n:]))


def device_shift(pairs) -> float:
    """Nanoseconds to add to device times to put them on the host spans'
    clock, from spans that block until their program ends: each
    execution must then start after its span starts and end before it
    ends.  Midway between the two bounds, taken robustly (the 95th and
    5th percentiles)."""
    if not pairs:
        return 0.0
    lo = np.percentile([s.start - r.start for s, r in pairs], 95)
    hi = np.percentile([s.end - r.end for s, r in pairs], 5)
    return float((lo + hi) / 2)


def span_at(trace: Trace, t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    best = None
    for e in trace.spans:
        if e.start <= t < e.end and (best is None or e.dur < best.dur):
            best = e
    return best.name if best else "no span"


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10,
              shift: float = 0.0):
    """[[label, seconds]] of the longest idle gaps of the first device
    in [lo, hi] (device clock), each labelled with the benchmark span
    open at its middle; ``shift`` puts device times on the spans'
    clock (``device_shift``)."""
    if not trace.ops:
        return []
    d = trace.devices[0]
    gs = sorted(gaps(union(trace.ops[d], lo, hi), lo, hi),
                key=lambda g: g[0] - g[1])[:k]
    return [[span_at(trace, (s + t) / 2 + shift), (t - s) / 1e9]
            for s, t in gs]


def spans_named(trace: Trace, name: str) -> List[Event]:
    return [e for e in trace.spans if e.name == name]
