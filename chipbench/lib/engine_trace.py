"""The serving engine's own spans, counters and scopes, reduced to
per-layer numbers.

The engine (``repro.serve.engine``) opens host spans named
``serve.<what>`` whose stats carry the tick's id (``tick``, its dispatch
counter) and, on ``serve.dispatch``, the tick's ``kind``; it counts
ticks, prompt tokens, row capacity and host seconds in ``Engine.stats``; it records when each request took a slot
(``admit_s``); and ``models/cache.py`` runs the paged gather and scatter
under ``jax.named_scope``s of those names.  This module reads them:

* ``planes_of`` / ``from_planes``: the program's spans (stats included)
  and JAX's compile events (``backend_compile...``) from a profiler
  trace, in the planes layout of ``lib/trace.py``, which reads the
  device planes and leaves these out;
* ``match``: each dispatched tick with the device's execution of its
  program, on the host's clock through ``shift_by_ends``;
* the readings: ``host_self_ms``, ``queue_wait_p90_ms``, ``chunk_fill``,
  ``tick_gaps`` (``tick_gap_us``), ``op_shares`` (``paged_kv_share``)
  and ``long_gaps``, the device's idle gaps with the ticks around them.

All trace times are in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.lib import trace as tl
from chipbench.lib.trace import Event

PREFIX = "serve."
COMPILE_PREFIX = "backend_compile"
DISPATCH_PROGRAM = "_dispatch_body"       # the engine's jitted tick
SCOPES = ("paged_gather", "paged_scatter")
END_TOLERANCE_NS = 0.5e6
HOST_SPANS = ("serve.admit", "serve.plan", "serve.prepare",
              "serve.dispatch", "serve.record")


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Event]          # the program's ``serve.*`` spans, by start
    compiles: List[Event]       # JAX's compilations, by start

    def named(self, name: str) -> List[Event]:
        return [e for e in self.spans if e.name == name]

    def span_at(self, t: float) -> Optional[Event]:
        """The innermost program span open at ``t`` (host clock)."""
        best = None
        for e in self.spans:
            if e.start <= t < e.end and (best is None or e.dur < best.dur):
                best = e
        return best


def planes_of(xplane_path: str) -> List[dict]:
    """The host planes' program spans and compile events of an
    ``.xplane.pb`` file as plain dicts, stats included."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        if tl._is_device(plane.name):
            continue
        lines = []
        for line in plane.lines:
            evs = [{"name": e.name, "start_ns": e.start_ns,
                    "duration_ns": e.duration_ns,
                    "stats": {k: tl._stat_value(v) for k, v in e.stats}}
                   for e in line.events
                   if e.name.startswith((PREFIX, COMPILE_PREFIX))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return out


def from_planes(planes: List[dict]) -> ProgramTrace:
    spans: List[Event] = []
    compiles: List[Event] = []
    for plane in planes:
        if tl._is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            for e in line["events"]:
                ev = Event(e["name"], float(e["start_ns"]),
                           float(e["duration_ns"]), e.get("stats", {}))
                if ev.name.startswith(PREFIX):
                    spans.append(ev)
                elif ev.name.startswith(COMPILE_PREFIX):
                    compiles.append(ev)
    spans.sort(key=lambda e: e.start)
    compiles.sort(key=lambda e: e.start)
    return ProgramTrace(spans, compiles)


def load_excerpt(path: str):
    """A recorded excerpt: (device and benchmark ``Trace``, the
    program's spans, {kind: {instruction: op_name}})."""
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return (tl.from_planes(doc["planes"]), from_planes(doc["planes"]),
            doc["scopes"])


# ------------------------------------------------------------------ ticks


@dataclasses.dataclass
class TickRun:
    tick: int
    kind: str
    dispatch: Event
    readback: Optional[Event] = None
    run: Optional[Event] = None       # the device's execution (device clock)


def ticks_of(prog: ProgramTrace) -> List[TickRun]:
    """Every tick dispatched in the trace, by id, with its read-back."""
    by: Dict[int, TickRun] = {}
    for e in prog.named("serve.dispatch"):
        t = int(e.stats["tick"])
        by[t] = TickRun(t, str(e.stats.get("kind", "")), e)
    for e in prog.named("serve.readback"):
        t = by.get(int(e.stats["tick"]))
        if t is not None:
            t.readback = e
    return [by[t] for t in sorted(by)]


def runs_of(trace: tl.Trace) -> List[Event]:
    """The first device's executions of the tick program, in order."""
    if not trace.ops:
        return []
    return [e for e in trace.modules.get(trace.devices[0], [])
            if DISPATCH_PROGRAM in e.name]


def bracket_pairs(ticks: Sequence[TickRun], runs: Sequence[Event]):
    """(span from a tick's dispatch start to its read-back end, its
    execution) for the ticks read back, pairing ticks and executions
    from the end (``lib/trace.pair_from_end``)."""
    return [(Event("tick", t.dispatch.start,
                   t.readback.end - t.dispatch.start, {}), r)
            for t, r in tl.pair_from_end(list(ticks), list(runs))
            if t.readback is not None]


def shift_by_ends(pairs) -> float:
    """Nanoseconds to add to device times to put them on the host's
    clock.  A tick's read-back ends just after its program does, but in
    the pipelined loop its dispatch starts while the device still runs
    the tick before, often a whole tick earlier, so only the end bounds
    the shift: the 5th percentile of (read-back end - execution end),
    which lies above the true shift by the least time the host takes to
    see a program end.  (``lib/trace.device_shift``, the midpoint of
    both bounds, is off by half a tick here.)"""
    if not pairs:
        return 0.0
    return float(np.percentile([s.end - r.end for s, r in pairs], 5))


def match(ticks: Sequence[TickRun], runs: Sequence[Event], shift: float,
          tol: float = END_TOLERANCE_NS) -> None:
    """Give each tick the device's execution of its program: the first
    execution not yet given that starts, on the host's clock, after the
    tick's dispatch began and, where the tick was read back, ends before
    the read-back did.  Executions that start before a tick's dispatch
    belong to ticks dispatched before the trace began; a tick whose next
    execution ends after its read-back has none in the trace."""
    j = 0
    for t in ticks:
        t.run = None
        while j < len(runs) and runs[j].start + shift < t.dispatch.start:
            j += 1
        if j == len(runs):
            continue
        r = runs[j]
        if t.readback is not None and r.end + shift > t.readback.end + tol:
            continue
        t.run = r
        j += 1


def tick_gaps(ticks: Sequence[TickRun], waits: Sequence[Event],
              shift: float) -> Tuple[List[float], int]:
    """(device ns from the end of one tick's program to the start of the
    next's, over consecutive ticks whose programs both ran in the trace
    and whose gap overlaps no ``serve.wait``; executions with an
    untraced tick between them, which are left out)."""
    ran = [t for t in ticks if t.run is not None]
    gaps, missed = [], 0
    for a, b in zip(ran, ran[1:]):
        if b.tick != a.tick + 1:
            missed += 1
            continue
        s, e = a.run.end + shift, b.run.start + shift
        if any(w.start < e and w.end > s for w in waits):
            continue
        gaps.append(b.run.start - a.run.end)
    return gaps, missed


# ----------------------------------------------------------------- scopes


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                    re.M)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled module's text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def _instruction(op_text: str) -> str:
    return op_text.partition(" = ")[0].strip().lstrip("%")


def op_shares(trace: tl.Trace, ticks: Sequence[TickRun],
              scopes: Dict[str, Dict[str, str]],
              pool_dims: Optional[Tuple[int, ...]] = None
              ) -> Dict[str, float]:
    """Device ns of the traced ticks' programs (``program``), of their
    ops under the paged cache's scopes (``scoped``), of ops outside them
    whose result has the paged pool's shape (``pool``: the layer scan's
    slices, updates and copies of the pool), of ops found in the
    compiled text (``mapped``) and of all ops (``ops``).  An op belongs
    to the execution it starts in; control flow (a while loop's own
    event spans its body's) is left out."""
    out = dict(program=0.0, ops=0.0, mapped=0.0, scoped=0.0, pool=0.0)
    if not trace.ops:
        return out
    ops = trace.ops[trace.devices[0]]
    starts = [e.start for e in ops]
    pool_tag = None if pool_dims is None else \
        ",".join(str(d) for d in pool_dims) + "]"
    for t in ticks:
        if t.run is None:
            continue
        names = scopes.get(t.kind, {})
        out["program"] += t.run.dur
        lo = bisect.bisect_left(starts, t.run.start)
        hi = bisect.bisect_left(starts, t.run.end)
        for e in ops[lo:hi]:
            if any(c in e.name for c in tl.CONTAINERS):
                continue
            out["ops"] += e.dur
            op_name = names.get(_instruction(e.name))
            if op_name is None:
                continue
            out["mapped"] += e.dur
            if any(f"/{s}/" in op_name for s in SCOPES):
                out["scoped"] += e.dur
            elif pool_tag and pool_tag in tl.op_label(e.name):
                out["pool"] += e.dur
    return out


# ---------------------------------------------------------- idle gaps


def long_gaps(trace: tl.Trace, prog: ProgramTrace,
              ticks: Sequence[TickRun], shift: float, lo: float, hi: float,
              min_ns: float = 10e6) -> List[dict]:
    """The first device's idle gaps of ``min_ns`` and more in [lo, hi]
    (device clock, as ``lib/trace.idle_gaps`` takes them), each with the
    ticks around it and what the host was doing.  ``case`` is

    * ``compile`` — a JAX compilation overlaps the gap;
    * ``missed execution`` — a tick whose program the trace does not
      show was in flight (dispatched, not yet read back) across the gap:
      the device ran it untraced;
    * ``host`` — the program span open at the gap's middle is one of the
      host's own (admit, plan, prepare, dispatch, record): a host stall;
    * ``arrival wait`` — the host was asleep with nothing to plan;
    * ``read-back`` — the host was waiting for a tick's tokens;
    * ``no span`` — no program span was open."""
    if not trace.ops:
        return []
    busy = tl.union(trace.ops[trace.devices[0]], lo, hi)
    out = []
    for s, e in tl.gaps(busy, lo, hi):
        if e - s < min_ns:
            continue
        hs, he = s + shift, e + shift
        missing = [t.tick for t in ticks if t.run is None
                   and t.dispatch.start < he
                   and (t.readback is None or t.readback.end > hs)]
        before = [t.tick for t in ticks if t.run is not None
                  and t.run.end <= s]
        after = [t.tick for t in ticks if t.run is not None
                 and t.run.start >= e]
        span = prog.span_at((hs + he) / 2)
        compile_ns = tl.length(tl.intersect(
            tl.union(prog.compiles, hs, he), [(hs, he)]))
        if compile_ns > 0:
            case = "compile"
        elif missing:
            case = "missed execution"
        elif span is None:
            case = "no span"
        elif span.name in HOST_SPANS:
            case = "host"
        elif span.name == "serve.wait":
            case = "arrival wait"
        else:
            case = "read-back"
        out.append({
            "at_s": (s - lo) / 1e9, "dur_s": (e - s) / 1e9, "case": case,
            "tick_before": before[-1] if before else None,
            "tick_after": after[0] if after else None,
            "ticks_missing": missing,
            "span_at_middle": None if span is None else
            [span.name, span.stats.get("tick")],
            "compile_s": compile_ns / 1e9})
    return out


# --------------------------------------------------------------- counters


def host_self_ms(stats) -> Optional[float]:
    """Host ms per dispatched tick outside read-back and arrival sleep
    (``EngineStats``), or None for a program without the counters."""
    if stats is None or not stats.dispatched:
        return None
    return 1e3 * stats.host_s / stats.dispatched


def chunk_fill(stats) -> Optional[float]:
    """Prompt tokens carried by the chunk ticks over their row capacity
    (slots x chunk width), in percent."""
    if stats is None or not stats.chunk_rows:
        return None
    return 100.0 * stats.chunk_tokens / stats.chunk_rows


def queue_wait_p90_ms(records: Dict[int, dict],
                      due: Sequence[Tuple[int, float]],
                      seconds: float) -> Optional[float]:
    """p90 over the requests due in the window of due time -> slot; a
    request still queued at the close counts its wait so far.  None for
    a program whose records carry no ``admit_s``."""
    if not due or not any("admit_s" in m for m in records.values()):
        return None
    waits = []
    for rid, arrival in due:
        admit = records.get(rid, {}).get("admit_s")
        waits.append((admit if admit is not None and admit <= seconds
                      else seconds) - arrival)
    return float(np.percentile(waits, 90)) * 1e3
