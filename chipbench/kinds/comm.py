"""Communicator cells: a fixed list of blocking exchanges, repeated.

Set-up builds one jitted program per exchange (transport x operation
x size) over the configuration's mesh, puts each size's payload on the
chips and runs every exchange once, which compiles (or loads) them all.
The window then runs whole passes over the list, each exchange awaited
with ``block_until_ready`` as the paper times a blocking message, until
a pass ends at or after ``--seconds``.

Payloads are float32 multiples of 2**-8 below 2**7, whose 16 significant
bits survive any order of summation over four ranks exactly, and which
bfloat16 cannot hold.  ``correct``: for every exchange, the output of
one pass drawn from the seed is compared, shard by shard on its own
device, with the NumPy reference; the number compared is the count of
elements that differ (limit 0) plus shards found on the wrong device.
"""
from __future__ import annotations

import dataclasses
import shutil
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.lib import trace as trace_lib
from chipbench.lib.harness import (BENCH, OUT, Check, CompileCount, Outcome,
                                   Phases, load_module, log, peak_bytes)

from repro.comms import Communicator
from repro.launch.mesh import make_mesh


@dataclasses.dataclass
class Exchange:
    op: str
    transport: str
    size: int                       # payload bytes per rank
    fn: object = None
    x: object = None
    kept: object = None             # one pass's output, drawn from the seed
    seen: int = 0
    wall_s: float = 0.0             # untraced wall time, summed
    calls: int = 0

    @property
    def span(self) -> str:
        return f"cb:{self.op}.{self.transport}.{self.size}"


def exchange_list(conf: dict, mix: dict) -> List[Exchange]:
    out = []
    for t in conf["transports"]:
        for op in mix["collectives"]["ops"]:
            for size in mix["collectives"]["bytes_per_rank"]:
                out.append(Exchange(op, t, int(size)))
        for op in mix["p2p"]["ops"]:
            for size in mix["p2p"]["bytes"]:
                out.append(Exchange(op, t, int(size)))
    return out


def payload(n: int, size: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, size])
    m = max(size // 4, 1)
    return (rng.integers(-(1 << 15), 1 << 15, size=(n, m))
            * 2.0 ** -8).astype(np.float32)


def program(comm: Communicator, op: str, conf: dict):
    p2p, root, n = conf["p2p"], conf["root"], comm.size

    def body(x):
        v = x.reshape(-1)
        if op == "send":
            out = comm.send(v, p2p["dst"], src=p2p["src"])
        elif op == "alltoall":
            m = v.shape[0]
            out = comm.alltoall(jnp.pad(v, (0, -(-m // n) * n - m)))
        elif op in ("bcast", "agg", "scatter"):
            out = getattr(comm, op)(v, root=root)
        else:
            out = getattr(comm, op)(v)
        return out.reshape(1, -1)

    return jax.jit(comm.wrap(body, in_specs=(P(comm.axes),),
                             out_specs=P(comm.axes)))


def mismatches(ex: Exchange, want: np.ndarray, mesh) -> int:
    """Elements that differ from ``want``, shard by shard, plus one for
    every shard that is not on its own rank's device."""
    bad = 0
    devices = list(mesh.devices.flat)
    for s in ex.kept.addressable_shards:
        rank = s.index[0].start or 0
        if s.device != devices[rank]:
            bad += 1
        got = np.asarray(s.data).reshape(-1)
        w = np.ascontiguousarray(want[rank], np.float32).reshape(-1)
        if got.shape != w.shape:
            bad += max(got.size, w.size)
            continue
        bad += int(np.count_nonzero(got.view(np.uint32) != w.view(np.uint32)))
    return bad


@dataclasses.dataclass
class CommRun:
    """What the per-layer readers read."""
    peaks: dict
    ranks: int
    exchanges: List[Exchange]
    passes: int
    trace: trace_lib.Trace = None
    lo_ns: float = 0.0                  # the traced window, device clock
    hi_ns: float = 0.0
    # (exchange, its program's execution on each device) in the trace
    runs: List[Tuple[Exchange, List[trace_lib.Event]]] = \
        dataclasses.field(default_factory=list)


def traced_runs(tr: trace_lib.Trace, exchanges: List[Exchange]):
    """Each traced exchange with its program's execution on every
    device, and the shift that puts device times on the host's clock
    (a blocking exchange's program runs inside its span)."""
    by_span = {x.span: x for x in exchanges}
    spans = [s for s in tr.spans if s.name in by_span]
    per_device = [trace_lib.pair_from_end(spans, tr.modules.get(d, []))
                  for d in tr.devices]
    n = min((len(p) for p in per_device), default=0)
    runs = []
    for i in range(n):
        row = [p[len(p) - n + i] for p in per_device]
        runs.append((by_span[row[0][0].name], [r for _, r in row]))
    shift = trace_lib.device_shift(per_device[0]) if per_device else 0.0
    return runs, shift


def run(cell, args, devices, peaks, phases: Phases) -> Outcome:
    conf, mix = cell.config, cell.traffic
    ref = load_module(BENCH / "references" / f"{conf['reference']}.py")
    mesh = make_mesh(conf["mesh"]["shape"], conf["mesh"]["axes"],
                     devices=devices)
    n = mesh.size
    exchanges = exchange_list(conf, mix)
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    inputs: Dict[int, np.ndarray] = {}
    with CompileCount() as setup_compiles:
        comms = {t: Communicator(mesh, t) for t in conf["transports"]}
        fns = {}
        for ex in exchanges:
            if ex.size not in inputs:
                inputs[ex.size] = payload(n, ex.size, args.seed)
            key = (ex.transport, ex.op)
            if key not in fns:
                fns[key] = program(comms[ex.transport], ex.op, conf)
            ex.fn = fns[key]
            ex.x = jax.device_put(inputs[ex.size], sharding)
        phases.mark("payloads")
        for ex in exchanges:
            ex.fn(ex.x).block_until_ready()
        phases.mark("first_pass")
    setup_s = phases.total
    log(f"set-up {setup_s:.2f} s ({setup_compiles.count} programs compiled "
        f"or loaded); {len(exchanges)} exchanges a pass")

    rng = np.random.default_rng([args.seed, 3])
    trace_at = args.seconds - conf["trace_seconds"] if args.trace else None
    out_dir = str(OUT / "trace" / cell.name)
    span = None
    passes = 0
    with CompileCount() as window_compiles:
        start = time.perf_counter()
        while True:
            now = time.perf_counter() - start
            if trace_at is not None and span is None and now >= trace_at:
                shutil.rmtree(out_dir, ignore_errors=True)
                jax.profiler.start_trace(out_dir)
                span = jax.profiler.TraceAnnotation("cb:traced")
                span.__enter__()
            for ex in exchanges:
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation(ex.span):
                    out = ex.fn(ex.x)
                    out.block_until_ready()
                if span is None:
                    ex.wall_s += time.perf_counter() - t
                    ex.calls += 1
                ex.seen += 1
                if rng.random() * ex.seen < 1.0:     # reservoir of one
                    ex.kept = out
            passes += 1
            if time.perf_counter() - start >= args.seconds:
                break
        window = time.perf_counter() - start
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    log(f"window: {passes} passes in {window:.3f} s, "
        f"{window_compiles.count} compiles inside the window")
    mem = peak_bytes(devices)
    bad = 0
    for ex in exchanges:
        want = ref.answer(ex.op, inputs[ex.size], root=conf["root"],
                          **conf["p2p"])
        bad += mismatches(ex, want, mesh)
    crun = CommRun(peaks, n, exchanges, passes)
    outcome = Outcome({"comm_pass_ms": window / passes * 1e3,
                       "setup_s": setup_s},
                      [Check("mismatches", bad, conf["check"]["mismatches"])],
                      passes * len(exchanges), 0, mem, crun,
                      notes={"setup_phases_s": phases.seconds,
                             "compiles_in_window": window_compiles.count})
    if span is not None:
        tr = trace_lib.read_dir(out_dir)
        s = trace_lib.spans_named(tr, "cb:traced")[-1]
        crun.runs, shift = traced_runs(tr, exchanges)
        lo, hi = s.start - shift, s.end - shift
        crun.trace, crun.lo_ns, crun.hi_ns = tr, lo, hi
        outcome.window_s = s.dur / 1e9
        outcome.busy_s = trace_lib.busy_ns(tr, lo, hi) / 1e9
        outcome.breakdown = {
            "device_ops": trace_lib.top_ops(tr, lo, hi),
            "idle_gaps": trace_lib.idle_gaps(tr, lo, hi, shift=shift)}
        shutil.rmtree(out_dir, ignore_errors=True)
    return outcome
