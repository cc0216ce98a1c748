#!/usr/bin/env python3
"""Per-layer readings from the serving engine's own spans, counters and
scopes (run on the chip, by hand).

    python3 chipbench/engine_readings.py --workload danube-chat \
        --seed 7100000001 --seconds 45

One traced window of a serving cell, in one process, as ``run.py
--trace 1`` runs it: the same set-up, the same load, the profiler over
the window's last ``trace_seconds``.  After warm-up it also takes the
compiled text of the chunk and decode tick programs (the executables the
window runs, from JAX's caches), which maps each device op to its
``op_name`` and so to the paged cache's scopes.  It prints one JSON line:

* ``end_to_end``: the cell's end-to-end metrics of this traced window;
* ``per_layer``: the cell's per-layer metrics as ``run.py`` reads them
  from the same trace, and the device's idle share of the window;
* ``engine``: the readings of ``lib/engine_trace.py`` —
  ``host_self_ms`` (from the engine's counters as the profiler
  started), ``queue_wait_p90_ms``, ``chunk_fill`` (the window's
  counters), ``tick_gap_us``, ``paged_kv_share`` and the share of pool
  slices, updates and copies outside the scopes, the clock shift, the
  ticks whose programs the trace does not show, and the device's idle
  gaps of 10 ms and more with the ticks around them.

These readings are not yet per-layer metrics of ``BENCHMARK.json``:
``kinds/serve.py`` would have to hand the engine's counters, records and
the program's spans to the metric readers (PERF.md, Open questions).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.lib import engine_trace as et  # noqa: E402
from chipbench.lib import trace as tl  # noqa: E402
from chipbench.lib import traffic as traffic_lib  # noqa: E402
from chipbench.lib.harness import (BENCH, OUT, ROOT, CompileCount,  # noqa: E402
                                   Outcome, find_cell, load_module, log,
                                   read_json, require_devices)


class SnapshotAtStart:
    """The cell's tracer, with a copy of the engine's counters taken
    just before it starts the profiler (the untraced ticks' host time,
    as ``tick_host_ms`` takes it)."""

    def __init__(self, tracer):
        self.tracer, self.stats = tracer, None

    def __call__(self, now: float) -> None:
        t = self.tracer
        if t.span is None and now >= t.start_at:
            self.stats = copy.deepcopy(getattr(t.eng, "stats", None))
        t(now)

    def stop(self) -> None:
        self.tracer.stop()


def tick_texts(eng) -> Dict[str, str]:
    """The compiled text of the chunk and decode tick programs, lowered
    with arguments of the shapes and kinds the engine dispatches."""
    import jax.numpy as jnp
    import numpy as np
    B = eng.slots
    out = {}
    for kind, width in (("chunk", eng.sched.chunk), ("decode", 1)):
        out[kind] = eng._dispatch_fn.lower(
            eng.params, jnp.asarray(np.zeros((B, width), np.int32)),
            jnp.asarray(np.zeros((B,), bool)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(eng.temps.copy()), eng.key, eng.next_buf,
            eng.cache, eng.btab).compile().as_text()
    return out


def pool_dims(conf: dict):
    """(blocks, block size, KV heads, head width) of the paged pool."""
    s, m = conf["serving"], conf["model"]
    blocks = s["slots"] * -(-s["max_len"] // s["block_size"])
    return blocks, s["block_size"], m["num_kv_heads"], m["head_dim"]


def ranges(ids):
    """[[first, last], ...] of the runs of consecutive ids."""
    out = []
    for i in ids:
        if out and i == out[-1][1] + 1:
            out[-1][1] = i
        else:
            out.append([i, i])
    return out


def trace_readings(trace: tl.Trace, prog: et.ProgramTrace,
                   texts: Dict[str, str], dims, lo: float, hi: float
                   ) -> dict:
    ticks = et.ticks_of(prog)
    runs = et.runs_of(trace)
    pairs = et.bracket_pairs(ticks, runs)
    shift = et.shift_by_ends(pairs)
    et.match(ticks, runs, shift)
    gaps, missed = et.tick_gaps(ticks, prog.named("serve.wait"), shift)
    shares = et.op_shares(trace, ticks, {k: et.scope_map(t)
                                         for k, t in texts.items()}, dims)
    prog_ns = shares["program"]
    return {
        "tick_gap_us": sum(gaps) / len(gaps) / 1e3 if gaps else None,
        "tick_gaps": len(gaps),
        "paged_kv_share": 100.0 * shares["scoped"] / prog_ns
        if prog_ns and shares["scoped"] else None,
        "pool_unscoped_share": 100.0 * shares["pool"] / prog_ns
        if prog_ns else None,
        "ops_mapped_share": 100.0 * shares["mapped"] / shares["ops"]
        if shares["ops"] else None,
        "shift_ms": shift / 1e6,
        "shift_midpoint_ms": tl.device_shift(pairs) / 1e6,
        "ticks_traced": sum(t.run is not None for t in ticks),
        "untraced_ticks": ranges([t.tick for t in ticks if t.run is None]),
        "gap_pairs_with_untraced_tick": missed,
        "long_gaps": et.long_gaps(trace, prog, ticks, shift, lo, hi),
        "compiles": len(prog.compiles)}


def readings(cell, args, devices, peaks) -> dict:
    from chipbench.run import per_layer
    import jax
    serve = load_module(BENCH / "kinds" / "serve.py")
    conf, mix = cell.config, cell.traffic
    weights = jax.block_until_ready(serve.make_weights(conf, args.seed))
    eng = serve.build(conf, weights, devices)
    serve.warm_up(eng)
    texts = tick_texts(eng)
    reqs = traffic_lib.generate(mix, args.seed, args.seconds,
                                conf["model"]["vocab_size"])
    tracer = SnapshotAtStart(serve.Tracer(
        eng, args.seconds, conf["trace_seconds"],
        OUT / "trace" / f"{cell.name}.engine"))
    log(f"set-up {time.perf_counter() - T0:.2f} s")
    with CompileCount() as window_compiles:
        due = serve.serve_window(eng, reqs, args.seconds, tracer)
    e2e = serve.window_metrics(eng, due, args.seconds)
    stats = getattr(eng, "stats", None)
    records = dict(getattr(eng, "_metrics", {}))
    srun = serve.ServeRun(conf["model"], peaks, list(eng.ticks),
                          serve.inflight_intervals(eng, args.seconds))
    del eng
    gc.collect()
    engine = {
        "host_self_ms": et.host_self_ms(tracer.stats or stats),
        "queue_wait_p90_ms": et.queue_wait_p90_ms(
            records, [(r.rid, r.arrival_s) for r in due], args.seconds),
        "chunk_fill": et.chunk_fill(stats),
        "stats": None if stats is None else dict(
            dataclasses.asdict(stats), host_s=stats.host_s),
        "compiles_in_window": window_compiles.count}
    out = {"workload": cell.name, "seed": args.seed, "end_to_end": e2e,
           "engine": engine}
    if tracer.tracer.span is None:
        return out
    prog = et.from_planes(et.planes_of(tl.find_xplane(
        tracer.tracer.out_dir)))
    outcome = Outcome(e2e, [], len(due), 0, None, srun)
    serve.read_trace(srun, tracer.tracer, outcome)   # removes the trace
    out["per_layer"] = {k: v["value"] for k, v in
                        per_layer(cell, outcome).items()}
    out["per_layer"]["idle_share"] = 1.0 - outcome.busy_s / outcome.window_s
    out["breakdown_idle_gaps"] = outcome.breakdown["idle_gaps"][:3]
    engine.update(trace_readings(srun.trace, prog, texts, pool_dims(conf),
                                 srun.lo_ns, srun.hi_ns))
    return out


def main(argv=None) -> None:
    from chipbench.peaks import peaks_for
    from chipbench.run import enable_cache
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = find_cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = require_devices(cell.chips)
    enable_cache()
    out = readings(cell, args, devices, peaks_for(devices[0].device_kind))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
