#!/usr/bin/env python3
"""Readings behind a cell's correctness limit (run on the chip, by hand).

    python3 chipbench/control.py --workload danube-chat --seconds 30 \
        --seeds 11,12,13

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the number its runs compare
(the program's reading, from which the limit's lower end comes) and
the same number for the control on the same sample (its upper end),
each put through the cell's own ``Check`` with its limit, so the line
shows whether it passes.

* serving cells: the mean gap below the float32 reference's best of
  the served tokens, and of the tokens that the reference run at float8
  e4m3 puts first (the precision below the served bfloat16), with the
  widest gap and the count of tokens off the reference's best;
* Communicator cells: the answers computed in bfloat16, put on the
  mesh as the program's output would be and counted by the run's own
  ``mismatches`` against the float32 answers.

One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from chipbench.lib import traffic as traffic_lib  # noqa: E402
from chipbench.lib.harness import (BENCH, ROOT, Check, find_cell,  # noqa: E402
                                   load_module, read_json, require_devices)
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.run import enable_cache  # noqa: E402


def serve_readings(cell, seed: int, seconds: float, devices) -> dict:
    serve = load_module(BENCH / "kinds" / "serve.py")
    conf, mix = cell.config, cell.traffic
    limit = conf["check"]["max_mean_logit_gap"]
    weights = serve.make_weights(conf, seed)
    eng = serve.build(conf, weights, devices)
    serve.warm_up(eng)
    reqs = traffic_lib.generate(mix, seed, seconds,
                                conf["model"]["vocab_size"])
    due = serve.serve_window(eng, reqs, seconds)
    sample = serve.served_sample(eng, reqs, seed, conf["check"]["rows"])
    finished = len(eng.finished)
    del eng
    gc.collect()
    served, control = serve.reference_gaps(conf, weights, sample,
                                           mix["output"]["max"], quant=True)
    out = {"due": len(due), "finished": finished}
    for name, gaps in (("program", served), ("control", control)):
        stats = serve.gap_stats(gaps)
        check = Check("mean_logit_gap", stats["mean"], limit)
        out[name] = dict(stats, correct=check.ok)
    return out


def comm_readings(cell, seed: int, devices) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    comm = load_module(BENCH / "kinds" / "comm.py")
    ref = load_module(BENCH / "references" / f"{cell.config['reference']}.py")
    conf = cell.config
    mesh = make_mesh(conf["mesh"]["shape"], conf["mesh"]["axes"],
                     devices=devices)
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    bad = 0
    for ex in comm.exchange_list(conf, cell.traffic):
        x = comm.payload(mesh.size, ex.size, seed)
        kw = dict(root=conf["root"], **conf["p2p"])
        want = ref.answer(ex.op, x, **kw)
        low = ref.answer(ex.op, x, dtype=_bf16(), **kw).astype(np.float32)
        ex.kept = jax.device_put(low, sharding)
        bad += comm.mismatches(ex, want, mesh)
    check = Check("mismatches", bad, conf["check"]["mismatches"])
    return {"control": bad, "correct": check.ok}


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = find_cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = require_devices(cell.chips)
    peaks_for(devices[0].device_kind)
    enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        if cell.config["kind"] == "serve":
            out = serve_readings(cell, seed, args.seconds, devices)
        else:
            out = comm_readings(cell, seed, devices)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "wall_s": time.perf_counter() - t, **out}),
              flush=True)


if __name__ == "__main__":
    main()
