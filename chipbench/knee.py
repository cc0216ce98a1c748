#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains (run once, on
the chip; its result is written into the cell's traffic file by hand).

    python3 chipbench/knee.py --workload danube-chat --seed 7 \
        --seconds 40 --rates 0.25,0.5,1,2

One process: for each rate it builds the cell's engine afresh, offers
the mix at that rate for ``--seconds`` and prints one JSON line with the
requests waiting for a slot and in flight at the middle and at the end
of the window,
tokens/s and the TTFT tail.  The knee is the highest rate at which the
waiting count does not grow from the middle to the end.
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

from chipbench.lib import traffic as traffic_lib  # noqa: E402
from chipbench.lib.harness import (BENCH, ROOT, find_cell, load_module,  # noqa: E402
                                   read_json, require_devices)
from chipbench.run import enable_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = find_cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = require_devices(cell.chips)
    enable_cache()
    serve = load_module(BENCH / "kinds" / f"{cell.config['kind']}.py")
    conf = cell.config
    weights = serve.make_weights(conf, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_per_s=rate)
        eng = serve.build(conf, weights, devices)
        serve.warm_up(eng)
        reqs = traffic_lib.generate(mix, args.seed, args.seconds,
                                    conf["model"]["vocab_size"])
        waiting = {}

        def probe(now, marks=(args.seconds / 2, args.seconds - 0.5)):
            for m in marks:
                if m not in waiting and now >= m:
                    due = sum(r.arrival_s <= now for r in reqs)
                    waiting[m] = (due - len(eng.admitted),
                                  len(eng.admitted) - len(eng.finished))

        t = time.perf_counter()
        due = serve.serve_window(eng, reqs, args.seconds, probe)
        e2e = serve.window_metrics(eng, due, args.seconds)
        print(json.dumps({"rate_per_s": rate, "due": len(due),
                          "finished": len(eng.finished),
                          "waiting_inflight_mid_end": list(waiting.values()),
                          "wall_s": time.perf_counter() - t, **e2e}),
              flush=True)
        del eng
        gc.collect()


if __name__ == "__main__":
    main()
