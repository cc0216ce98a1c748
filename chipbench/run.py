#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``chipbench/configs/<name>.json``, whose
``kind`` picks ``chipbench/kinds/<kind>.py`` and whose ``reference``
picks ``chipbench/references/<reference>.py``) and a traffic mix
(``chipbench/traffic/<name>.json``).  With ``--trace 0`` the last line
of standard output carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by
``chipbench/metrics/<metric>.py``, and the device's busy time.  Without
an accelerator, or with fewer chips than the cell needs, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.lib.harness import (BENCH, ROOT, Phases, find_cell,  # noqa: E402
                                   load_module, log, read_json,
                                   require_devices)
from chipbench.peaks import peaks_for  # noqa: E402


def enable_cache() -> None:
    """The program's compile cache (``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), keeping every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def per_layer(cell, outcome) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(outcome.run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, outcome, devices, trace: bool) -> dict:
    if trace:
        metrics = per_layer(cell, outcome)
    else:
        metrics = {m["name"]: {"value": float(outcome.end_to_end[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    if trace:
        device.update(busy_s=outcome.busy_s, window_s=outcome.window_s)
    line = {"correct": all(c.ok for c in outcome.checks),
            "attempted": outcome.attempted, "failed": outcome.failed,
            "metrics": metrics, "device": device}
    if trace and outcome.breakdown:
        line["breakdown"] = outcome.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    phases = Phases(T0)
    cell = find_cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    phases.mark("imports")
    devices = require_devices(cell.chips)
    phases.mark("runtime")
    peaks = peaks_for(devices[0].device_kind)
    enable_cache()
    kind = load_module(BENCH / "kinds" / f"{cell.config['kind']}.py")
    phases.mark("program_imports")
    outcome = kind.run(cell, args, devices, peaks, phases)
    line = result_line(cell, outcome, devices, bool(args.trace))
    for note, value in outcome.notes.items():
        log(f"{note}: {value}")
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
