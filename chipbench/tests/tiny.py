"""Tiny cells for the CPU tests: the real harness, at toy sizes."""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from chipbench.lib.harness import BENCH, Cell, read_json  # noqa: E402

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 256}


def serve_cell(config="h2o-danube-1.8b", traffic="chat-azure-conv",
               slots=4, max_len=96, chunk=16, rate=4.0) -> Cell:
    conf = copy.deepcopy(read_json(BENCH / "configs" / f"{config}.json"))
    conf["model"].update(TINY_MODEL)
    conf["model"]["sliding_window"] = min(conf["model"]["sliding_window"],
                                          64) or 0
    conf["serving"] = {"slots": slots, "max_len": max_len, "block_size": 16,
                       "prefill_chunk": chunk}
    conf["check"]["rows"] = 2
    conf["trace_seconds"] = 1
    mix = copy.deepcopy(read_json(BENCH / "traffic" / f"{traffic}.json"))
    mix["prompt"].update(min=4, max=64)
    mix["output"].update(min=2, max=24)
    if "median" in mix["prompt"]:
        mix["prompt"]["median"], mix["output"]["median"] = 24, 8
    if mix["arrival"] == "poisson":
        mix["rate_per_s"] = rate
    return Cell("tiny-serve", 1, config, conf, traffic, mix, [], [])


def comm_cell(sizes=(8, 8192), p2p=(16, 4096)) -> Cell:
    conf = read_json(BENCH / "configs" / "communicator-v5e4.json")
    conf["trace_seconds"] = 1
    mix = read_json(BENCH / "traffic" / "comm-paper-sweep.json")
    mix["collectives"]["bytes_per_rank"] = list(sizes)
    mix["p2p"]["bytes"] = list(p2p)
    return Cell("tiny-comm", 4, "communicator-v5e4", conf,
                "comm-paper-sweep", mix, [], [])


def args(seed=12345678901, seconds=2.0, trace=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=trace)


def run_cell(cell, a, devices=None):
    """kind.run for ``cell`` on this process's devices (the harness's
    look for an accelerator skipped), and the result line run.py
    prints."""
    import time

    import jax
    from chipbench.lib.harness import Phases, load_module
    from chipbench.peaks import PEAKS
    from chipbench.run import result_line
    devices = devices or jax.devices()[:cell.chips]
    kind = load_module(BENCH / "kinds" / f"{cell.config['kind']}.py")
    out = kind.run(cell, a, devices, PEAKS["TPU v5 lite"],
                   Phases(time.perf_counter()))
    return out, result_line(cell, out, devices, bool(a.trace))


def comm_faults() -> dict:
    """``correct`` of tiny Communicator runs: sound, with an answer
    altered where it is produced, and with the exchange left out.
    Needs four devices.  Also the control's: the answers in bfloat16
    counted by the run's own comparison."""
    import jax
    from chipbench.lib.harness import load_module
    from repro.comms import Communicator
    out = {}
    for fault in ("none", "altered", "left_out"):
        saved = {op: getattr(Communicator, op)
                 for op in ("allreduce", "bcast")}
        if fault == "altered":
            Communicator.allreduce = lambda self, x: saved["allreduce"](
                self, x) + 1.0
        elif fault == "left_out":
            Communicator.bcast = lambda self, x, root=0: x
        try:
            _, line = run_cell(comm_cell(), args(seconds=0.5))
        finally:
            for op, f in saved.items():
                setattr(Communicator, op, f)
        out[fault] = line["correct"]
    control = load_module(BENCH / "control.py")
    out["control"] = control.comm_readings(comm_cell(), 1, jax.devices()[:4])[
        "correct"]
    return out


if __name__ == "__main__":
    # python chipbench/tests/tiny.py serve|comm: one tiny run, its outcome
    import time

    import jax
    from chipbench.lib.harness import Phases, load_module
    from chipbench.peaks import PEAKS
    which = sys.argv[1]
    if which == "comm-faults":
        print(json.dumps(comm_faults()))
        sys.exit(0)
    cell = serve_cell() if which == "serve" else comm_cell()
    kind = load_module(BENCH / "kinds" / f"{cell.config['kind']}.py")
    out = kind.run(cell, args(trace=int(sys.argv[2]) if len(sys.argv) > 2
                              else 0), jax.devices()[:cell.chips],
                   PEAKS["TPU v5 lite"], Phases(time.perf_counter()))
    print(json.dumps({"e2e": out.end_to_end, "checks": [
        (c.name, c.value, c.limit) for c in out.checks],
        "attempted": out.attempted}, default=str))
