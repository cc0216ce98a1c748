"""Least time of the decode ticks over their device time, in percent.
A tick's least time is max(FLOPs / bf16 peak, bytes / HBM bandwidth),
with bytes = every weight once + the K/V of its live tokens
(``lib/flops.py``); device time is its program's execution in the
trace."""
from chipbench.lib import flops


def read(run):
    pairs = [(t, e) for t, e in getattr(run, "tick_programs", lambda: [])()
             if t.kind == "decode"]
    if not pairs:
        return None
    least = sum(flops.least_time(flops.tick_flops(run.model, t.rows),
                                 flops.decode_bytes(run.model, t.rows),
                                 run.peaks) for t, _ in pairs)
    device = sum(e.dur for _, e in pairs) / 1e9
    return 100.0 * least / device
