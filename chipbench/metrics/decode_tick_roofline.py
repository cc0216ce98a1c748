"""Least time of the decode ticks over their device time, in percent.
A tick's least time is max(FLOPs / bf16 peak, bytes / HBM bandwidth),
with FLOPs and bytes (every weight once + the cache of its live tokens)
counted by the configuration's reference module; device time is its
program's execution in the trace."""
from chipbench.lib import flops


def read(run):
    pairs = [(t, e) for t, e in getattr(run, "tick_programs", lambda: [])()
             if t.kind == "decode"]
    if not pairs:
        return None
    counts, model = run.counts, run.model
    least = sum(flops.least_time(counts.tick_flops(model, t.rows),
                                 counts.decode_bytes(model, t.rows),
                                 run.peaks) for t, _ in pairs)
    device = sum(e.dur for _, e in pairs) / 1e9
    return 100.0 * least / device
