"""Exchanges of at least 1 MB per rank: the bytes a chip must move for
the operation's semantics over the interconnect's peak, divided by the
device time of the exchange's program (the slowest chip's execution),
in percent, summed over the traced exchanges."""
from chipbench.lib import flops

LARGE = 1 << 20


def read(run):
    least = device = 0.0
    for x, execs in getattr(run, "runs", []):
        if x.size < LARGE:
            continue
        least += flops.exchange_bytes(x.op, x.size, run.ranks) \
            / run.peaks["ici_bytes_per_s"]
        device += max(e.dur for e in execs) / 1e9
    if device <= 0:
        return None
    return 100.0 * least / device
