"""Share of the traced time with at least one request in flight during
which no operation ran on the device; from the trace."""
from chipbench.lib import trace as tl


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None or not tr.ops:
        return None
    flight = [(max(run.host_to_ns(s), run.lo_ns),
               min(run.host_to_ns(t), run.hi_ns)) for s, t in run.inflight]
    flight = [(s, t) for s, t in flight if t > s]
    total = tl.length(flight)
    if total <= 0:
        return None
    busy = tl.intersect(tl.union(tr.ops[tr.devices[0]], run.lo_ns,
                                 run.hi_ns), flight)
    return 100.0 * (1.0 - tl.length(busy) / total)
