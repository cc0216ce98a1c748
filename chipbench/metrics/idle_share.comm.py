"""Share of the traced window in which no operation ran on the device,
averaged over the chips; from the trace."""
from chipbench.lib import trace as tl


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None or not tr.ops or run.hi_ns <= run.lo_ns:
        return None
    busy = tl.busy_ns(tr, run.lo_ns, run.hi_ns)
    return 100.0 * (1.0 - busy / (run.hi_ns - run.lo_ns))
