"""Mean wall time of the engine's ``_dispatch`` (block-table and mask
uploads, then the enqueue), over the ticks run before the profiler
started; host clock."""


def read(run):
    ticks = getattr(run, "untraced_ticks", lambda: [])() \
        or getattr(run, "ticks", [])
    if not ticks:
        return None
    return 1e3 * sum(t.host_s for t in ticks) / len(ticks)
