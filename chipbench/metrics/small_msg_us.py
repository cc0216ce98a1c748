"""Mean wall time of an exchange of at most 8 KB per rank, over the
passes run before the profiler started; host clock."""

SMALL = 8 << 10


def read(run):
    small = [x for x in getattr(run, "exchanges", []) if x.size <= SMALL]
    calls = sum(x.calls for x in small)
    if not calls:
        return None
    return 1e6 * sum(x.wall_s for x in small) / calls
