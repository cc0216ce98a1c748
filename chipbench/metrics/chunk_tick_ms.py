"""Mean device time of the chunk-tick program (prefill), from the trace;
each execution is paired with its dispatch span."""


def read(run):
    pairs = getattr(run, "tick_programs", lambda: [])()
    runs = [e.dur for t, e in pairs if t.kind == "chunk"]
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6
