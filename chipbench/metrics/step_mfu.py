"""Model FLOPs of the prompt and decode tokens of the ticks dispatched
in the traced window, counted by the configuration's reference module,
over the window and the chip's bf16 peak."""


def read(run):
    if getattr(run, "trace", None) is None:
        return None
    ticks = run.traced_ticks()
    window = (run.hi_ns - run.lo_ns) / 1e9
    if not ticks or window <= 0:
        return None
    work = sum(run.counts.tick_flops(run.model, t.rows) for t in ticks)
    return 100.0 * work / window / run.peaks["bf16_flops_per_s"]
