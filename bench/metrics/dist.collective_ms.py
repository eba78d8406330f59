"""dist.collective_ms: device time in collective ops (halo exchange,
all-reduce, all-gather) per CG iteration, per chip (ms), from the traced
window. Time a collective overlaps with compute counts too."""
from bench import dist_work


def read(ctx):
    if ctx.trace is None or not ctx.trace.device_ops:
        return None
    secs = ctx.trace.seconds_where(dist_work.is_collective)
    return 1e3 * secs / sum(ctx.window.iters)
