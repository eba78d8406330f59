"""iter_ms: the window's wall time over all solver iterations it
completed (ms)."""


def read(ctx):
    return 1e3 * ctx.window.seconds / sum(ctx.window.iters)
