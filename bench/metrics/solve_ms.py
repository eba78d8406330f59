"""solve_ms: the window's wall time over the solves it completed (ms)."""


def read(ctx):
    return 1e3 * ctx.window.seconds / len(ctx.window.solve_s)
