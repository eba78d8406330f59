"""dist.iters: mean CGResult.iters per solve of the window."""


def read(ctx):
    return sum(ctx.window.iters) / len(ctx.window.iters)
