"""setup.optimize_s: host seconds of the optimize call (partition,
format selection, conversion; the MG hierarchy)."""


def read(ctx):
    return ctx.setup["optimize_s"]
