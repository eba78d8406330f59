"""dist.idle_share: 1 - device busy union / traced window (%), averaged
over the chips traced."""


def read(ctx):
    share = ctx.trace.idle_share if ctx.trace is not None else None
    return None if share is None else 100.0 * share
