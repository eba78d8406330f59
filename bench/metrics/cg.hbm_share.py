"""cg.hbm_share: least bytes of the window's CG solves over what the
chip's HBM could move in the window (%)."""


def read(ctx):
    return ctx.work.window_hbm_share(ctx)
