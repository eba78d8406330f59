"""mg.hbm_share: least bytes of the window's MG-PCG sets over what the
chip's HBM could move in the window (%)."""


def read(ctx):
    return ctx.work.window_hbm_share(ctx)
