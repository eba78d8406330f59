"""dist.hbm_share: least bytes of the window's CG solves over what the
HBM of the configuration's ``shards`` chips could move in the window
(%)."""
from bench import dist_work


def read(ctx):
    return dist_work.window_hbm_share(ctx)
