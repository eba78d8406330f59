"""solve_p95_ms: 95th percentile of every solve of the window, each
from dispatch to ready on the host clock (ms)."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(ctx.window.solve_s, 95))
