"""dist.spmv_roofline: least SpMV bytes per chip of the traced window
over the peak HBM bytes/s times each chip's device time in the Pallas
SpMV kernels (%).

A chip's least bytes per SpMV are its rows' values
(``bench/dist_work.py``), counted once per CG iteration and for the
initial residual. The time is that of the custom calls whose op text
holds ``spmv``, averaged over the chips traced: a chip that runs a
kernel on its interior and again on its boundary rows pays both.
"""
import re

from bench import dist_work

_SPMV = re.compile(r"spmv")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    secs = ctx.trace.seconds_where(
        lambda name: _SPMV.search(name) and "custom-call" in name)
    if secs <= 0:
        return None
    least = dist_work.spmv_calls(ctx.window) * dist_work.chip_spmv_bytes(
        ctx.config)
    return 100.0 * least / (ctx.peaks["hbm_bytes_per_s"] * secs)
