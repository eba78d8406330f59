"""spmv_roofline: least SpMV bytes of the traced window over the peak
HBM bytes/s times the device time of the SpMV kernel's events (%).

The SpMV calls are one per CG iteration plus the initial residual. Only
the Pallas SpMV kernels' own events count (custom calls whose op name
holds ``spmv``): the trace does not tie the XLA ops around a kernel, such
as a relayout of its table, to the SpMV. Operations are 2 per value, far
under the chip's compute peak, so bytes bound the kernel.
"""
import re

_SPMV = re.compile(r"spmv")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    secs = ctx.trace.seconds_where(
        lambda name: _SPMV.search(name) and "custom-call" in name)
    if secs <= 0:
        return None
    g = ctx.config["grid"]
    calls = sum(k + 1 for k in ctx.window.iters)
    least = calls * ctx.work.spmv_bytes(ctx.work.stencil_nnz(*g))
    return 100.0 * least / (ctx.peaks["hbm_bytes_per_s"] * secs)
