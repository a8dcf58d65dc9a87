"""The PERKS stencil kernels' share of their roofline: the least time the
chip needs for the calls' compulsory bytes and useful flops (``work.py``),
over the kernels' device time in the traced window."""
import work


def read(ctx):
    t = ctx.trace.op_s(ctx.info["kernel_prefix"])
    if t is None or ctx.peak is None:
        return None
    least, _ = work.least_time_s(ctx.info["work_per_call"] * ctx.calls,
                                 ctx.peak)
    return 100.0 * least / t
