"""The whole traced window's share of the chips' peak: the least time for
the work of every call completed in the window (``work.py``), over the
window times the chips."""
import work


def read(ctx):
    if ctx.peak is None or not ctx.calls:
        return None
    least, _ = work.least_time_s(ctx.info["work_per_call"] * ctx.calls,
                                 ctx.peak)
    return 100.0 * least / (ctx.window_s * ctx.chips)
