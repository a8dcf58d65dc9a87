"""Share of the traced window the cell's chips spent in collective
operations (the distributed tier's halo ``ppermute``:
``collective-permute-start``/``-done``), averaged over the chips."""


def read(ctx):
    t = ctx.trace.collective_s()
    if t is None:
        return None
    return 100.0 * t / ctx.window_s
