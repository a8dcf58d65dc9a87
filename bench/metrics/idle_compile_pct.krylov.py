"""Share of the traced window in which the device idled while the host was
inside ``repro.compile``: a runner's first dispatch, which traces, lowers,
compiles or loads from the cache, and enqueues (Krylov cells)."""
import program_spans


def read(ctx):
    return program_spans.idle_pct(ctx, [program_spans.COMPILE])
