"""Share of the traced window in which the device idled while the host was
inside no program span: the caller's work between solves (the right-hand
side, the problem's build, the wait for the result; Krylov cells)."""
import program_spans


def read(ctx):
    return program_spans.idle_pct(ctx, [None])
