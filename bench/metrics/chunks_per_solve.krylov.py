"""Runner dispatches (``repro.compile`` and ``repro.chunk`` spans) per solve
completed in the traced window: one a host sync in a device loop that
syncs every ``sync_every`` iterations (Krylov cells)."""
import program_spans


def read(ctx):
    counts = program_spans.span_counts(ctx.trace)
    n = counts[program_spans.COMPILE] + counts[program_spans.CHUNK]
    if not n or not ctx.info["solves"]:
        return None
    return n / ctx.info["solves"]
