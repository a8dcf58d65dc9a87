"""Share of the traced window in which the device idled while the host was
inside ``repro.barrier`` or ``repro.chunk``: the round trip at each host
sync, the read-back and decision, then the next chunk's dispatch (Krylov
cells)."""
import program_spans


def read(ctx):
    return program_spans.idle_pct(
        ctx, [program_spans.BARRIER, program_spans.CHUNK])
