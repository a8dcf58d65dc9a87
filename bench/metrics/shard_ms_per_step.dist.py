"""Device milliseconds a chip was busy outside collective operations per
time step advanced in the traced window, averaged over the chips: each
chip's compute on its own band, the per-shard step (an enclosing
``while`` counts where no collective runs under it)."""
import trace_reduce


def read(ctx):
    devices = ctx.trace.devices
    steps = ctx.calls * ctx.info["steps_per_call"]
    if not devices or not steps:
        return None
    ns = 0
    for d in devices:
        coll = [s for s in d.ops if trace_reduce.COLLECTIVE.match(s.name)]
        ns += d.busy_ns() - sum(b - a for a, b in trace_reduce.union(coll))
    return 1e-6 * ns / len(devices) / steps
