"""Device milliseconds of the PERKS stencil kernels (operations named
``stencil_perks...``) per time step they advanced in the traced window."""


def read(ctx):
    t = ctx.trace.op_s(ctx.info["kernel_prefix"])
    if t is None:
        return None
    return 1e3 * t / (ctx.calls * ctx.info["steps_per_call"])
