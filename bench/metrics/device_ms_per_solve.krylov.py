"""Device busy milliseconds per solve completed in the traced window."""


def read(ctx):
    busy = ctx.trace.busy_s()
    if busy is None or not ctx.info["solves"]:
        return None
    return 1e3 * busy / ctx.info["solves"]
