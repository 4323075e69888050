"""Share of the traced window in which no operation ran on the chip
(mean over the chips), in a cell whose units are label rounds."""


def read(ctx):
    if ctx.trace is None or not ctx.traced.work.get("rounds"):
        return None
    return 100.0 * ctx.trace.idle_share
