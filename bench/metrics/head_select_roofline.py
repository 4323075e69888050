"""``head_select``'s share of its roofline: the least time the chip needs
for the kernel's work in the traced window (the larger of its FLOPs over
the bf16 peak and its least bytes over the HBM bandwidth, both from
shapes) over the summed device time of its Mosaic calls in the trace.
Beside the value it names the term that bounds it (``flops`` or
``bytes``) and gives both terms' seconds."""
from bench.peaks import roofline_seconds
from bench.trace import kernel_time


def read(ctx):
    work = ctx.flops.get("head_select")
    if ctx.trace is None or work is None:
        return None
    rounds = ctx.traced.work.get("rounds", 0)
    busy = kernel_time(ctx.trace, "head_select")
    if not rounds or not busy:
        return None
    flops, moved = work["flops"] * rounds, work["bytes"] * rounds
    least, bound = roofline_seconds(flops, moved, ctx.peak)
    return {"value": 100.0 * least / busy, "bound": bound,
            "flops_s": flops / ctx.peak["bf16_flops_per_s"],
            "bytes_s": moved / ctx.peak["hbm_bytes_per_s"],
            "kernel_s": busy}
