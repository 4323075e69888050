"""Model FLOP utilization of label rounds: the forward FLOPs of one round
(public and calibration sets through every node, head included, from
shapes) times the rounds in the traced window, over the traced window's
length, the chips and their bf16 peak."""


def read(ctx):
    if ctx.trace is None or "round" not in ctx.flops:
        return None
    rounds = ctx.traced.work.get("rounds", 0)
    if not rounds:
        return None
    achieved = ctx.flops["round"] * rounds / ctx.trace.window_s
    return 100.0 * achieved / (ctx.chips * ctx.peak["bf16_flops_per_s"])
