"""Wall seconds per homogenization round: the whole window over the
rounds completed back to back in it."""


def read(ctx):
    rounds = ctx.window.work.get("rounds", 0)
    return ctx.window.seconds / rounds if rounds else None
