"""Reads aligned and written as SAM per second (a pair counts as two):
the reads of every batch completed in the window, over the time from the
window's start to the last of those completions."""


def read(ctx):
    done = [(t, n) for t, n in ctx.completions if t <= ctx.seconds]
    if not done:
        return None
    return sum(n for _, n in done) / done[-1][0]
