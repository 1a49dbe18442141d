"""Host milliseconds per thousand reads in the program's ``chain`` span."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_chain_s" not in snap:
        return None
    return snap["time_chain_s"] * 1e6 / ctx.reads_traced
