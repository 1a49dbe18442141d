"""Host milliseconds per thousand reads in the program's ``bsw`` span
(seed extension, its BSW kernel dispatches and their waits included)."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_bsw_s" not in snap:
        return None
    return snap["time_bsw_s"] * 1e6 / ctx.reads_traced
