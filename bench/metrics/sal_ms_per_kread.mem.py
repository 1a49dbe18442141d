"""Host milliseconds per thousand reads in the program's ``sal`` span
(suffix-array lookups of the seeds' intervals)."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_sal_s" not in snap:
        return None
    return snap["time_sal_s"] * 1e6 / ctx.reads_traced
