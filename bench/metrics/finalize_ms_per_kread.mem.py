"""Host milliseconds per thousand reads in the program's ``finalize``
span (CIGAR by banded global alignment, flags, MAPQ, SAM records)."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_finalize_s" not in snap:
        return None
    return snap["time_finalize_s"] * 1e6 / ctx.reads_traced
