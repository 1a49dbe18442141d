"""Host milliseconds per thousand reads in the program's
``finalize.cigar`` spans: the banded global alignment that writes each
record's CIGAR (``core.sam.global_align_cigar``)."""


def read(ctx):
    snap = ctx.snapshot
    key = "time_finalize.cigar_s"
    if not snap or not ctx.reads_traced or key not in snap:
        return None
    return snap[key] * 1e6 / ctx.reads_traced
