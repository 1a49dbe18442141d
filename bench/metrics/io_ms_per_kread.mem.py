"""Host milliseconds per thousand reads in the program's ``io`` span
(reading and packing the FASTQ batches, writing the SAM lines)."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_io_s" not in snap:
        return None
    return snap["time_io_s"] * 1e6 / ctx.reads_traced
