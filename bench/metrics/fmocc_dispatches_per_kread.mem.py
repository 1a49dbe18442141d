"""Occ-kernel dispatches per thousand reads: the program's
``kernel_fmocc_dispatches`` counter, an exact count."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced:
        return None
    n = snap.get("kernel_fmocc_dispatches", 0)
    return n * 1e3 / ctx.reads_traced if n else None
