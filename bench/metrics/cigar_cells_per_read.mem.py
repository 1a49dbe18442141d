"""Cells of the CIGAR dynamic programme per read: the program's
``finalize_cigar_cells`` counter (the exact band cells
``global_align_cigar`` fills) over the reads traced."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "finalize_cigar_cells" not in snap:
        return None
    return snap["finalize_cigar_cells"] / ctx.reads_traced
