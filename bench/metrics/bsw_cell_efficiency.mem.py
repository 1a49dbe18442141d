"""Useful share (%) of the Smith-Waterman cells the BSW blocks computed:
the program's ``cells_useful`` over ``cells_total``, mate rescue's cells
included."""


def read(ctx):
    snap = ctx.snapshot or {}
    useful = snap.get("cells_useful", 0) + snap.get("rescue_cells_useful", 0)
    total = snap.get("cells_total", 0) + snap.get("rescue_cells_total", 0)
    return 100.0 * useful / total if total else None
