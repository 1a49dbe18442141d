"""Host milliseconds per thousand reads in the program's paired-end
spans: ``pe_stat`` (insert-size estimate), ``pe_rescue`` (mate rescue)
and ``pe_pair`` (pairing).  Nothing to read in a single-end cell."""

SPANS = ("time_pe_stat_s", "time_pe_rescue_s", "time_pe_pair_s")


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or not any(s in snap for s in SPANS):
        return None
    return sum(snap.get(s, 0.0) for s in SPANS) * 1e6 / ctx.reads_traced
