"""Host milliseconds per thousand reads in the program's
``finalize.replay`` spans: ``chain2aln`` replayed over the BSW result
table, the first part of finalize."""


def read(ctx):
    snap = ctx.snapshot
    key = "time_finalize.replay_s"
    if not snap or not ctx.reads_traced or key not in snap:
        return None
    return snap[key] * 1e6 / ctx.reads_traced
