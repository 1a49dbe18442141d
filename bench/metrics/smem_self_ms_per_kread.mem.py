"""Host milliseconds per thousand reads in the program's ``smem`` span
less its ``kernel.fmocc`` child spans: the SMEM search's own host work
between occ-kernel dispatches."""


def read(ctx):
    snap = ctx.snapshot
    if not snap or not ctx.reads_traced or "time_smem_s" not in snap:
        return None
    own = snap["time_smem_s"] - snap.get("time_kernel.fmocc_s", 0.0)
    return own * 1e6 / ctx.reads_traced
