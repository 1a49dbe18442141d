"""Useful Smith-Waterman cell updates per second of BSW-kernel device
time, in billions (GCUPS): the program's useful-cell counters over the
device time of the Pallas BSW kernel's own operation (the custom call
that runs ``_bsw_kernel_body``; the layout copies of its dispatch are
left out).  A rate, not a share: v5e publishes no int32 VPU peak."""

from harness import kernels


def read(ctx):
    if ctx.device is None or not ctx.snapshot:
        return None
    t = kernels.bsw_device_s(ctx.device, ctx.host_spans)
    cells = kernels.bsw_useful_cells(ctx.snapshot)
    if t <= 0 or cells <= 0:
        return None
    return cells / t / 1e9
