"""Share (%) of the HBM roofline that the occ step reached: the floor of
the bytes its queries must read (``harness.kernels``) over its device
time, divided by the chip's HBM bandwidth.  The device time is that of
every device operation inside the program's ``kernel.fmocc`` spans
(``harness.kernels``): the Pallas compare+count kernel, the XLA gather
that feeds it and the interval arithmetic of the round."""

from harness import kernels


def read(ctx):
    if ctx.device is None or not ctx.peaks:
        return None
    t = kernels.fmocc_device_s(ctx.device, ctx.host_spans)
    nbytes = kernels.fmocc_floor_bytes(ctx.host_spans)
    if t <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / t / ctx.peaks["hbm_bytes_per_s"]
