"""Share of the traced window (%) in which the device ran no operation:
100 * (1 - union of the device's operation intervals / window)."""


def read(ctx):
    if ctx.device is None or not ctx.device.ops:
        return None
    return 100.0 * (1.0 - ctx.device.busy_s() / ctx.device.window_s)
