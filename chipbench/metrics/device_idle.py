"""device_idle (device, trace): 1 - the union of device-op intervals over
the traced window, in %, averaged over the cell's devices."""


def read(ctx):
    if ctx.summary is None:
        return None
    return 100.0 * ctx.summary.idle_share
