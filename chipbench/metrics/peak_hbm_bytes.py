"""peak_hbm_bytes: ``peak_bytes_in_use`` after the window, the largest over
the cell's devices (``None`` where the backend keeps no such count)."""


def read(ctx):
    return ctx.memory_peak_bytes or None
