"""collective_share (collectives, trace): device time in all-gather /
all-reduce and the other collective ops over the traced window, in %,
averaged over the cell's devices.  ``None`` where the trace has none."""


def read(ctx):
    s = ctx.summary
    if s is None or not any(s.collective_s.values()):
        return None
    return 100.0 * sum(s.collective_s.values()) / len(s.collective_s) / s.window_s
