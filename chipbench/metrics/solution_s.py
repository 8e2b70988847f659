"""solution_s (host clock): the window over the requests completed in it.

A request is handed over as host arrays and ends when its solution is back
on the host; requests are whole, so this is the mean time to solution.
"""


def read(ctx):
    return ctx.window_s / len(ctx.requests)
