"""setup_s (host clock): process start to the first timed request.

Runtime start, system and pool, conversion, first generation, compile or
cache load, and the warm-up request.
"""


def read(ctx):
    return ctx.setup_s
