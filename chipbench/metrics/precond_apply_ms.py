"""precond_apply_ms (preconditioner, trace): the generated preconditioner's
apply, jitted alone and run 20 times under the profiler; device time per
call in ms."""

import numpy as np


def read(ctx):
    lib = ctx.lib
    if ctx.summary is None or lib.M is None or isinstance(lib.M, str):
        return None
    import jax

    v = jax.device_put(np.ones(lib.system.n, np.dtype(ctx.config["dtype"])),
                       lib.device)
    t = ctx.probe("precond_apply",
                  lambda M, u: M.apply(u, executor=lib.ex), lib.M, v)
    return 1e3 * t
