"""cg_iter_roofline (solver, trace): the least HBM bytes one preconditioned
CG iteration must move (``roofline.cg_iter_min_bytes``) over the HBM peak,
divided by the device's busy time in the window per iteration, in %.
Bandwidth bounds it.  CG on one chip only: per-chip shares on several
chips, and other solvers' iterations, are not defined yet."""

import numpy as np

from chipbench import roofline


def read(ctx):
    iterations = sum(r["iterations"] for r in ctx.requests)
    if (ctx.summary is None or iterations == 0 or ctx.lib.distributed
            or ctx.config["solver"] != "cg"):
        return None
    s = ctx.lib.system
    least = roofline.cg_iter_min_bytes(s.n, s.nnz, ctx.config["precond"],
                                       np.dtype(ctx.config["dtype"]).itemsize)
    per_iteration = ctx.summary.mean_busy_s / iterations
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") / per_iteration
