"""spmv_loop_roofline (kernels, trace): the SpMV as it runs inside the CG
loop, found by the program's op scopes: ``spmv_dot_ell`` (the fused SpMV and
dot of every iteration, its ``x[col_idx]`` gather included) and ``spmv_ell``
(the initial residual).  Least bytes (``roofline.spmv_min_bytes``: values,
``x`` and ``y`` at the stated precision, no index bytes) over the HBM peak,
divided by the device self time under those scopes per call, in %; one call
per iteration and one per solve.  Bandwidth bounds it.  ``None`` where no op
ran under them."""

import numpy as np

from chipbench import roofline, scopes

SCOPES = ("spmv_dot_ell", "spmv_ell")

scopes.enable_for_traced_run()


def read(ctx):
    if ctx.summary is None or ctx.lib.distributed:
        return None
    seconds = scopes.seconds_under(ctx, *SCOPES)
    calls = scopes.loop_calls(ctx)
    if seconds is None or calls == 0:
        return None
    s = ctx.lib.system
    least = roofline.spmv_min_bytes(s.n, s.nnz, np.dtype(ctx.config["dtype"]).itemsize)
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") / (seconds / calls)
