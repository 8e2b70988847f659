"""spmv_roofline (kernels, trace): the operator's SpMV through the
program's public op (``sparse.ops.apply``) on the cell's executor, jitted
alone and run 20 times under the profiler.  Least bytes (values, ``x`` and
``y`` at the stated precision, no index bytes) over the HBM peak, divided
by the device time per call, in %.  Bandwidth bounds it."""

import numpy as np

from chipbench import roofline


def read(ctx):
    lib = ctx.lib
    if ctx.summary is None or lib.A is None or lib.distributed:
        return None
    import jax

    from repro.sparse import ops

    s = lib.system
    dtype = np.dtype(ctx.config["dtype"])
    x = jax.device_put(np.ones(s.n, dtype), lib.device)
    t = ctx.probe("spmv", lambda A, v: ops.apply(A, v, executor=lib.ex), lib.A, x)
    least = roofline.spmv_min_bytes(s.n, s.nnz, dtype.itemsize)
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") / t
