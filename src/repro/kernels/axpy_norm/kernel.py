"""Fused axpy + squared-norm Pallas TPU kernel — apply-with-reduction.

The second arXiv:2011.08879 fusion on the Krylov hot path: every iteration
updates the residual (``r ← r - α·Ap``) and immediately needs ``‖r‖²`` for
the stopping criterion.  Unfused, that is three HBM round trips over the
vector (write z, read z, reduce); fused, the updated tile is reduced while it
is still in VMEM — one read of x and y, one write of z, and a scalar.

The vectors stream as lane-dense ``(rows, 128)`` views
(:mod:`repro.kernels.lanes`).  Each grid step writes its z block and adds its
column sums of ``z²`` into a ``(1, 128)`` accumulator block revisited by
every step (TPU grids iterate in order, so the read-modify-write is
well-defined); the 128 partial sums are added outside.  ``alpha`` rides in
SMEM as a ``(1, 1)`` operand of the accumulation dtype (at least f32), so
the kernel stays trace-compatible with solver loops where it is a traced
scalar.  Tail padding (x = y = 0) produces z = 0
and adds nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes


def vmem_bytes(block_n: int, itemsize: int) -> int:
    """Scoped VMEM of one launch: double-buffered x, y and z blocks, the
    accumulator and the compiler's scratch."""
    return 2 * 3 * block_n * itemsize + lanes.LANES * 4 + lanes.MOSAIC_SCRATCH_BYTES


def _axpy_norm_kernel(alpha_ref, x_ref, y_ref, z_ref, ss_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        ss_ref[...] = jnp.zeros_like(ss_ref)

    acc = ss_ref.dtype
    z = alpha_ref[0, 0] * x_ref[...].astype(acc) + y_ref[...].astype(acc)
    z_ref[...] = z.astype(z_ref.dtype)
    ss_ref[...] += jnp.sum(z * z, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def axpy_norm(
    alpha: jax.Array,
    x: jax.Array,
    y: jax.Array,
    *,
    block_n: int = 8192,
    interpret: bool = False,
):
    """(z, z·z) with z = alpha*x + y, computed in one pass over the vectors."""
    n = x.shape[0]
    rows, block_rows = lanes.row_tiling(n, block_n, x.dtype)
    vec = pl.BlockSpec((block_rows, lanes.LANES), lambda i: (i, 0))
    acc = jnp.promote_types(x.dtype, jnp.float32)
    z, ss = pl.pallas_call(
        _axpy_norm_kernel,
        name="axpy_norm",
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vec, vec],
        out_specs=[vec, pl.BlockSpec((1, lanes.LANES), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes.LANES), x.dtype),
            jax.ShapeDtypeStruct((1, lanes.LANES), acc),
        ],
        compiler_params=lanes.compiler_params(
            vmem_bytes(block_rows * lanes.LANES, x.dtype.itemsize)
        ),
        interpret=interpret,
    )(
        jnp.asarray(alpha, acc).reshape(1, 1),
        lanes.to_rows(x, rows),
        lanes.to_rows(y, rows),
    )
    return lanes.from_rows(z, n), jnp.sum(ss).astype(x.dtype)
