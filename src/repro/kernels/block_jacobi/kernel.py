"""Block-Jacobi apply Pallas TPU kernel.

``y[b] = inv_blocks[b] @ vp[b]`` for ``nb`` small ``(bs, bs)`` blocks.  The
kernel works block-minor and lane-dense (:mod:`repro.kernels.lanes`): the
inverted blocks as ``(bs, bs, rows, 128)`` and the vector segments as
``(bs, rows, 128)``, block ``b`` at ``[..., b // 128, b % 128]``.  One block
row is then ``bs`` elementwise multiply-adds of full vector tiles — no lane
shuffles, and no 8-wide minor axis padded out to 128 lanes.  Each grid step
covers ``block_nb`` blocks and is independent of the others.

Mixed precision: ``inv_blocks`` may arrive in a reduced *storage* precision
(bf16/fp16 — the adaptive block-Jacobi selection); the kernel upcasts inside
the body so the VMEM traffic pays the reduced footprint while the arithmetic
stays in the vector's precision (arXiv:2006.16852's storage/arithmetic
decoupling).

Padding blocks (appended to round ``nb`` up to a whole number of tiles) are
zero everywhere, produce zero rows, and are sliced off by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes


def vmem_bytes(block_nb: int, bs: int, itemsize: int) -> int:
    """Scoped VMEM of one launch: double-buffered inverted-block, segment and
    output tiles (vectors in f32) plus the compiler's scratch."""
    return 2 * block_nb * bs * (bs * itemsize + 2 * 4) + lanes.MOSAIC_SCRATCH_BYTES


def _block_jacobi_kernel(inv_ref, v_ref, o_ref):
    bs = v_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, o_ref.dtype)  # (bs, block_rows, 128)
    for j in range(bs):
        acc += inv_ref[:, j].astype(o_ref.dtype) * v_ref[j][None]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_nb", "interpret"))
def block_jacobi_apply(
    inv_blocks: jax.Array,
    vp: jax.Array,
    *,
    block_nb: int = 8192,
    interpret: bool = False,
) -> jax.Array:
    """y[b] = inv_blocks[b] @ vp[b] for (nb, bs, bs) blocks, (nb, bs) segments."""
    nb, bs, _ = inv_blocks.shape
    out_dtype = vp.dtype
    rows, block_rows = lanes.row_tiling(nb, block_nb, inv_blocks.dtype, out_dtype)
    inv_t = lanes.to_rows(jnp.moveaxis(inv_blocks, 0, -1), rows)  # (bs, bs, R, 128)
    v_t = lanes.to_rows(vp.T, rows)  # (bs, R, 128)
    tile = (block_rows, lanes.LANES)
    out = pl.pallas_call(
        _block_jacobi_kernel,
        name="block_jacobi_apply",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((bs, bs) + tile, lambda i: (0, 0, i, 0)),
            pl.BlockSpec((bs,) + tile, lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bs,) + tile, lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, rows, lanes.LANES), out_dtype),
        compiler_params=lanes.compiler_params(
            vmem_bytes(block_rows * lanes.LANES, bs, inv_blocks.dtype.itemsize)
        ),
        interpret=interpret,
    )(inv_t, v_t)
    return lanes.from_rows(out, nb).T
