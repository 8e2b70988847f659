"""ELL SpMV Pallas TPU kernel, with an optional fused dot epilogue.

XLA builds the ``x`` operand outside the kernel; the kernel streams the
values and the matching ``x`` entries and reduces each row.  Both operands
are laid out slot-major and lane-dense — ``(k, rows, 128)``, row ``r`` of the
matrix at ``[:, r // 128, r % 128]`` (:mod:`repro.kernels.lanes`) — so the
row reduction is an elementwise sum over the leading ``k`` axis and the
output ``y`` is a lane-dense ``(rows, 128)`` view.  This is Ginkgo's
column-major ELL storage: consecutive rows sit in consecutive lanes.

The operand is produced one of two ways, by the matrix's layout
(:class:`repro.sparse.formats.Ell`).  Left-packed slots gather
``x[col_idx]``.  Diagonal-aligned slots (``offsets`` given) need no gather:
slot ``q`` of every row reads ``x[r + offsets[q]]``, a contiguous slice of
``x`` zero-padded at both ends, and ``col_idx`` is not read.  That path's
``pallas_call`` is named ``spmv_ell_band`` / ``spmv_dot_ell_band``.

Grid = (row blocks, k blocks), k innermost; partial sums accumulate in the
revisited output block (TPU grids iterate in order, so the read-modify-write
across k steps is well-defined).

With ``w`` given, each step also adds ``Σ_r w_r · partial_r`` into a
``(1, 128)`` accumulator revisited by every step — the apply-with-reduction
fusion of arXiv:2011.08879 (``p·Ap`` in CG): the dot is linear in the tile
contributions, so accumulation order only changes rounding.  Padding rows
carry ``w = 0`` and value 0.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes


def vmem_bytes(block_m: int, block_k: int, itemsize: int, *, dot: bool) -> int:
    """Scoped VMEM of one launch: double-buffered value and gathered-x slabs,
    the y block (and the w block with ``dot``), the dot accumulator and the
    compiler's scratch."""
    slabs = 2 * 2 * block_k * block_m * itemsize
    vectors = 2 * (2 if dot else 1) * block_m * itemsize
    return slabs + vectors + lanes.LANES * 4 + lanes.MOSAIC_SCRATCH_BYTES


def _ell_kernel(vals_ref, xg_ref, *refs, dot: bool):
    i, j = pl.program_id(0), pl.program_id(1)
    if dot:
        w_ref, y_ref, d_ref = refs
    else:
        (y_ref,) = refs

    @pl.when(j == 0)
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    acc = jnp.promote_types(y_ref.dtype, jnp.float32)
    part = jnp.sum(
        vals_ref[...].astype(acc) * xg_ref[...].astype(acc), axis=0
    )  # (block_rows, 128)
    y_ref[...] += part.astype(y_ref.dtype)
    if dot:

        @pl.when((i == 0) & (j == 0))
        def _init_dot():
            d_ref[...] = jnp.zeros_like(d_ref)

        d_ref[...] += jnp.sum(
            w_ref[...].astype(acc) * part, axis=0, keepdims=True
        ).astype(d_ref.dtype)


def _shifted_slabs(x, offsets, rows: int, pk: int):
    """``(pk, rows, 128)`` operand of diagonal-aligned slots: slab ``q``
    holds ``x[r + offsets[q]]`` at row ``r`` (0 outside ``x``), slabs past
    ``len(offsets)`` are zero."""
    n, span = x.shape[0], rows * lanes.LANES
    lo = max(-min(offsets), 0)
    xpad = jnp.pad(x, (lo, max(max(offsets), 0) + span - n))
    slabs = [jax.lax.slice_in_dim(xpad, lo + d, lo + d + span) for d in offsets]
    slabs += [jnp.zeros(span, x.dtype)] * (pk - len(offsets))
    return jnp.stack(slabs).reshape(pk, rows, lanes.LANES)


def ell_apply(
    col_idx: jax.Array,
    values: jax.Array,
    x: jax.Array,
    w: Optional[jax.Array] = None,
    *,
    offsets: Optional[Tuple[int, ...]] = None,
    block_m: int,
    block_k: int,
    interpret: bool,
):
    """``y = A @ x`` (and ``w · y`` when ``w`` is given) for ELL-format A;
    ``offsets`` are the diagonals of aligned slots (``Ell.offsets``)."""
    m, k = values.shape
    dtype = jnp.result_type(values.dtype, x.dtype)
    dot = w is not None
    rows, block_rows = lanes.row_tiling(m, block_m, values.dtype, dtype)
    block_k = max(min(block_k, k), 1)
    pk = pl.cdiv(k, block_k) * block_k
    # slot-major, lane-dense operands; padding is (col 0, value 0)
    vals_t = lanes.to_rows(values.T, rows)
    if pk != k:
        vals_t = jnp.pad(vals_t, ((0, pk - k), (0, 0), (0, 0)))
    if offsets is not None:
        xg = _shifted_slabs(x, offsets, rows, pk)
    else:
        cols_t = lanes.to_rows(col_idx.T, rows)
        if pk != k:
            cols_t = jnp.pad(cols_t, ((0, pk - k), (0, 0), (0, 0)))
        xg = x[cols_t]  # the gather stays in XLA
    name = ("spmv_dot_ell" if dot else "spmv_ell") + (
        "" if offsets is None else "_band"
    )

    slab = pl.BlockSpec((block_k, block_rows, lanes.LANES), lambda i, j: (j, i, 0))
    vec = pl.BlockSpec((block_rows, lanes.LANES), lambda i, j: (i, 0))
    in_specs = [slab, slab]
    operands = [vals_t, xg]
    out_specs = [vec]
    out_shape = [jax.ShapeDtypeStruct((rows, lanes.LANES), dtype)]
    if dot:
        in_specs.append(vec)
        operands.append(lanes.to_rows(w.astype(dtype), rows))
        out_specs.append(pl.BlockSpec((1, lanes.LANES), lambda i, j: (0, 0)))
        acc = jnp.promote_types(dtype, jnp.float32)
        out_shape.append(jax.ShapeDtypeStruct((1, lanes.LANES), acc))
    itemsize = max(jnp.dtype(values.dtype).itemsize, jnp.dtype(dtype).itemsize)
    outs = pl.pallas_call(
        functools.partial(_ell_kernel, dot=dot),
        name=name,
        grid=(rows // block_rows, pk // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=lanes.compiler_params(
            vmem_bytes(block_rows * lanes.LANES, block_k, itemsize, dot=dot)
        ),
        interpret=interpret,
    )(*operands)
    y = lanes.from_rows(outs[0], m)
    if dot:
        return y, jnp.sum(outs[1]).astype(dtype)
    return y


@functools.partial(
    jax.jit, static_argnames=("offsets", "block_m", "block_k", "interpret")
)
def spmv_ell(
    col_idx: jax.Array,
    values: jax.Array,
    x: jax.Array,
    *,
    offsets: Optional[Tuple[int, ...]] = None,
    block_m: int = 8192,
    block_k: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """y = A @ x for ELL-format A given as (col_idx, values) of shape (m, k).

    ``block_m`` rows (a multiple of 1024 = 8 sublanes x 128 lanes, rounded
    down) and ``block_k`` ELL slots are streamed per grid step; ``offsets``
    (``Ell.offsets``) selects the gather-free path of aligned slots.
    """
    return ell_apply(
        col_idx, values, x, offsets=offsets,
        block_m=block_m, block_k=block_k, interpret=interpret,
    )
