"""Lane-dense 2-D views of vectors — the tiling every solve-path kernel streams.

The TPU's vector unit works on (sublanes, 128 lanes) tiles, and a Pallas
block must tile that way: its last dimension a multiple of 128 (or the whole
axis) and its second-to-last a multiple of the dtype's sublane count (or the
whole axis).  A 1-D vector of ``n`` entries is therefore padded to
``rows * 128`` and viewed as ``(rows, 128)``; a kernel streams it in blocks
of ``block_rows`` such rows.  Arrays with a small leading axis (the ``k``
slots of an ELL row, the ``bs`` rows of a Jacobi block) put that axis in
front — ``(k, rows, 128)`` — where Pallas places no alignment rule on it.

Every kernel on the solve path asks the compiler for the VMEM its
:class:`~repro.core.tuning.TuningSpec` working-set model counts (see
:func:`compiler_params`), so the budget the tuning tables check is the one
the compiled kernel gets.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "LANES",
    "row_tiling",
    "to_rows",
    "from_rows",
    "compiler_params",
    "MOSAIC_SCRATCH_BYTES",
]

LANES = 128

#: VMEM the kernel compiler keeps for its own temporaries (spilled vector
#: values, reduction trees) on top of the pipelined blocks.
MOSAIC_SCRATCH_BYTES = 2 * 1024 * 1024


def _sublanes(*dtypes) -> int:
    """Sublane tile height for the narrowest of ``dtypes`` (8 rows at 32 bit,
    16 at 16 bit)."""
    itemsize = min(jnp.dtype(d).itemsize for d in dtypes)
    return 8 * max(1, 4 // itemsize)


def row_tiling(n: int, block: int, *dtypes) -> Tuple[int, int]:
    """``(rows, block_rows)`` tiling ``n`` entries in blocks of ~``block``.

    ``block_rows`` is a multiple of the sublane tile; when one block covers
    the whole vector it is the whole (unpadded) row count instead, which the
    block-shape rule also allows.  ``rows`` is padded to a multiple of
    ``block_rows``.
    """
    sub = _sublanes(*dtypes)
    rows = max(pl.cdiv(n, LANES), 1)
    block_rows = max(sub, (block // LANES) // sub * sub)
    if block_rows >= rows:
        return rows, rows
    return pl.cdiv(rows, block_rows) * block_rows, block_rows


def to_rows(v: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the last axis to ``rows * 128`` and view it as ``(rows, 128)``."""
    pad = rows * LANES - v.shape[-1]
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v.reshape(v.shape[:-1] + (rows, LANES))


def from_rows(v: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`to_rows`: the first ``n`` entries of the last axis."""
    return v.reshape(v.shape[:-2] + (-1,))[..., :n]


def compiler_params(vmem_bytes: int):
    """Mosaic parameters asking for ``vmem_bytes`` of scoped VMEM.

    Grid axes keep the default in-order iteration, which the accumulating
    kernels (revisited output blocks) rely on.
    """
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_bytes))
