"""Fused ELL SpMV + dot Pallas TPU kernel — apply-with-reduction.

The arXiv:2011.08879 fusion: Krylov iterations follow every SpMV with a dot
product against the same vectors (``p·Ap`` in CG, ``r̂·v`` in BiCGSTAB), and
launching the dot separately re-streams ``y`` through HBM.  The ELL SpMV
kernel (:mod:`repro.kernels.spmv_ell.kernel`) emits the partial reduction in
the same pass when given ``w``; this module is its fused entry point.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.spmv_ell.kernel import ell_apply


@functools.partial(
    jax.jit, static_argnames=("offsets", "block_m", "block_k", "interpret")
)
def spmv_dot_ell(
    col_idx: jax.Array,
    values: jax.Array,
    x: jax.Array,
    w: jax.Array,
    *,
    offsets: Optional[Tuple[int, ...]] = None,
    block_m: int = 8192,
    block_k: int = 32,
    interpret: bool = False,
):
    """(y, w·y) = (A @ x, dot) for ELL-format A of shape (m, k), one pass."""
    return ell_apply(
        col_idx, values, x, w, offsets=offsets,
        block_m=block_m, block_k=block_k, interpret=interpret,
    )
