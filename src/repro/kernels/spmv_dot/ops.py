"""Registry binding: the fused Pallas ELL SpMV+dot serves ``spmv_dot_ell``.

The reference/xla spaces live in :mod:`repro.sparse.ops` (they compute the
unfused SpMV followed by a vdot — bitwise identical to the unfused path, which
is what the fallback-parity tests pin).  This module binds the hardware-native
fused skeleton; its tile geometry resolves through the launch-configuration
table with the same alignment rules as the plain ELL SpMV, and like it the
binding never switches to another space.

``spmv_dot_csr`` has no pallas space — mirroring the base ``spmv_csr``
coverage (the repo carries no hand-written CSR SpMV kernel); pallas executors
reach its xla formulation through the permissive fallback chain, and the
optional-op capability probe (:func:`repro.sparse.ops.has_fused_ops`) still
answers True because a serving space exists.
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.spmv_dot.kernel import spmv_dot_ell as spmv_dot_ell_pallas
from repro.kernels.spmv_ell.ops import (
    ell_constrain,
    ell_seed,
    ell_vmem_bytes,
)
from repro.sparse.formats import Ell

SPMV_DOT_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="spmv_dot",
        params=("block_m", "block_k"),
        seed=ell_seed,
        vmem_bytes=lambda shapes, block: ell_vmem_bytes(shapes, block, dot=True),
        constrain=ell_constrain,
        floors={"block_m": 1024, "block_k": 1},
    )
)


def _spmv_dot_ell_skeleton(ex, A: Ell, x, w, *, variant: str):
    if x.ndim != 1:
        raise NotImplementedError("pallas fused ELL spmv_dot is single-rhs")
    cfg = ex.launch_config(
        "spmv_dot",
        {
            "m": A.values.shape[0],
            "k": A.values.shape[1],
            "n": x.shape[0],
            "itemsize": x.dtype.itemsize,
        },
    )
    return spmv_dot_ell_pallas(
        A.col_idx,
        A.values,
        x,
        w,
        offsets=A.offsets,
        block_m=cfg["block_m"],
        block_k=cfg["block_k"],
        interpret=ex.interpret,
    )


registry.instantiate_common(
    "spmv_dot_ell", _spmv_dot_ell_skeleton, {"pallas": dict(variant="pallas")}
)
