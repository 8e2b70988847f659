"""Registry binding: the fused Pallas axpy+norm serves operation ``axpy_norm``.

The reference/xla spaces live in :mod:`repro.sparse.ops` (unfused composition,
bitwise identical to separate ``blas_axpy`` + ``blas_dot`` calls — the
fallback-parity contract).  This module binds the hardware-native fused
skeleton; batched ``(nb, n)`` operands fall through to the xla formulation
(the pallas kernel streams one vector — the batched solvers share the same
*operation* so the fusion fix lands in both paths, per-space coverage follows
the family's single-vector kernel).
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.axpy_norm import kernel as axpy_kernel


def _constrain(hw, shapes, block):
    tile = hw.sublane_count * hw.lane_count
    bn = max(int(block["block_n"]), tile)
    bn -= bn % tile
    return {"block_n": bn}


AXPY_NORM_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="axpy_norm",
        params=("block_n",),
        seed=lambda hw: {"block_n": hw.lane_count * hw.sublane_count * 32},
        vmem_bytes=lambda shapes, block: axpy_kernel.vmem_bytes(
            block["block_n"], shapes.get("itemsize", 4)
        ),
        constrain=_constrain,
        floors={"block_n": 1024},
    )
)


def _axpy_norm_skeleton(ex, alpha, x, y, *, variant: str):
    if x.ndim != 1:
        # batched rows: delegate to the shared vectorized formulation
        from repro.sparse.ops import _axpy_norm_xla

        return _axpy_norm_xla(ex, alpha, x, y)
    cfg = ex.launch_config(
        "axpy_norm", {"n": x.shape[0], "itemsize": x.dtype.itemsize}
    )
    return axpy_kernel.axpy_norm(
        alpha, x, y, block_n=cfg["block_n"], interpret=ex.interpret
    )


registry.instantiate_common(
    "axpy_norm", _axpy_norm_skeleton, {"pallas": dict(variant="pallas")}
)
