"""Registry binding: the Pallas SELL-P SpMV serves operation ``spmv_sellp``.

The reference/xla spaces live in :mod:`repro.sparse.ops`; this module binds the
hardware-native skeleton.  ``block_cols`` comes from the launch-configuration
table, constrained to divide the format's ``stride_factor`` so slice offsets
always land on block boundaries.
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.spmv_sellp.kernel import spmv_sellp as spmv_sellp_pallas
from repro.sparse.formats import Sellp


def _vmem_bytes(shapes, block) -> int:
    # cols (int32) + values tiles of (block_cols, C), x VMEM-resident, (1, C) out
    bc = block["block_cols"]
    C = shapes.get("slice_size", 8)
    n = shapes.get("n", 0)
    itemsize = shapes.get("itemsize", 4)
    return bc * C * (itemsize + 4) + n * itemsize + C * itemsize


def _constrain(hw, shapes, block):
    bc = max(int(block["block_cols"]), 1)
    sf = int(shapes.get("stride_factor", bc))
    bc = min(bc, sf)
    while sf % bc:  # slice offsets are stride_factor multiples; stay divisible
        bc -= 1
    return {"block_cols": bc}


SELLP_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="spmv_sellp",
        params=("block_cols",),
        seed=lambda hw: {"block_cols": hw.sublane_count},
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"block_cols": 1},
    )
)


def _spmv_sellp_skeleton(ex, A: Sellp, x, *, variant: str):
    if x.ndim != 1:
        raise NotImplementedError("pallas SELL-P spmv is single-rhs")
    cfg = ex.launch_config(
        "spmv_sellp",
        {
            "m": A.shape[0],
            "n": x.shape[0],
            "slice_size": A.slice_size,
            "stride_factor": A.stride_factor,
            "itemsize": x.dtype.itemsize,
        },
    )
    if not cfg.fits_vmem:
        from repro.sparse.ops import _spmv_sellp_xla

        return _spmv_sellp_xla(ex, A, x)
    return spmv_sellp_pallas(
        A.col_idx,
        A.values,
        A.slice_sets,
        x,
        m=A.shape[0],
        slice_size=A.slice_size,
        block_cols=cfg["block_cols"],
        max_slice_cols=A.max_slice_cols,
        interpret=ex.interpret,
    )


registry.instantiate_common(
    "spmv_sellp", _spmv_sellp_skeleton, {"pallas": dict(variant="pallas")}
)
