"""Registry binding: the Pallas ELL SpMV serves operation ``spmv_ell``.

The reference/xla spaces live in :mod:`repro.sparse.ops`; this module binds the
hardware-native skeleton, whose (block_m, block_k) tile comes from the
launch-configuration table.  The kernel keeps no vector resident in VMEM, so
every shape runs the Pallas kernel — there is no size at which the binding
switches to another space.
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.spmv_ell import kernel as ell_kernel
from repro.sparse.formats import Ell


def ell_constrain(hw, shapes, block):
    """Row blocks of whole (sublane x lane) tiles; at least one ELL slot."""
    tile = hw.sublane_count * hw.lane_count
    bm = max(int(block["block_m"]), tile)
    bm -= bm % tile
    return {"block_m": bm, "block_k": max(int(block["block_k"]), 1)}


def ell_seed(hw):
    return {
        "block_m": hw.sublane_count * hw.lane_count * 8,
        "block_k": hw.sublane_count * 4,
    }


def ell_vmem_bytes(shapes, block, *, dot: bool) -> int:
    bk = min(block["block_k"], shapes.get("k", block["block_k"]))
    return ell_kernel.vmem_bytes(
        block["block_m"], bk, shapes.get("itemsize", 4), dot=dot
    )


ELL_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="spmv_ell",
        params=("block_m", "block_k"),
        seed=ell_seed,
        vmem_bytes=lambda shapes, block: ell_vmem_bytes(shapes, block, dot=False),
        constrain=ell_constrain,
        floors={"block_m": 1024, "block_k": 1},
    )
)


def _spmv_ell_skeleton(ex, A: Ell, x, *, variant: str):
    if x.ndim != 1:
        raise NotImplementedError("pallas ELL spmv is single-rhs")
    cfg = ex.launch_config(
        "spmv_ell",
        {
            "m": A.values.shape[0],
            "k": A.values.shape[1],
            "n": x.shape[0],
            "itemsize": x.dtype.itemsize,
        },
    )
    return ell_kernel.spmv_ell(
        A.col_idx,
        A.values,
        x,
        offsets=A.offsets,
        block_m=cfg["block_m"],
        block_k=cfg["block_k"],
        interpret=ex.interpret,
    )


registry.instantiate_common(
    "spmv_ell", _spmv_ell_skeleton, {"pallas": dict(variant="pallas")}
)
