"""Registry binding: the Pallas batched ELL SpMV serves ``spmv_batch_ell``.

The reference/xla spaces live in :mod:`repro.batch.ops`; this module binds the
hardware-native skeleton.  Tile geometry resolves through the executor's
launch-configuration table (new per-target ``spmv_batch_ell`` entries ride the
same table override / HardwareParams-seed chain as every other kernel family
— no hard-coded block sizes).
"""

from __future__ import annotations

from repro.batch.formats import BatchEll
from repro.core import registry, tuning
from repro.kernels.spmv_batch_ell.kernel import (
    spmv_batch_ell as spmv_batch_ell_pallas,
)


def _vmem_bytes(shapes, block) -> int:
    # shared col tile (int32) + one system's value tile, that system's x row
    # fully VMEM-resident, one output column — the batch axis streams, so it
    # adds no per-step working set.
    bm, bk = block["block_m"], block["block_k"]
    n = shapes.get("n", 0)
    itemsize = shapes.get("itemsize", 4)
    return bm * bk * (itemsize + 4) + n * itemsize + bm * itemsize


def _constrain(hw, shapes, block):
    bm = max(int(block["block_m"]), hw.sublane_count)
    bm -= bm % hw.sublane_count
    # power-of-two lanes keep the coop butterfly legal
    bk = tuning.prev_pow2(max(int(block["block_k"]), 8))
    return {"block_m": bm, "block_k": bk}


BATCH_ELL_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="spmv_batch_ell",
        params=("block_m", "block_k"),
        seed=lambda hw: {
            # batched systems are small (Ginkgo: O(100)-O(10k) rows each), so
            # seed a tighter row tile than the single-system kernel and let
            # the k axis take the full lane width
            "block_m": max(hw.sublane_count * 16, 8),
            "block_k": hw.lane_count,
        },
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"block_m": 8, "block_k": 8},
    )
)


def _spmv_batch_ell_skeleton(ex, A: BatchEll, X, *, variant: str):
    if X.ndim != 2:
        raise NotImplementedError("pallas batched ELL spmv wants (nb, n) rhs")
    cfg = ex.launch_config(
        "spmv_batch_ell",
        {
            "nb": A.values.shape[0],
            "m": A.values.shape[1],
            "k": A.values.shape[2],
            "n": X.shape[1],
            "itemsize": X.dtype.itemsize,
        },
    )
    if not cfg.fits_vmem:
        # one system's x row does not fit the residency strategy here — fall
        # through to the portable batched kernel (executor picks the variant
        # suited to the problem granularity).
        from repro.batch.ops import _spmv_batch_ell_xla

        return _spmv_batch_ell_xla(ex, A, X)
    return spmv_batch_ell_pallas(
        A.col_idx,
        A.values,
        X,
        block_m=cfg["block_m"],
        block_k=cfg["block_k"],
        use_coop=True,
        interpret=ex.interpret,
    )


registry.instantiate_common(
    "spmv_batch_ell", _spmv_batch_ell_skeleton, {"pallas": dict(variant="pallas")}
)
