"""Registry binding: the block-Jacobi apply serves ``block_jacobi_apply``.

Three kernel spaces:

* ``reference`` — the sequential-semantics einsum oracle (:mod:`.ref`);
* ``xla``       — the same formulation handed to the compiler (small batched
  matvecs fuse well; Ginkgo's OpenMP slot);
* ``pallas``    — the hardware-native tile kernel (:mod:`.kernel`), its block
  batch tile resolved through ``Executor.launch_config`` with the registered
  ``block_jacobi`` :class:`~repro.core.tuning.TuningSpec` — no hard-coded
  geometry, per-target entries ride the same table override /
  HardwareParams-seed chain as every other kernel family.
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.block_jacobi import kernel as bj_kernel
from repro.kernels.block_jacobi.ref import block_jacobi_apply_ref


def _constrain(hw, shapes, block):
    # whole (sublane x lane) tiles of blocks: the kernel is block-minor
    tile = hw.sublane_count * hw.lane_count
    bnb = max(int(block["block_nb"]), tile)
    bnb -= bnb % tile
    return {"block_nb": bnb}


BLOCK_JACOBI_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="block_jacobi",
        params=("block_nb",),
        seed=lambda hw: {"block_nb": hw.sublane_count * hw.lane_count * 4},
        vmem_bytes=lambda shapes, block: bj_kernel.vmem_bytes(
            block["block_nb"], shapes.get("bs", 8), shapes.get("itemsize", 4)
        ),
        constrain=_constrain,
        floors={"block_nb": 1024},
    )
)


def _block_jacobi_skeleton(ex, inv_blocks, vp, *, variant: str):
    if variant != "pallas":
        return block_jacobi_apply_ref(inv_blocks, vp)
    cfg = ex.launch_config(
        "block_jacobi",
        {
            "nb": inv_blocks.shape[0],
            "bs": inv_blocks.shape[1],
            "itemsize": inv_blocks.dtype.itemsize,
        },
    )
    return bj_kernel.block_jacobi_apply(
        inv_blocks, vp, block_nb=cfg["block_nb"], interpret=ex.interpret
    )


registry.instantiate_common(
    "block_jacobi_apply",
    _block_jacobi_skeleton,
    {
        "reference": dict(variant="reference"),
        "xla": dict(variant="xla"),
        "pallas": dict(variant="pallas"),
    },
)
