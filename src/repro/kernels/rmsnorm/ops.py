"""Registry bindings for fused RMSNorm (operation ``nn_rmsnorm``).

One skeleton, three kernel spaces; the Pallas instantiation takes its row-tile
from the launch-configuration table (sublane-aligned, VMEM-checked) instead of
a hard-coded ``block_rows``.
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.rmsnorm.kernel import rmsnorm as rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _vmem_bytes(shapes, block) -> int:
    # x tile in + out (native dtype) + the f32 compute copy + the weight row
    rows = block["block_rows"]
    d = shapes.get("d", 4096)
    itemsize = shapes.get("itemsize", 4)
    return rows * d * (2 * itemsize + 4) + d * itemsize


def _constrain(hw, shapes, block):
    rows = max(int(block["block_rows"]), hw.sublane_count)
    rows -= rows % hw.sublane_count  # keep tiles VREG-aligned (8 sublanes)
    return {"block_rows": rows}


RMSNORM_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="nn_rmsnorm",
        params=("block_rows",),
        seed=lambda hw: {"block_rows": hw.sublane_count * 32},
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"block_rows": 8},
    )
)


def _rmsnorm_skeleton(ex, x, weight, eps: float = 1e-6, *, variant: str):
    if variant != "pallas":
        # same math; XLA fuses this well — the Pallas win is explicit tiling
        return rmsnorm_ref(x, weight, eps)
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    cfg = ex.launch_config(
        "nn_rmsnorm",
        {"rows": rows, "d": x.shape[-1], "itemsize": x.dtype.itemsize},
    )
    return rmsnorm_pallas(
        x, weight, eps=eps, block_rows=cfg["block_rows"], interpret=ex.interpret
    )


rmsnorm_op = registry.instantiate_common(
    "nn_rmsnorm",
    _rmsnorm_skeleton,
    {
        "reference": dict(variant="reference"),
        "xla": dict(variant="xla"),
        "pallas": dict(variant="pallas"),
    },
)
rmsnorm_op.__doc__ = "fused RMSNorm over the last axis"
