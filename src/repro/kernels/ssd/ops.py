"""Registry bindings for the Mamba2 SSD scan (operation ``nn_ssd_scan``).

One skeleton, three spaces; chunk length comes from the launch-configuration
table (the (L, L) decay/score matrices and the carried (N, P) state set the
VMEM working set).
"""

from __future__ import annotations

from repro.core import registry, tuning
from repro.kernels.ssd.kernel import ssd_scan
from repro.kernels.ssd.ref import ssd_ref


def _vmem_bytes(shapes, block) -> int:
    # Ldec + CB/G (L, L) f32 matrices + x/B/C chunk tiles + (N, P) state scratch
    L = block["chunk"]
    N = shapes.get("N", 64)
    P = shapes.get("P", 64)
    return 4 * (2 * L * L + L * (2 * N + 2 * P) + N * P)


def _constrain(hw, shapes, block):
    return {"chunk": tuning.prev_pow2(max(int(block["chunk"]), 8))}


SSD_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="nn_ssd_scan",
        params=("chunk",),
        seed=lambda hw: {"chunk": max(hw.sublane_count * 8, 32)},
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"chunk": 8},
    )
)


def _ssd_skeleton(ex, x, dt, A, B_mat, C, *, variant: str):
    if variant == "reference":
        return ssd_ref(x, dt, A, B_mat, C)
    cfg = ex.launch_config(
        "nn_ssd_scan",
        {
            "S": x.shape[1],
            "N": B_mat.shape[-1],
            "P": x.shape[-1],
            "itemsize": x.dtype.itemsize,
        },
    )
    if variant == "xla":
        # chunked batched-einsum formulation (xla.py) — the optimized portable path
        from repro.kernels.ssd.xla import ssd_chunked_xla

        return ssd_chunked_xla(x, dt, A, B_mat, C, chunk=cfg["chunk"])
    return ssd_scan(x, dt, A, B_mat, C, chunk=cfg["chunk"], interpret=ex.interpret)


ssd_op = registry.instantiate_common(
    "nn_ssd_scan",
    _ssd_skeleton,
    {
        "reference": dict(variant="reference"),
        "xla": dict(variant="xla"),
        "pallas": dict(variant="pallas"),
    },
)
ssd_op.__doc__ = "Mamba2 SSD scan -> (y, final_state)"
