"""Registry bindings for the RWKV6 WKV scan (operation ``nn_rwkv6_scan``).

One skeleton, three spaces; both the optimized XLA formulation and the Pallas
kernel take their chunk length from the launch-configuration table — the
(L, L, K) stability tensor is the VMEM driver, so the chunk must shrink on
small-VMEM targets rather than overflow.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import registry, tuning
from repro.kernels.rwkv6.kernel import rwkv6_scan_log
from repro.kernels.rwkv6.ref import rwkv6_ref


def _vmem_bytes(shapes, block) -> int:
    # (L, L, K) ratio tensor + the (L, L) G matrix + r/k/v/logw chunk tiles
    # + the carried (K, V) state scratch, all f32
    L = block["chunk"]
    K = shapes.get("K", 64)
    V = shapes.get("V", K)
    return 4 * (L * L * K + L * L + L * (3 * K + V) + K * V)


def _constrain(hw, shapes, block):
    return {"chunk": tuning.prev_pow2(max(int(block["chunk"]), 8))}


RWKV6_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="nn_rwkv6_scan",
        params=("chunk",),
        seed=lambda hw: {"chunk": max(hw.sublane_count * 4, 16)},
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"chunk": 8},
    )
)


def _rwkv6_skeleton(ex, r, k, v, logw, u, *, variant: str):
    if variant == "reference":
        return rwkv6_ref(r, k, v, jnp.exp(logw.astype(jnp.float32)), u)
    cfg = ex.launch_config(
        "nn_rwkv6_scan",
        {
            "S": r.shape[1],
            "K": r.shape[-1],
            "V": v.shape[-1],
            "itemsize": r.dtype.itemsize,
        },
    )
    if variant == "xla":
        # chunked batched-einsum formulation (xla.py) — the optimized portable path
        from repro.kernels.rwkv6.xla import rwkv6_chunked_xla

        return rwkv6_chunked_xla(r, k, v, logw, u, chunk=cfg["chunk"])
    return rwkv6_scan_log(r, k, v, logw, u, chunk=cfg["chunk"], interpret=ex.interpret)


rwkv6_op = registry.instantiate_common(
    "nn_rwkv6_scan",
    _rwkv6_skeleton,
    {
        "reference": dict(variant="reference"),
        "xla": dict(variant="xla"),
        "pallas": dict(variant="pallas"),
    },
)
rwkv6_op.__doc__ = "RWKV6 WKV scan (log-space decay) -> (y, final_state)"
