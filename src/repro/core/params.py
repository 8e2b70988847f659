"""Hardware parameter tables — the analogue of Ginkgo's per-backend config headers.

Ginkgo stores one parameterized kernel skeleton in ``common/`` and instantiates it
per backend with architecture-specific parameters (warp size 32 vs 64,
``launch_bounds``, ...).  Here the same role is played by :class:`HardwareParams`
(per-target machine model: tile geometry, subgroup size, memory budgets, roofline
constants) which both the Pallas kernels and the roofline analysis read.

The TPU v5e peaks are the published per-chip figures of Google Cloud's
"TPU v5e" documentation page: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s (200 GB/s) of chip-to-chip interconnect.  :func:`params_for_device`
maps a JAX device's ``device_kind`` to its entry; a kind that is not in
:data:`DEVICE_KINDS` is an error, never a guess.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping


@dataclasses.dataclass(frozen=True)
class HardwareParams:
    """Machine model for one execution target.

    The fields mirror what Ginkgo's backends configure per architecture:

    * ``subgroup_size``   — the cooperative-group granularity (paper: subwarp
      size; here: contiguous-lane segment width used by :mod:`repro.core.coop`).
    * ``warp_size``       — the full "warp" width inside which subgroups live
      (paper: 32 on CUDA / 64 on HIP; here: a lane segment of the 128-lane VPU).
    * ``lane_count`` / ``sublane_count`` — VREG tile geometry (8, 128) on TPU.
    * ``mxu_dim``         — systolic array dimension; matmul tiles should be
      multiples of this.
    * ``vmem_limit_bytes``— VMEM budget a kernel invocation may claim.
    """

    name: str
    kernel_space: str  # "reference" | "xla" | "pallas"
    interpret: bool = False  # Pallas interpret mode (CPU validation path)

    # Cooperative-group geometry (paper §4 "Cooperative groups").
    warp_size: int = 32
    subgroup_size: int = 8

    # VPU / MXU geometry.
    lane_count: int = 128
    sublane_count: int = 8
    mxu_dim: int = 128

    # Memory system.
    vmem_limit_bytes: int = 64 * 1024 * 1024
    hbm_bytes: int = 16 * 1024**3

    # Roofline constants (per chip / per link).
    peak_flops_bf16: float = 197e12
    peak_flops_f32: float = 49e12
    hbm_bandwidth: float = 819e9
    ici_bandwidth: float = 50e9

    def subgroups_per_warp(self) -> int:
        return self.warp_size // self.subgroup_size


# --- Target table ------------------------------------------------------------
# The analogue of Ginkgo's {cuda,hip,dpcpp}/config headers: one entry per
# supported execution target.  ``cpu_interpret`` runs the *pallas* kernel space
# in interpret mode — the validation backend (paper: "reference" executor is the
# correctness oracle; our reference space plays that role, and interpret mode
# lets us validate the hardware-native kernels without the hardware).

# Peaks: Google Cloud, "TPU v5e" (bf16 FLOP/s, HBM capacity and bandwidth,
# 1,600 Gbit/s ICI).  The page publishes no f32 peak; peak_flops_f32 is a
# quarter of bf16, an assumption.  VMEM: 96 of the core's 128 MiB, the most
# one kernel may ask for through its compiler parameters.
TPU_V5E = HardwareParams(
    name="tpu_v5e",
    kernel_space="pallas",
    interpret=False,
    warp_size=32,
    subgroup_size=8,
    vmem_limit_bytes=96 * 1024 * 1024,
    hbm_bytes=16 * 1024**3,
    peak_flops_bf16=197e12,
    peak_flops_f32=49e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=200e9,
)

TPU_V4 = HardwareParams(
    name="tpu_v4",
    kernel_space="pallas",
    interpret=False,
    warp_size=32,
    subgroup_size=8,
    vmem_limit_bytes=96 * 1024 * 1024,
    hbm_bytes=32 * 1024**3,
    peak_flops_bf16=275e12,
    peak_flops_f32=69e12,
    hbm_bandwidth=1228e9,
    ici_bandwidth=100e9,
)

CPU_INTERPRET = HardwareParams(
    name="cpu_interpret",
    kernel_space="pallas",
    interpret=True,
    warp_size=32,
    subgroup_size=8,
    # Generous "VMEM" so interpret-mode shapes never trip the budget check.
    vmem_limit_bytes=1024 * 1024 * 1024,
    hbm_bytes=32 * 1024**3,
    peak_flops_bf16=1e12,
    peak_flops_f32=5e11,
    hbm_bandwidth=50e9,
    ici_bandwidth=10e9,
)

CPU_XLA = HardwareParams(
    name="cpu_xla",
    kernel_space="xla",
    interpret=True,
    warp_size=32,
    subgroup_size=8,
    vmem_limit_bytes=1024 * 1024 * 1024,
    hbm_bytes=32 * 1024**3,
    peak_flops_bf16=1e12,
    peak_flops_f32=5e11,
    hbm_bandwidth=50e9,
    ici_bandwidth=10e9,
)

CPU_REFERENCE = dataclasses.replace(CPU_XLA, name="cpu_reference", kernel_space="reference")

TARGETS: Mapping[str, HardwareParams] = {
    p.name: p
    for p in (TPU_V5E, TPU_V4, CPU_INTERPRET, CPU_XLA, CPU_REFERENCE)
}


#: ``jax.Device.device_kind`` -> target.  Only kinds whose peaks are sourced
#: above; a v4 (kind "TPU v4") still has to be checked against its own page.
DEVICE_KINDS: Mapping[str, HardwareParams] = {
    "TPU v5 lite": TPU_V5E,
    "cpu": CPU_XLA,
}


def params_for_device(device) -> HardwareParams:
    """The hardware table for a JAX device, keyed by its ``device_kind``."""
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware table for {device.platform} device kind "
            f"{device.device_kind!r}; known kinds: {sorted(DEVICE_KINDS)}"
        ) from None


def get_target(name: str) -> HardwareParams:
    try:
        return TARGETS[name]
    except KeyError:
        raise KeyError(
            f"unknown hardware target {name!r}; known: {sorted(TARGETS)}"
        ) from None
