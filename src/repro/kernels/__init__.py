"""repro.kernels — hardware-native Pallas TPU kernels (the CUDA/HIP slot).

Importing this package registers every Pallas implementation in the operation
registry (the analogue of linking Ginkgo's device backends); ``import repro``
imports it, so every executor sees the same kernel spaces.

Layout: one directory per hot-spot, each with
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  ops.py    — registry bindings / jit wrappers
  ref.py    — the pure-jnp oracle the kernel is validated against
"""

import repro.kernels.axpy_norm.ops  # noqa: F401
import repro.kernels.block_jacobi.ops  # noqa: F401
import repro.kernels.flash_attention.ops  # noqa: F401
import repro.kernels.rmsnorm.ops  # noqa: F401
import repro.kernels.rwkv6.ops  # noqa: F401
import repro.kernels.spmv_batch_ell.ops  # noqa: F401
import repro.kernels.spmv_dot.ops  # noqa: F401
import repro.kernels.spmv_ell.ops  # noqa: F401
import repro.kernels.spmv_sellp.ops  # noqa: F401
import repro.kernels.ssd.ops  # noqa: F401

from repro.kernels.axpy_norm.kernel import axpy_norm
from repro.kernels.block_jacobi.kernel import block_jacobi_apply
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.kernels.rwkv6.kernel import rwkv6_scan, rwkv6_scan_log
from repro.kernels.spmv_batch_ell.kernel import spmv_batch_ell
from repro.kernels.spmv_dot.kernel import spmv_dot_ell
from repro.kernels.spmv_ell.kernel import spmv_ell
from repro.kernels.spmv_sellp.kernel import spmv_sellp
from repro.kernels.ssd.kernel import ssd_scan

__all__ = [
    "axpy_norm",
    "block_jacobi_apply",
    "flash_attention",
    "rmsnorm",
    "rwkv6_scan",
    "rwkv6_scan_log",
    "spmv_batch_ell",
    "spmv_dot_ell",
    "spmv_ell",
    "spmv_sellp",
    "ssd_scan",
]
