"""Distributed SpMV benchmark: per-shard achieved bandwidth vs the bound.

For each suite matrix, row-partition over the available devices and time the
halo-exchange SpMV (local block + gathered-column remote block under
``shard_map``).  Reported per matrix:

* achieved GFLOP/s (2 * true nnz / t) and the fraction of the single-device
  bandwidth-induced bound (``spmv_bandwidth_bound`` over the underlying
  format's own byte accounting) — the paper's performance-portability metric,
  now per shard;
* per-shard achieved bandwidth GB/s: the bytes one shard actually streams
  (its slice of the distributed operator + the gathered x + its y chunk)
  over the wall time, next to the machine bandwidth the bound assumes.

Interpret-mode CPU timings are not TPU-indicative; the point in CI (--smoke)
is that the sharded path runs end to end and the accounting adds up.
"""

from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (
    banded,
    emit,
    matrix_suite,
    spmv_bandwidth_bound,
    stencil_2d,
    time_fn,
    tridiag,
)
from repro import sparse
from repro.core import XlaExecutor, use_executor
from repro.distributed import DistCsr, DistEll, Partition
from repro.solvers import krylov
from repro.solvers.common import Stop

DIST_BUILD = {
    "csr": (sparse.csr_from_dense, DistCsr),
    "ell": (sparse.ell_from_dense, DistEll),
}


def shard_bytes(Ad, x_itemsize: int) -> float:
    """Bytes ONE shard streams per apply: its slice of the operator, the
    all-gathered x (padded global), and its padded y chunk."""
    P = Ad.partition.num_parts
    Lmax = Ad.partition.max_part_size
    return Ad.memory_bytes / P + (P * Lmax + Lmax) * x_itemsize


def run(bandwidth: float, smoke: bool = False) -> None:
    ndev = len(jax.devices())
    suite = (
        # compact smoke suite: one matrix per structural regime, CI-sized
        {
            "stencil2d_16": stencil_2d(16),
            "tridiag_512": tridiag(512),
            "banded_256": banded(256),
        }
        if smoke
        else matrix_suite()
    )
    rng = np.random.default_rng(7)
    ex = XlaExecutor()

    with use_executor(ex):
        for mat_name, a in suite.items():
            n = a.shape[0]
            nnz = int((a != 0).sum())
            parts = min(ndev, n)
            part = Partition.uniform(n, parts)
            x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
            for fmt, (build, dist_cls) in DIST_BUILD.items():
                A = build(a)
                Ad = dist_cls.from_matrix(A, part)
                fn = jax.jit(lambda x, Ad=Ad: Ad.apply(x, executor=ex))
                t = time_fn(fn, x)
                gflops = 2 * nnz / t / 1e9
                bound = spmv_bandwidth_bound(A, bandwidth, nnz) / 1e9
                shard_gbs = shard_bytes(Ad, x.dtype.itemsize) / t / 1e9
                emit(
                    f"dist_spmv_{fmt}_{mat_name}_{parts}shard",
                    t * 1e6,
                    f"{gflops:.3f}GFLOP/s_frac{gflops/bound:.2f}"
                    f"_shardbw{shard_gbs:.3g}GB/s_of{bandwidth/1e9:.0f}GB/s",
                )

        if smoke:
            # end-to-end sharded CG must actually converge in CI
            from repro.launch.dist_solve import build_system

            host, xstar, b = build_system(6)
            n = host[3][0]
            Ad = DistCsr.from_matrix(
                sparse.csr_from_arrays(*host), Partition.uniform(n, min(ndev, 8))
            )
            res = krylov.cg(
                Ad, jnp.asarray(b), stop=Stop(max_iters=500), executor=ex
            )
            assert bool(res.converged), "distributed CG smoke did not converge"
            err = float(np.abs(np.asarray(res.x) - xstar).max())
            assert err < 1e-3, f"distributed CG smoke error {err}"
            print(f"# dist cg smoke: {int(res.iterations)} iters, err {err:.2e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small suite + CG check")
    ap.add_argument(
        "--bandwidth", type=float, default=None,
        help="machine bandwidth B/s for the bound (default: hw table)",
    )
    args = ap.parse_args(argv)
    bw = args.bandwidth or XlaExecutor().hw.hbm_bandwidth
    print(f"# distributed spmv over {len(jax.devices())} device(s), "
          f"bound bandwidth {bw/1e9:.0f} GB/s")
    run(bw, smoke=args.smoke)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
