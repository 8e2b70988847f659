"""Distributed-solve driver: one Krylov solve sharded across the devices.

The distributed twin of ``repro.launch.batch_solve``: build a gallery system
(:func:`build_system`), row-partition it over the available devices
(:class:`repro.distributed.Partition` + :class:`DistCsr`/:class:`DistEll`),
and hand it to the UNCHANGED solver entry point — ``krylov.cg`` notices the
distributed operand and runs the whole iteration under ``shard_map`` (local
SpMV + halo exchange, psum reductions).  The run is checked against the
single-device solve: same iteration count (±1), matching solution.

Usage:
    python -m repro.launch.dist_solve --smoke
    python -m repro.launch.dist_solve --n-side 64 --solver cg --format ell \
        --precond jacobi --shards 4 --executor pallas

On a CPU host, force virtual devices first:
    XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import sparse
from repro.core import make_executor, use_executor
from repro.distributed import DistCsr, DistEll, Partition
from repro.launch.cache import use_compile_cache
from repro.observability import trace
from repro.solvers import krylov
from repro.solvers.common import Stop
from repro.sparse import gallery

__all__ = ["build_system", "csr_matvec_f64", "main"]


def csr_matvec_f64(host_csr, x: np.ndarray) -> np.ndarray:
    """``A @ x`` in float64 on the host from gallery CSR arrays."""
    indptr, indices, values, (m, _) = host_csr
    rows = np.repeat(np.arange(m), np.diff(indptr))
    prod = values.astype(np.float64) * np.asarray(x, np.float64)[indices]
    return np.bincount(rows, weights=prod, minlength=m)


def build_system(n_side: int, *, nonsym: bool = False, seed: int = 0):
    """The gallery system the solve drivers and their tests share.

    SPD: the 7-point 3-D Poisson stencil on an ``n_side``³ grid (HPCG's
    problem class; ``n_side=128`` is 2,097,152 rows).  ``nonsym``: upwind
    convection-diffusion on an ``n_side``² grid, for the nonsymmetric
    solvers.  Returns ``(host_csr, xstar, b)`` with ``xstar`` drawn from
    ``seed`` and ``b = A @ xstar`` (float32).
    """
    if nonsym:
        host = gallery.convection_diffusion_2d(n_side)
    else:
        host = gallery.poisson_3d(n_side)
    n = host[3][0]
    xstar = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    return host, xstar, csr_matvec_f64(host, xstar).astype(np.float32)


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small end-to-end run with parity check")
    ap.add_argument("--n-side", type=int, default=16,
                    help="grid side (3-D Poisson: n_side^3 rows)")
    ap.add_argument("--solver", default="cg",
                    choices=("cg", "fcg", "bicgstab", "cgs", "gmres"))
    ap.add_argument("--format", default="csr", choices=("csr", "ell"),
                    dest="fmt")
    ap.add_argument("--precond", default="none",
                    choices=("none", "jacobi", "block_jacobi"))
    ap.add_argument("--shards", type=int, default=0,
                    help="parts (default: all devices)")
    ap.add_argument("--executor", default="xla",
                    help="executor kind or hardware target name")
    ap.add_argument("--max-iters", type=int, default=500)
    ap.add_argument("--tol", type=float, default=1e-6)
    trace.add_cli_flag(ap)
    args = ap.parse_args(argv)
    trace.enable_from_args(args)

    n_side = 6 if args.smoke else args.n_side
    ndev = len(jax.devices())
    shards = args.shards or ndev
    if shards > ndev:
        print(f"dist_solve: clamping --shards {shards} to {ndev} devices")
        shards = ndev

    nonsym = args.solver in ("bicgstab", "cgs", "gmres")
    host, xstar, b = build_system(n_side, nonsym=nonsym)
    n = host[3][0]
    A = sparse.csr_from_arrays(*host)
    if args.fmt == "ell":
        A = sparse.ell_from_csr_host(*host)
    part = Partition.uniform(n, shards)
    dist_cls = DistCsr if args.fmt == "csr" else DistEll
    Ad = dist_cls.from_matrix(A, part)
    print(
        f"dist_solve: n={n} {args.fmt} nnz={Ad.nnz} over {shards} shards "
        f"(sizes {min(part.part_sizes)}..{max(part.part_sizes)}, halo cols "
        f"{min(Ad.num_halo_cols)}..{max(Ad.num_halo_cols)}), "
        f"{args.solver}/{args.precond}, executor={args.executor}"
    )

    stop = Stop(max_iters=args.max_iters, reduction_factor=args.tol)
    fn = getattr(krylov, args.solver)
    M = None if args.precond == "none" else args.precond
    ex = make_executor(args.executor)
    with use_executor(ex):
        single = fn(A, jnp.asarray(b), stop=stop, M=M, executor=ex)
        t0 = time.perf_counter()
        res = fn(Ad, jnp.asarray(b), stop=stop, M=M, executor=ex)
        jax.block_until_ready(res.x)
        wall = time.perf_counter() - t0

    err = np.abs(np.asarray(res.x) - xstar).max()
    diff = np.abs(np.asarray(res.x) - np.asarray(single.x)).max()
    iters_d, iters_s = int(res.iterations), int(single.iterations)
    print(
        f"  distributed: {iters_d} iters, residual {float(res.residual_norm):.3e}, "
        f"{wall*1e3:.1f} ms   single-device: {iters_s} iters"
    )
    print(f"  error vs known solution = {err:.3e}, vs single-device = {diff:.3e}")

    # block-Jacobi is block-LOCAL per shard: when shard boundaries split a
    # block, the distributed preconditioner differs from the single-device
    # one and iteration counts legitimately diverge — only the solutions
    # must still agree
    same_preconditioner = args.precond != "block_jacobi" or shards == 1
    iters_ok = abs(iters_d - iters_s) <= 1 if same_preconditioner else True
    ok = bool(res.converged) and iters_ok and diff < 1e-3
    if not ok:
        print("dist_solve: PARITY FAILURE")
    if args.trace and trace.export():
        print(f"  trace -> {args.trace}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
