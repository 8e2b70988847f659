"""AMG smoke driver: hierarchy report + iteration-cut gate vs block-Jacobi.

Builds a 2D Poisson system from :mod:`repro.sparse.gallery`, sets up the
smoothed-aggregation :class:`repro.precond.amg.Multigrid` hierarchy, and runs
preconditioned CG twice — ``M="amg"`` against the ``M="block_jacobi"``
baseline.  The run reports the hierarchy (per-level rows/nnz, operator
complexity) and both convergence histories, then ends with a greppable
``AMG-GATE: PASS|FAIL`` line — the CI smoke gate — asserting that

* both solves converged,
* the AMG hierarchy actually coarsened (more than one level), and
* AMG cut CG iterations by at least ``--iter-cut`` (default 3x).

Usage:
    python -m repro.launch.amg_check --smoke
    python -m repro.launch.amg_check --n-side 128 --cycle w --iter-cut 5
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import make_executor, use_executor
from repro.launch.cache import use_compile_cache
from repro.observability import trace
from repro.precond import make_preconditioner
from repro.solvers.common import Stop
from repro.solvers.krylov import cg
from repro.sparse import csr_from_arrays
from repro.sparse.gallery import poisson_2d

__all__ = ["run_amg_check", "main"]


def run_amg_check(
    n_side: int,
    *,
    cycle: str = "v",
    theta: float = 0.08,
    iter_cut: float = 3.0,
    max_iters: int = 2000,
    tol: float = 1e-6,
    executor=None,
) -> bool:
    ex = executor or make_executor("xla")
    indptr, indices, values, shape = poisson_2d(n_side)
    A = csr_from_arrays(indptr, indices, values, shape)
    rng = np.random.default_rng(0)
    b = rng.normal(size=shape[0]).astype(np.float32)
    stop = Stop(max_iters=max_iters, reduction_factor=tol)

    print(f"amg_check: poisson_2d({n_side}) -> {shape[0]} rows, "
          f"{indices.size} nnz, cycle={cycle}, theta={theta:g}")

    t0 = time.perf_counter()
    M_amg = make_preconditioner(A, "amg", executor=ex,
                                cycle=cycle, theta=theta)
    setup_s = time.perf_counter() - t0
    rows = [int(L.A.shape[0]) for L in M_amg.levels]
    print(f"  hierarchy: {M_amg.num_levels} levels, rows {rows}, "
          f"operator complexity {M_amg.operator_complexity:.2f}, "
          f"setup {setup_s:.2f} s")

    M_bj = make_preconditioner(A, "block_jacobi", executor=ex)

    res_bj = cg(A, b, stop=stop, M=M_bj, executor=ex)
    res_amg = cg(A, b, stop=stop, M=M_amg, executor=ex)
    it_bj = int(res_bj.iterations)
    it_amg = int(res_amg.iterations)
    ratio = it_bj / max(it_amg, 1)
    print(f"  block_jacobi-cg: {it_bj} iters, "
          f"rnorm {float(res_bj.residual_norm):.3e}, "
          f"converged {bool(res_bj.converged)}")
    print(f"  amg-cg:          {it_amg} iters, "
          f"rnorm {float(res_amg.residual_norm):.3e}, "
          f"converged {bool(res_amg.converged)}")
    print(f"  iteration cut: {ratio:.1f}x (gate: >= {iter_cut:g}x)")

    ok = (
        bool(res_bj.converged)
        and bool(res_amg.converged)
        and M_amg.num_levels > 1
        and ratio >= iter_cut
    )
    print(f"AMG-GATE: {'PASS' if ok else 'FAIL'}")
    return ok


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run (64x64 grid, 3x gate)")
    ap.add_argument("--n-side", type=int, default=128,
                    help="Poisson grid side (rows = n_side^2)")
    ap.add_argument("--cycle", default="v", choices=("v", "w"))
    ap.add_argument("--theta", type=float, default=0.08,
                    help="strength-of-connection threshold")
    ap.add_argument("--iter-cut", type=float, default=3.0,
                    help="gate: AMG must cut CG iterations by this factor")
    ap.add_argument("--max-iters", type=int, default=2000)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--executor", default="xla")
    trace.add_cli_flag(ap)
    args = ap.parse_args(argv)
    trace.enable_from_args(args)

    n_side = 64 if args.smoke else args.n_side
    ex = make_executor(args.executor)
    with use_executor(ex):
        ok = run_amg_check(
            n_side,
            cycle=args.cycle,
            theta=args.theta,
            iter_cut=args.iter_cut,
            max_iters=args.max_iters,
            tol=args.tol,
            executor=ex,
        )
    if args.trace and trace.export():
        print(f"  trace -> {args.trace}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
