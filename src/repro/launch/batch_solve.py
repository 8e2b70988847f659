"""Sharded batched-solve driver: one launch, thousands of systems, N devices.

The batch axis is embarrassingly parallel — every system is independent — so
the driver shards it across the mesh's data axis with the existing mesh
utilities: the shared index structure (``col_idx`` / ``indptr``) replicates,
the value tensor and right-hand sides split on their leading batch axis, and
the masked batched solver runs unchanged under ``jit`` (GSPMD keeps every
per-system reduction local to its shard; the loop's ``any(active)`` is the
only cross-device collective, one bit per iteration).

Usage:
    python -m repro.launch.batch_solve --smoke
    python -m repro.launch.batch_solve --batch 512 --n 64 --solver bicgstab \
        --format csr --precond jacobi --executor xla
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import batch as batch_lib
from repro.core import make_executor, use_executor
from repro.launch.cache import use_compile_cache
from repro.observability import trace
from repro.solvers.common import Stop

__all__ = ["build_batch", "shard_batch", "solve_batch", "main"]


def build_batch(
    nb: int, n: int, *, fmt: str = "ell", nonsym: bool = False, seed: int = 0
):
    """``nb`` synthetic shifted-tridiagonal systems of size ``n``.

    The diagonal shift varies across the batch so per-system iteration counts
    differ — the convergence mask has real work to do.  ``nonsym`` adds a
    strictly-upper perturbation (BiCGSTAB territory).
    """
    rng = np.random.default_rng(seed)
    idx = np.arange(n)
    stack = np.zeros((nb, n, n), np.float32)
    for b in range(nb):
        a = stack[b]
        a[idx, idx] = 3.0 + 2.0 * (b % 8)
        a[idx[1:], idx[:-1]] = -1.0
        a[idx[:-1], idx[1:]] = -1.0
        if nonsym:
            a += np.triu(rng.normal(size=(n, n)).astype(np.float32) * 0.05, 1)
    xstar = rng.normal(size=(nb, n)).astype(np.float32)
    B = np.einsum("bmn,bn->bm", stack, xstar)
    if fmt == "ell":
        A = batch_lib.batch_ell_from_dense(stack)
    elif fmt == "csr":
        A = batch_lib.batch_csr_from_dense(stack)
    else:
        raise ValueError(f"unknown batched format {fmt!r} (ell | csr)")
    return A, jnp.asarray(B), xstar


def shard_batch(mesh, A, B):
    """Place the batch on the mesh: values/rhs split on the batch axis, the
    shared index structure replicated (it is identical for every system)."""
    batch_spec = NamedSharding(mesh, P("data", *([None] * (A.values.ndim - 1))))
    replicated = NamedSharding(mesh, P())
    leaves, treedef = jax.tree_util.tree_flatten(A)
    shardings = []
    for leaf in leaves:
        if leaf.ndim == A.values.ndim and leaf.shape[0] == A.values.shape[0]:
            shardings.append(batch_spec)
        else:
            shardings.append(replicated)
    A = jax.device_put(A, jax.tree_util.tree_unflatten(treedef, shardings))
    B = jax.device_put(B, NamedSharding(mesh, P("data", None)))
    return A, B


def solve_batch(
    A,
    B,
    *,
    solver: str = "cg",
    precond: str = "none",
    stop: Stop = Stop(),
    executor=None,
):
    fn = {"cg": batch_lib.batch_cg, "bicgstab": batch_lib.batch_bicgstab}[solver]
    M = (
        batch_lib.batch_jacobi_preconditioner(A, executor=executor)
        if precond == "jacobi"
        else None
    )
    return jax.jit(lambda B: fn(A, B, stop=stop, M=M, executor=executor))(B)


def report(res, xstar, wall: float) -> None:
    iters = np.asarray(res.iterations)
    conv = np.asarray(res.converged)
    rnorm = np.asarray(res.residual_norms)
    err = np.abs(np.asarray(res.x) - xstar).max()
    print(f"batch_solve: {res.num_batch} systems in {wall*1e3:.1f} ms")
    print(
        f"  converged {int(conv.sum())}/{conv.size}  "
        f"iterations min/median/max = {iters.min()}/{int(np.median(iters))}/"
        f"{iters.max()}  distinct counts = {len(np.unique(iters))}"
    )
    print(
        f"  residual max = {rnorm.max():.3e}  "
        f"error vs known solution = {err:.3e}"
    )


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small end-to-end run (64 systems)")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--n", type=int, default=64, help="rows per system")
    ap.add_argument("--solver", default="cg", choices=("cg", "bicgstab"))
    ap.add_argument("--format", default="ell", choices=("ell", "csr"),
                    dest="fmt")
    ap.add_argument("--precond", default="none", choices=("none", "jacobi"))
    ap.add_argument("--executor", default="xla",
                    help="executor kind or hardware target name")
    ap.add_argument("--max-iters", type=int, default=500)
    ap.add_argument("--tol", type=float, default=1e-6)
    trace.add_cli_flag(ap)
    args = ap.parse_args(argv)
    trace.enable_from_args(args)

    nb = 64 if args.smoke else args.batch
    n = 48 if args.smoke else args.n

    ndev = len(jax.devices())
    # the data axis carries the batch; pad nb up so it divides evenly
    if nb % ndev:
        nb += ndev - nb % ndev
    mesh = jax.make_mesh(
        (ndev,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    print(f"batch_solve: {nb} x ({n}x{n}) {args.fmt} systems, "
          f"{args.solver}/{args.precond}, mesh data={ndev}, "
          f"executor={args.executor}")

    A, B, xstar = build_batch(
        nb, n, fmt=args.fmt, nonsym=(args.solver == "bicgstab")
    )
    A, B = shard_batch(mesh, A, B)
    stop = Stop(max_iters=args.max_iters, reduction_factor=args.tol)

    ex = make_executor(args.executor)
    with use_executor(ex):
        t0 = time.perf_counter()
        res = solve_batch(
            A, B, solver=args.solver, precond=args.precond, stop=stop,
            executor=ex,
        )
        jax.block_until_ready(res.x)
        wall = time.perf_counter() - t0
    report(res, xstar, wall)
    ok = bool(np.asarray(res.converged).all())
    if not ok:
        print("batch_solve: NOT all systems converged")
    if args.trace and trace.export():
        print(f"  trace -> {args.trace}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
