"""Krylov solver survey (paper Figs. 12-14): GFLOP/s vs the ai=1 bound.

The paper runs each solver 10k iterations on 10 matrices and reports
GFLOP/s against the aggressive arithmetic-intensity-1 bound (BW / bytes-per-
value: f64 -> BW/8; here f32 -> BW/4).  We run a fixed iteration budget
(restart-free stopping disabled) and count flops structurally:

    per CG iteration: 1 SpMV (2 nnz) + 3 axpy (2n) + 2 dots (2n) + norm (2n)
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, spd_suite, time_fn
from repro import solvers, sparse
from repro.core import XlaExecutor, use_executor

ITERS = 200


def flops_per_iter(kind: str, nnz: int, n: int) -> float:
    spmv = 2 * nnz
    axpy = 2 * n
    dot = 2 * n
    if kind == "cg":
        return spmv + 3 * axpy + 3 * dot
    if kind == "fcg":
        return spmv + 3 * axpy + 4 * dot
    if kind == "bicgstab":
        return 2 * spmv + 6 * axpy + 5 * dot
    if kind == "cgs":
        return 2 * spmv + 7 * axpy + 2 * dot
    if kind == "gmres":  # per inner iteration, restart 30 amortized
        return spmv + 30 * dot + 31 * axpy
    raise KeyError(kind)


def run(bandwidth: float, small: bool = False) -> None:
    bound = bandwidth / 4 / 1e9  # f32 ai=1 bound, GFLOP/s
    suite = spd_suite(small)
    stop = solvers.Stop(max_iters=ITERS, reduction_factor=0.0)  # fixed budget
    with use_executor(XlaExecutor()):
        for mat_name, a in suite.items():
            n = a.shape[0]
            nnz = int((a != 0).sum())
            A = sparse.csr_from_dense(a)
            b = jnp.asarray(np.ones(n, np.float32))
            for kind, fn in (
                ("cg", solvers.cg),
                ("fcg", solvers.fcg),
                ("bicgstab", solvers.bicgstab),
                ("cgs", solvers.cgs),
            ):
                solve = jax.jit(lambda b, fn=fn: fn(A, b, stop=stop).x)
                t = time_fn(solve, b, warmup=1, repeats=3)
                gflops = ITERS * flops_per_iter(kind, nnz, n) / t / 1e9
                emit(
                    f"solver_{kind}_{mat_name}",
                    t * 1e6,
                    f"{gflops:.3f}GFLOP/s_frac{gflops/bound:.2f}",
                )


def precond_fixture(small: bool = False):
    """Blocked SPD system with mixed per-block conditioning — the adaptive
    block-Jacobi showcase fixture (well-conditioned blocks drop to 16-bit
    storage, stretched ones stay fp32)."""
    rng = np.random.default_rng(7)
    n, bs = (512 if small else 2048), 8
    a = np.zeros((n, n), np.float32)
    for bi, s in enumerate(range(0, n, bs)):
        blk = rng.normal(size=(bs, bs)).astype(np.float32)
        blk = blk @ blk.T + 4 * np.eye(bs, dtype=np.float32)
        if bi % 3 == 0:  # every third block badly scaled
            scale = np.linspace(1.0, 30.0, bs).astype(np.float32)
            blk = blk * np.sqrt(scale[:, None] * scale[None, :])
        a[s : s + bs, s : s + bs] = blk
    for i in range(n - bs):
        a[i, i + bs] = a[i + bs, i] = 0.05
    return a, bs


def nonsym_suite(small: bool = False):
    """Nonsymmetric/realistic-spectrum gallery systems (PR-10 corpus)."""
    from repro.sparse.gallery import convection_diffusion_2d, power_law_laplacian

    side = 24 if small else 48
    n = 512 if small else 2048
    return {
        f"convdiff{side}_pe0p5": convection_diffusion_2d(
            side, peclet=0.5, scheme="centered"),
        f"convdiff{side}_pe5": convection_diffusion_2d(
            side, peclet=5.0, scheme="upwind"),
        f"powerlaw{n}": power_law_laplacian(n, seed=4),
    }


def run_nonsym(small: bool = False) -> None:
    """Nonsymmetric solver survey: time-to-tolerance for the solvers that are
    actually safe on nonsymmetric A (gmres, bicgstab, cgs) over the gallery
    corpus.  CG is deliberately absent: the symmetry guard rejects these
    operands (that rejection is pinned by the tier-1 suite, not timed here).
    """
    stop = solvers.Stop(max_iters=2000, reduction_factor=1e-6)
    with use_executor(XlaExecutor()):
        for mat_name, (indptr, indices, values, shape) in nonsym_suite(small).items():
            A = sparse.csr_from_arrays(indptr, indices, values, shape)
            rng = np.random.default_rng(0)
            b = jnp.asarray(rng.normal(size=shape[0]).astype(np.float32))
            for kind, fn in (
                ("gmres", solvers.gmres),
                ("bicgstab", solvers.bicgstab),
                ("cgs", solvers.cgs),
            ):
                res = fn(A, b, stop=stop)
                solve = jax.jit(lambda b, fn=fn: fn(A, b, stop=stop).x)
                t = time_fn(solve, b, warmup=1, repeats=3)
                emit(
                    f"nonsym_{kind}_{mat_name}",
                    t * 1e6,
                    f"iters{int(res.iterations)}_conv{int(bool(res.converged))}",
                )


def run_preconditioners(small: bool = False) -> None:
    """Preconditioner survey (the adaptive block-Jacobi feature table):
    CG iterations, wall time, and preconditioner storage per variant."""
    a, bs = precond_fixture(small)
    n = a.shape[0]
    A = sparse.csr_from_dense(a)
    rng = np.random.default_rng(0)
    xstar = rng.normal(size=n).astype(np.float32)
    b = jnp.asarray((a @ xstar).astype(np.float32))
    stop = solvers.Stop(max_iters=1000, reduction_factor=1e-6)
    with use_executor(XlaExecutor()):
        # every variant is a LinOp — the identity included — so the survey
        # reads storage_bytes off the uniform interface, no isinstance
        # checks or getattr defaults
        variants = {
            "identity": solvers.identity_preconditioner,
            "jacobi": solvers.jacobi_preconditioner(A),
            "block_jacobi_fp32": solvers.block_jacobi_preconditioner(A, block_size=bs),
            "block_jacobi_adaptive": solvers.block_jacobi_preconditioner(
                A, block_size=bs, adaptive=True
            ),
        }
        for name, M in variants.items():
            res = solvers.cg(A, b, stop=stop, M=M)
            t = time_fn(
                lambda b, M=M: solvers.cg(A, b, stop=stop, M=M).x,
                b, warmup=1, repeats=3,
            )
            detail = f"iters{int(res.iterations)}_storage{M.storage_bytes}B"
            counts = getattr(M, "precision_counts", None)
            if counts:
                detail += "_" + "+".join(f"{d}:{c}" for d, c in counts)
            emit(f"precond_cg_{name}", t * 1e6, detail)
            assert bool(res.converged), f"{name} failed to converge"


def run_ir(small: bool = False, smoke: bool = False) -> None:
    """Mixed-precision iterative refinement survey (the LinOp showcase).

    Solves the SPD suite to the f64 tolerance two ways — plain f64 CG vs an
    IR outer loop whose inner CG runs on an f32 copy of A (half the operator
    bytes per inner iteration) — and reports wall time, outer sweeps, and
    inner-operator storage.  ``smoke=True`` runs one small system and asserts
    convergence (the CI gate for the IR path).
    """
    from repro.precond import unit_roundoff

    suite = spd_suite(small or smoke)
    if smoke:
        name = "stencil2d_32"
        suite = {name: suite[name]}
    stop = solvers.Stop(max_iters=200, reduction_factor=1e-12)
    with jax.enable_x64(True), use_executor(XlaExecutor()):
        for mat_name, a in suite.items():
            a = a.astype(np.float64)
            n = a.shape[0]
            A = sparse.csr_from_dense(a)
            rng = np.random.default_rng(11)
            xstar = rng.normal(size=n)
            b = jnp.asarray(a @ xstar)

            res64 = solvers.cg(A, b, stop=stop)
            t64 = time_fn(
                lambda b: solvers.cg(A, b, stop=stop).x, b, warmup=1, repeats=3
            )
            emit(
                f"ir_cg_f64_{mat_name}", t64 * 1e6,
                f"iters{int(res64.iterations)}_storage{A.memory_bytes}B",
            )

            # generation (the astype cast + inner-solver factory) happens once,
            # outside the timer — like the f64 baseline's prebuilt A above
            A_low = A.astype(jnp.float32)
            inner = solvers.CgSolver(
                A_low,
                stop=solvers.Stop(
                    max_iters=200,
                    reduction_factor=unit_roundoff(jnp.float32) ** 0.5,
                ),
            )
            solve_ir = lambda b: solvers.ir(  # noqa: E731
                A, b, stop=stop, inner=inner, inner_dtype=jnp.float32
            )
            res_ir = solve_ir(b)
            t_ir = time_fn(lambda b: solve_ir(b).x, b, warmup=1, repeats=3)
            emit(
                f"ir_mixed_f32_{mat_name}", t_ir * 1e6,
                f"sweeps{int(res_ir.iterations)}_innerstorage{A_low.memory_bytes}B",
            )
            if smoke:
                assert bool(res_ir.converged), "mixed-precision IR failed to converge"
                err = float(jnp.abs(res_ir.x - xstar).max())
                assert err < 1e-8, f"IR error {err} above f64 tolerance"
                print(f"# ir smoke ok: {int(res_ir.iterations)} sweeps, err {err:.2e}")


if __name__ == "__main__":
    from benchmarks.bench_stream import run as stream_run

    bw = stream_run(sizes=(1 << 22,))
    run(bw, small=True)
    run_preconditioners(small=True)
    run_ir(small=True)
