"""Mixed-precision iterative refinement (solvers.ir) — the LinOp payoff.

The acceptance contract: an f32 inner CG under an f64 outer residual must
recover the f64 solution on the SPD regression matrices; plain Richardson and
preconditioner-inner variants must behave like the textbook iteration.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import solvers, sparse
from repro.core import (
    PallasInterpretExecutor,
    ReferenceExecutor,
    XlaExecutor,
    use_executor,
)
from repro.precond import unit_roundoff


def spd_dense(n=96, rng=None, dtype=np.float64):
    rng = rng or np.random.default_rng(3)
    a = np.zeros((n, n), dtype)
    for i in range(n):
        a[i, i] = 4.0
        if i > 0:
            a[i, i - 1] = a[i - 1, i] = -1.0
        if i > 2:
            a[i, i - 3] = a[i - 3, i] = -0.5
    return a


def blocked_spd_dense(n=128, bs=8, dtype=np.float64):
    """The adaptive block-Jacobi regression fixture, f64."""
    rng = np.random.default_rng(7)
    a = np.zeros((n, n), dtype)
    for s in range(0, n, bs):
        blk = rng.normal(size=(bs, bs))
        a[s : s + bs, s : s + bs] = blk @ blk.T + 4 * np.eye(bs)
    for i in range(n - bs):
        a[i, i + bs] = a[i + bs, i] = 0.05
    return a


F64_STOP = solvers.Stop(max_iters=100, reduction_factor=1e-12)


@pytest.mark.parametrize("fixture", [spd_dense, blocked_spd_dense])
def test_mixed_precision_ir_reaches_f64_tolerance(fixture):
    """f32 inner CG + x64 outer residual converges to the f64 tolerance —
    far below anything a pure-f32 solve can reach."""
    with jax.enable_x64(True):
        a = fixture()
        n = a.shape[0]
        A = sparse.csr_from_dense(a)
        assert A.dtype == jnp.float64
        rng = np.random.default_rng(0)
        xstar = rng.normal(size=n)
        b = jnp.asarray(a @ xstar)
        with use_executor(XlaExecutor()):
            res = solvers.mixed_precision_ir(A, b, stop=F64_STOP)
            pure32 = solvers.cg(
                A.astype(jnp.float32), b.astype(jnp.float32),
                stop=solvers.Stop(max_iters=2000, reduction_factor=1e-12),
            )
        assert bool(res.converged)
        assert res.x.dtype == jnp.float64
        # at the f64 tolerance, clearly below the f32 floor
        assert float(res.residual_norm) < 1e-9
        assert float(res.residual_norm) < 0.1 * float(pure32.residual_norm)
        np.testing.assert_allclose(np.asarray(res.x), xstar, atol=1e-8)


def test_mixed_precision_ir_outer_sweeps_are_few():
    """IR theory: each outer sweep contracts the error by ~ the inner solve
    accuracy; reaching 1e-12 from an sqrt(u_f32) ~ 2e-4 inner tolerance
    should take a handful of sweeps, not tens."""
    with jax.enable_x64(True):
        a = spd_dense()
        A = sparse.csr_from_dense(a)
        b = jnp.asarray(a @ np.ones(a.shape[0]))
        with use_executor(XlaExecutor()):
            res = solvers.mixed_precision_ir(A, b, stop=F64_STOP)
        assert bool(res.converged)
        assert int(res.iterations) <= 8, int(res.iterations)


@pytest.mark.parametrize(
    "exec_cls", [ReferenceExecutor, XlaExecutor, PallasInterpretExecutor]
)
def test_mixed_precision_ir_cross_executor(exec_cls):
    with jax.enable_x64(True):
        a = spd_dense(48)
        A = sparse.csr_from_dense(a)
        xstar = np.random.default_rng(1).normal(size=48)
        b = jnp.asarray(a @ xstar)
        with use_executor(exec_cls()):
            res = solvers.mixed_precision_ir(A, b, stop=F64_STOP)
        assert bool(res.converged), exec_cls.__name__
        np.testing.assert_allclose(np.asarray(res.x), xstar, atol=1e-8)


def test_plain_richardson():
    """inner=None degenerates to x += relaxation * r; converges for
    rho(I - omega*A) < 1 (here A ~ diag(4), omega = 0.2)."""
    a = spd_dense(64, dtype=np.float32)
    A = sparse.csr_from_dense(a)
    xstar = np.random.default_rng(2).normal(size=64).astype(np.float32)
    b = jnp.asarray(a @ xstar)
    with use_executor(XlaExecutor()):
        res = solvers.ir(
            A, b, relaxation=0.2,
            stop=solvers.Stop(max_iters=500, reduction_factor=1e-5),
        )
    assert bool(res.converged)
    np.testing.assert_allclose(res.x, xstar, atol=1e-3)


def test_ir_with_preconditioner_inner():
    """Any LinOp can be the inner operator — block-Jacobi IR is the classic
    'relaxation by approximate inverse'."""
    a = blocked_spd_dense(64, 8, dtype=np.float32)
    A = sparse.csr_from_dense(a)
    xstar = np.random.default_rng(4).normal(size=64).astype(np.float32)
    b = jnp.asarray(a @ xstar)
    with use_executor(XlaExecutor()):
        M = solvers.block_jacobi_preconditioner(A, block_size=8)
        res = solvers.ir(
            A, b, inner=M,
            stop=solvers.Stop(max_iters=500, reduction_factor=1e-5),
        )
    assert bool(res.converged)
    np.testing.assert_allclose(res.x, xstar, atol=1e-3)


def test_ir_respects_max_iters():
    a = spd_dense(32, dtype=np.float32)
    A = sparse.csr_from_dense(a)
    b = jnp.asarray((a @ np.ones(32)).astype(np.float32))
    with use_executor(XlaExecutor()):
        res = solvers.ir(
            A, b, relaxation=0.01,  # far too small to converge in 3 sweeps
            stop=solvers.Stop(max_iters=3, reduction_factor=1e-10),
        )
    assert int(res.iterations) == 3
    assert not bool(res.converged)


def test_ir_solver_factory_is_linop():
    """IrSolver composes like any operator — here preconditioning CG."""
    a = spd_dense(48, dtype=np.float32)
    A = sparse.csr_from_dense(a)
    xstar = np.random.default_rng(5).normal(size=48).astype(np.float32)
    b = jnp.asarray(a @ xstar)
    with use_executor(XlaExecutor()):
        S = solvers.IrSolver(
            A,
            inner=solvers.jacobi_preconditioner(A),
            stop=solvers.Stop(max_iters=20, reduction_factor=1e-2),
        )
        res = solvers.cg(
            A, b, M=S, stop=solvers.Stop(max_iters=200, reduction_factor=1e-5)
        )
    assert bool(res.converged)
    np.testing.assert_allclose(res.x, xstar, atol=1e-3)


def test_mixed_precision_ir_is_jittable():
    with jax.enable_x64(True):
        a = spd_dense(48)
        A = sparse.csr_from_dense(a)
        xstar = np.random.default_rng(6).normal(size=48)
        b = jnp.asarray(a @ xstar)
        with use_executor(XlaExecutor()):
            x = jax.jit(
                lambda b: solvers.mixed_precision_ir(A, b, stop=F64_STOP).x
            )(b)
        np.testing.assert_allclose(np.asarray(x), xstar, atol=1e-8)


def test_unit_roundoff_table():
    """The PR 3 precision machinery the IR budget reuses."""
    assert unit_roundoff(jnp.float16) == 2.0**-11
    assert unit_roundoff(jnp.bfloat16) == 2.0**-8
    assert unit_roundoff(jnp.float32) == 2.0**-24
    with jax.enable_x64(True):
        assert unit_roundoff(jnp.float64) == 2.0**-53


def test_mixed_precision_ir_requires_astype():
    with pytest.raises(TypeError, match="astype"):
        solvers.mixed_precision_ir(lambda v: v, jnp.ones(4, jnp.float32))


def test_ir_threads_executor_into_inner_operator():
    """The documented contract: executor= passed to ir() governs the whole
    operator subtree, inner solve included."""
    from repro.core import LinOp

    seen = []

    class Probe(LinOp):
        def _apply(self, v, executor):
            seen.append(executor)
            return v

    a = spd_dense(16, dtype=np.float32)
    A = sparse.csr_from_dense(a)
    b = jnp.asarray((a @ np.ones(16)).astype(np.float32))
    ex = XlaExecutor()
    solvers.ir(A, b, inner=Probe(),
               stop=solvers.Stop(max_iters=2, reduction_factor=1e-10),
               executor=ex)
    assert seen and all(e is ex for e in seen), seen
