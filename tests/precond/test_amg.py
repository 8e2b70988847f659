"""Smoothed-aggregation AMG: hierarchy construction, cycles, solver seam.

Pins the setup pipeline (strength → aggregation → smoothed P → Galerkin
R·A·P via the registered SpGEMM family), the V/W-cycle as a convergent
preconditioner, the ``M="amg"`` string seam into every Krylov solver, and the
serve-path pattern/values split (:func:`amg_serve_pattern` /
:func:`amg_serve_factors` / :func:`batch_amg_apply`).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import sparse
from repro.core import make_executor
from repro.precond import Multigrid, amg_preconditioner, make_preconditioner
from repro.precond.amg import (
    aggregate,
    amg_serve_factors,
    amg_serve_pattern,
    batch_amg_apply,
    strength_mask,
    tentative_prolongator,
)
from repro.solvers.common import Stop
from repro.solvers.krylov import (
    CgSolver,
    FcgSolver,
    bicgstab,
    cg,
    cgs,
    fcg,
    gmres,
)
from repro.sparse import csr_from_arrays
from repro.sparse.gallery import anisotropic_2d, poisson_2d


def _poisson(n_side=16):
    indptr, indices, values, shape = poisson_2d(n_side)
    return csr_from_arrays(indptr, indices, values, shape)


def _dense(C):
    return np.asarray(sparse.to_dense(C, executor=make_executor("reference")))


# =============================================================================
# setup pipeline
# =============================================================================


def test_strength_mask_drops_weak_direction():
    indptr, indices, values, shape = anisotropic_2d(8, 0.001)
    strong = strength_mask(indptr, indices, values, theta=0.08)
    n = shape[0]
    rows = np.repeat(np.arange(n), np.diff(indptr))
    # x-neighbours (|i-j| == 1) carry the unit coupling — all strong;
    # y-neighbours (|i-j| == 8) carry the ε coupling — all weak
    off = np.abs(rows - indices)
    assert strong[off == 1].all()
    assert not strong[off == 8].any()


def test_aggregate_covers_every_row():
    A = _poisson(12)
    indptr, indices = np.asarray(A.indptr), np.asarray(A.indices)
    values = np.asarray(A.values)
    strong = strength_mask(indptr, indices, values)
    agg, n_agg = aggregate(indptr, indices, strong, A.shape[0])
    assert agg.min() >= 0 and agg.max() == n_agg - 1
    assert n_agg < A.shape[0]  # actually coarsens
    # every aggregate id in range is used
    assert np.unique(agg).size == n_agg


def test_tentative_prolongator_partition_of_unity():
    agg = np.array([0, 0, 1, 2, 1])
    T = tentative_prolongator(agg, 3)
    d = _dense(T)
    assert d.shape == (5, 3)
    np.testing.assert_array_equal(d.sum(axis=1), np.ones(5))
    np.testing.assert_array_equal(np.argmax(d, axis=1), agg)


def test_galerkin_matches_dense_triple_product():
    A = _poisson(10)
    M = Multigrid(A, max_levels=1, coarse_size=8)
    L = M.levels[0]
    a, p, r = _dense(L.A), _dense(L.P), _dense(L.R)
    np.testing.assert_allclose(r, p.T, atol=1e-6)
    np.testing.assert_allclose(
        _dense(M.coarse_A), r @ a @ p, atol=1e-3, rtol=1e-3
    )


def test_hierarchy_coarsens_and_reports_complexity():
    A = _poisson(24)
    M = amg_preconditioner(A, coarse_size=32)
    assert M.num_levels >= 3
    rows = [L.A.shape[0] for L in M.levels] + [M.coarse_A.shape[0]]
    assert all(a > b for a, b in zip(rows, rows[1:]))
    assert rows[-1] <= 32
    assert 1.0 < M.operator_complexity < 3.0


# =============================================================================
# the cycle as a preconditioner
# =============================================================================


def test_vcycle_reduces_residual():
    A = _poisson(16)
    M = amg_preconditioner(A)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    x = M.apply(b)
    r = b - sparse.apply(A, x)
    assert float(jnp.linalg.norm(r)) < 0.5 * float(jnp.linalg.norm(b))


@pytest.mark.parametrize("cycle", ["v", "w"])
def test_amg_cg_cuts_iterations(cycle):
    A = _poisson(16)
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    stop = Stop(max_iters=1000, reduction_factor=1e-6)
    base = cg(A, b, stop=stop, M="block_jacobi")
    amg = cg(A, b, stop=stop, M="amg", precond_opts={"cycle": cycle})
    assert bool(base.converged) and bool(amg.converged)
    assert int(amg.iterations) * 3 <= int(base.iterations)


def test_wcycle_not_weaker_than_vcycle():
    A = _poisson(16)
    rng = np.random.default_rng(2)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    stop = Stop(max_iters=1000, reduction_factor=1e-8)
    it_v = int(cg(A, b, stop=stop, M="amg",
                  precond_opts={"cycle": "v"}).iterations)
    it_w = int(cg(A, b, stop=stop, M="amg",
                  precond_opts={"cycle": "w"}).iterations)
    assert it_w <= it_v


@pytest.mark.parametrize("solver_fn", [cg, fcg, bicgstab, cgs, gmres])
def test_amg_string_seam_all_solvers(solver_fn):
    """``M="amg"`` resolves through make_preconditioner in every solver."""
    A = _poisson(8)
    rng = np.random.default_rng(3)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    stop = Stop(max_iters=300, reduction_factor=1e-5)
    res = solver_fn(A, b, stop=stop, M="amg")
    assert bool(res.converged), solver_fn.__name__
    r = b - sparse.apply(A, res.x)
    assert float(jnp.linalg.norm(r)) <= 1e-4 * max(
        1.0, float(jnp.linalg.norm(b))
    ) * 10


def test_amg_options_via_make_preconditioner():
    A = _poisson(8)
    M = make_preconditioner(
        A, "amg", theta=0.1, cycle="w", smooth_prolongator=False,
        coarse_solver="cg", coarse_size=16,
    )
    assert isinstance(M, Multigrid)
    assert M.cycle == "w" and M._coarse_inv is None
    with pytest.raises(ValueError):
        make_preconditioner(A, "amg", cycle="x")
    with pytest.raises(TypeError):
        make_preconditioner(np.eye(4, dtype=np.float32), "amg")


def test_block_jacobi_smoother_variant():
    A = _poisson(12)
    M = amg_preconditioner(A, smoother="block_jacobi",
                           smoother_opts={"block_size": 4})
    rng = np.random.default_rng(4)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    stop = Stop(max_iters=500, reduction_factor=1e-6)
    res = cg(A, b, stop=stop, M=M)
    assert bool(res.converged)


def test_solver_as_linop_composition():
    """Inner-outer: a generated AMG-CG solver IS a LinOp, so it slots in as
    the preconditioner of an outer flexible method — Ginkgo's factory
    composability.  FCG tolerates the iteration-varying inner operator."""
    A = _poisson(8)
    inner = CgSolver(A, stop=Stop(max_iters=8, reduction_factor=1e-10),
                     M="amg")
    outer = FcgSolver(A, stop=Stop(max_iters=100, reduction_factor=1e-6),
                      M=inner)
    rng = np.random.default_rng(5)
    b = jnp.asarray(rng.normal(size=A.shape[0]).astype(np.float32))
    res = outer.solve(b)
    assert bool(res.converged)
    assert int(res.iterations) <= 5  # a near-exact inner solve ≈ one step


def test_jit_apply_traceable():
    A = _poisson(8)
    M = amg_preconditioner(A)
    b = jnp.ones(A.shape[0], jnp.float32)
    eager = M.apply(b)
    jitted = jax.jit(M.apply)(b)
    np.testing.assert_allclose(
        np.asarray(eager), np.asarray(jitted), atol=1e-5
    )


import jax  # noqa: E402


# =============================================================================
# serve-path pattern/values split
# =============================================================================


def test_serve_pattern_values_split_matches_direct():
    """Factors from the split path must equal factors computed from scratch —
    the cache-reuse correctness property."""
    A = _poisson(8)
    indptr, indices = np.asarray(A.indptr), np.asarray(A.indices)
    pat = amg_serve_pattern(indptr, indices, A.shape[0])
    assert pat.flat_len == A.shape[0] + pat.n_agg**2
    flat = amg_serve_factors(pat, A.values)
    # inv_diag segment: Poisson diagonal is 4
    np.testing.assert_allclose(
        np.asarray(flat[: A.shape[0]]), 0.25, atol=1e-6
    )
    # coarse block: A_c = Pᵀ A P with the unit tentative P over pat.agg
    a = _dense(A)
    p = np.zeros((A.shape[0], pat.n_agg), np.float32)
    p[np.arange(A.shape[0]), pat.agg] = 1.0
    c_inv = np.asarray(flat[A.shape[0]:]).reshape(pat.n_agg, pat.n_agg)
    np.testing.assert_allclose(
        np.linalg.inv(c_inv.astype(np.float64)), p.T @ a @ p,
        atol=1e-2, rtol=1e-3,
    )


def test_batch_amg_apply_rows_independent():
    """Each batch row applies its own factors — slot independence is what
    lets the serve engine freeze/swap rows without touching neighbours."""
    A = _poisson(8)
    n = A.shape[0]
    indptr, indices = np.asarray(A.indptr), np.asarray(A.indices)
    pat = amg_serve_pattern(indptr, indices, n)
    f1 = amg_serve_factors(pat, A.values)
    f2 = amg_serve_factors(pat, 2.0 * A.values)
    flat = jnp.stack([f1, f2])
    rng = np.random.default_rng(6)
    R = jnp.asarray(rng.normal(size=(2, n)).astype(np.float32))
    out = batch_amg_apply(pat, flat, R)
    solo0 = batch_amg_apply(pat, f1[None], R[:1])
    solo1 = batch_amg_apply(pat, f2[None], R[1:])
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(solo0[0]), atol=1e-6, rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(out[1]), np.asarray(solo1[0]), atol=1e-6, rtol=1e-6
    )
    # scaling A by 2 scales M⁻¹ by 1/2 (same input vector, scaled factors)
    half = batch_amg_apply(pat, f2[None], R[:1])
    np.testing.assert_allclose(
        np.asarray(half[0]), 0.5 * np.asarray(solo0[0]), atol=1e-5, rtol=1e-5
    )


# =============================================================================
# 3-D hierarchies, ELL operands, the pytree
# =============================================================================


def _poisson3d(n_side):
    from repro.sparse.gallery import poisson_3d

    indptr, indices, values, shape = poisson_3d(n_side)
    return indptr, indices, values.astype(np.float32), shape


@pytest.mark.parametrize("n_side", [16, 32])
def test_3d_hierarchy_at_theta_0_coarsens_twofold(n_side):
    A = csr_from_arrays(*_poisson3d(n_side))
    M = amg_preconditioner(A, theta=0.0)
    rows = [L.A.shape[0] for L in M.levels] + [M.coarse_A.shape[0]]
    assert len(rows) >= 3 and rows[-1] <= 64
    assert all(2 * b <= a for a, b in zip(rows, rows[1:])), rows
    assert M.operator_complexity < 2.0


def test_a_level_whose_aggregation_keeps_most_rows_ends_the_descent():
    """At θ = 0.08 the second level of the 3-D stencil's hierarchy would
    keep all but one of its rows: it becomes the coarsest level."""
    A = csr_from_arrays(*_poisson3d(32))
    M = amg_preconditioner(A)
    assert M.num_levels == 2
    n_c = M.coarse_A.shape[0]
    assert n_c < A.shape[0] // 2
    indptr, indices, values = sparse.csr_host_arrays(M.coarse_A)
    _, n_agg = aggregate(indptr, indices, strength_mask(indptr, indices, values),
                         n_c)
    assert 2 * n_agg > n_c


def test_a_coarsest_level_too_large_for_the_dense_inverse_is_refused(monkeypatch):
    from repro.precond import amg

    monkeypatch.setattr(amg, "DENSE_COARSE_MAX_ROWS", 20)
    A = _poisson(12)
    with pytest.raises(ValueError, match=r"coarsest level has \d+ rows"):
        amg_preconditioner(A, coarse_size=40)
    # the CG coarse solver takes it
    M = amg_preconditioner(A, coarse_size=40, coarse_solver="cg")
    assert M.coarse_A.shape[0] > 20


def test_an_ell_operand_gives_the_levels_and_cycle_of_the_equal_csr():
    indptr, indices, values, shape = _poisson3d(16)
    ell = sparse.ell_from_csr_host(indptr, indices, values, shape)
    assert ell.offsets is not None  # the band layout
    csr = csr_from_arrays(indptr, indices, values, shape)
    M_ell = make_preconditioner(ell, "amg", theta=0.0)
    M_csr = make_preconditioner(csr, "amg", theta=0.0)
    assert M_ell.levels[0].A is ell  # the fine level is the operand itself
    # a CSR operand is converted to the same ELL once, for the cycle's SpMVs
    fine = M_csr.levels[0].A
    assert isinstance(fine, sparse.Ell) and fine.offsets == ell.offsets
    np.testing.assert_array_equal(np.asarray(fine.values), np.asarray(ell.values))
    assert M_ell.num_levels == M_csr.num_levels
    for a, b in zip(M_ell.levels, M_csr.levels):
        for name in ("P", "R"):
            np.testing.assert_array_equal(np.asarray(getattr(a, name).col_idx),
                                          np.asarray(getattr(b, name).col_idx))
            np.testing.assert_allclose(np.asarray(getattr(a, name).values),
                                       np.asarray(getattr(b, name).values),
                                       rtol=1e-6, atol=1e-7)
    r = jnp.asarray(np.random.default_rng(7).normal(size=shape[0]), jnp.float32)
    np.testing.assert_allclose(np.asarray(M_ell.apply(r)), np.asarray(M_csr.apply(r)),
                               rtol=1e-5, atol=1e-5)


def test_multigrid_is_a_pytree_that_a_jitted_solve_takes():
    indptr, indices, values, shape = _poisson3d(16)
    A = sparse.ell_from_csr_host(indptr, indices, values, shape)
    M = make_preconditioner(A, "amg", theta=0.0)
    leaves, tree = jax.tree_util.tree_flatten(M)
    assert all(isinstance(leaf, jax.Array) for leaf in leaves)
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert again.num_levels == M.num_levels and again.cycle == M.cycle
    stop = Stop(max_iters=100, reduction_factor=1e-6)
    solve = jax.jit(lambda A, M, b: cg(A, b, M=M, stop=stop, strict=False))
    rng = np.random.default_rng(8)
    b = jnp.asarray(rng.normal(size=shape[0]), jnp.float32)
    res = solve(A, M, b)
    assert bool(res.converged) and int(res.iterations) <= 15
    solve(A, M, 2 * b)
    assert solve._cache_size() == 1  # one compile serves every right-hand side
    r = b - sparse.apply(A, res.x)
    assert float(jnp.linalg.norm(r)) <= 2e-6 * float(jnp.linalg.norm(b))
