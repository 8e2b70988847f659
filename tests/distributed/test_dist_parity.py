"""Sharded-vs-single-device parity: the distributed layer may not change math.

For each shard count x format x operation, the mesh-sharded result must match
the single-device LinOp result to tight tolerance — including ragged
partitions (rows % devices != 0) and an empty-shard degenerate.  The CG
acceptance case runs in f64 against the convergence-regression SPD fixture
(same construction as tests/solvers/test_convergence_regression.py) and pins
iteration count (±1) and residual/solution parity at rtol 1e-10; a spawn-based
twin keeps that acceptance check running even when the parent pytest process
is locked to one device.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import sparse
from repro.distributed import (
    DistCsr,
    DistEll,
    DistVector,
    Partition,
    dist_dot,
    dist_norm2,
)
from repro.solvers import krylov
from repro.solvers.common import Stop

SHARDS = (1, 2, 4, 8)
FORMATS = ("csr", "ell")
N = 101  # prime: ragged under every multi-shard count

DIST_BUILD = {"csr": DistCsr, "ell": DistEll}
BUILD = {"csr": sparse.csr_from_dense, "ell": sparse.ell_from_dense}


def _sparse_pattern(n=N, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(n, n)).astype(dtype)
    a[rng.random((n, n)) > 0.15] = 0.0
    a[np.arange(n), np.arange(n)] = 6.0
    return a


def spd_system(n=96, dtype=np.float32, rng=None):
    """The convergence-regression SPD fixture (same construction)."""
    rng = rng or np.random.default_rng(3)
    a = np.zeros((n, n), dtype)
    for i in range(n):
        a[i, i] = 4.0
        if i > 0:
            a[i, i - 1] = a[i - 1, i] = -1.0
        if i > 2:
            a[i, i - 3] = a[i - 3, i] = -0.5
    x = rng.normal(size=n).astype(dtype)
    return a, x, (a @ x).astype(dtype)


def _partition(n, parts, kind="uniform"):
    if kind == "uniform":
        return Partition.uniform(n, parts)
    if kind == "empty_shard":
        # one shard owns nothing — the degenerate every collective must survive
        sizes = list(Partition.uniform(n, parts - 1).part_sizes) + [0]
        return Partition.from_part_sizes(sizes)
    raise ValueError(kind)


# -----------------------------------------------------------------------------
# SpMV
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("parts", SHARDS)
def test_spmv_parity(parts, fmt, require_devices):
    require_devices(parts)
    a = _sparse_pattern()
    x = np.random.default_rng(1).normal(size=N).astype(np.float32)
    A = BUILD[fmt](a)
    want = np.asarray(sparse.apply(A, jnp.asarray(x)))
    Ad = DIST_BUILD[fmt].from_matrix(A, Partition.uniform(N, parts))
    got = np.asarray(Ad.apply(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fmt", FORMATS)
def test_spmv_empty_shard_degenerate(fmt, require_devices):
    require_devices(3)
    a = _sparse_pattern()
    x = np.random.default_rng(2).normal(size=N).astype(np.float32)
    Ad = DIST_BUILD[fmt].from_matrix(BUILD[fmt](a), _partition(N, 3, "empty_shard"))
    np.testing.assert_allclose(
        np.asarray(Ad.apply(jnp.asarray(x))), a @ x, rtol=1e-4, atol=1e-4
    )


# -----------------------------------------------------------------------------
# BLAS-1 (dot / norm) — psum reductions, ragged partitions
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("parts", SHARDS)
def test_dot_norm_parity(parts, require_devices):
    require_devices(parts)
    rng = np.random.default_rng(5)
    x = rng.normal(size=N).astype(np.float32)
    y = rng.normal(size=N).astype(np.float32)
    part = Partition.uniform(N, parts)
    xv = DistVector.from_global(jnp.asarray(x), part)
    yv = DistVector.from_global(jnp.asarray(y), part)
    assert np.allclose(float(dist_dot(xv, yv)), float(x @ y), rtol=1e-5)
    assert np.allclose(
        float(dist_norm2(xv)), float(np.linalg.norm(x)), rtol=1e-6
    )
    np.testing.assert_allclose(np.asarray(xv.to_global()), x)


def test_psum_norm_padding_regression(require_devices):
    """The padded-shard double-count guard (Stop.threshold-style audit).

    On a ragged partition the shards carry padding slots; a psum'd norm must
    mask them, or whatever sits there is double-counted into every stopping
    criterion.  Poison the padding explicitly and demand the unsharded norm.
    """
    require_devices(2)
    x = np.random.default_rng(7).normal(size=N).astype(np.float32)  # N odd
    part = Partition.uniform(N, 2)
    xv = DistVector.from_global(jnp.asarray(x), part)
    mask = jnp.asarray(part.pad_mask)
    assert not bool(mask.all()), "ragged partition must actually have padding"
    poisoned = dataclasses.replace(
        xv, local=jnp.where(mask, xv.local, jnp.float32(1e9))
    )
    want = float(np.linalg.norm(x))
    assert np.allclose(float(dist_norm2(poisoned)), want, rtol=1e-6)
    assert np.allclose(float(dist_dot(poisoned, poisoned)), float(x @ x), rtol=1e-5)
    # and the round-trip drops the poison
    np.testing.assert_allclose(np.asarray(poisoned.to_global()), x)


# -----------------------------------------------------------------------------
# CG solve — the acceptance case (f64, iterations ±1, rtol 1e-10)
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("parts", SHARDS)
def test_cg_parity_f64(parts, fmt, require_devices):
    require_devices(parts)
    with jax.enable_x64(True):
        a, _, b = spd_system(dtype=np.float64)
        A = BUILD[fmt](a)
        stop = Stop(max_iters=500, reduction_factor=1e-12)
        single = krylov.cg(A, jnp.asarray(b), stop=stop)
        Ad = DIST_BUILD[fmt].from_matrix(A, Partition.uniform(a.shape[0], parts))
        dist = krylov.cg(Ad, jnp.asarray(b), stop=stop)
        assert dist.x.dtype == jnp.float64
        assert bool(dist.converged)
        assert abs(int(dist.iterations) - int(single.iterations)) <= 1
        np.testing.assert_allclose(
            float(dist.residual_norm), float(single.residual_norm), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(dist.x), np.asarray(single.x), rtol=1e-10, atol=1e-12
        )


@pytest.mark.parametrize("precond", ("jacobi", "block_jacobi"))
def test_cg_preconditioned_dist(precond, require_devices):
    require_devices(4)
    a, xstar, b = spd_system()
    Ad = DistCsr.from_matrix(sparse.csr_from_dense(a), Partition.uniform(96, 4))
    opts = {"block_size": 4} if precond == "block_jacobi" else None
    res = krylov.cg(
        Ad, jnp.asarray(b), stop=Stop(max_iters=300), M=precond,
        precond_opts=opts,
    )
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), xstar, rtol=1e-3, atol=1e-3)


def test_dist_precond_surfaces(require_devices):
    """The distributed preconditioners' non-solver surfaces: global LinOp
    apply parity, partition-mismatch rejection, adaptive=True rejection."""
    require_devices(2)
    from repro.distributed import (
        dist_block_jacobi,
        dist_preconditioner,
        dist_scalar_jacobi,
    )

    a, _, b = spd_system()
    part = Partition.uniform(96, 2)
    Ad = DistCsr.from_matrix(sparse.csr_from_dense(a), part)

    # global apply of both preconditioners matches the dense block math
    Ms = dist_scalar_jacobi(Ad)
    np.testing.assert_allclose(
        np.asarray(Ms.apply(jnp.asarray(b))), b / np.diagonal(a), rtol=1e-6
    )
    Mb = dist_block_jacobi(Ad, block_size=4)
    want = np.zeros_like(b)
    for lo in range(0, 96, 4):
        want[lo : lo + 4] = np.linalg.solve(
            a[lo : lo + 4, lo : lo + 4], b[lo : lo + 4]
        )
    np.testing.assert_allclose(
        np.asarray(Mb.apply(jnp.asarray(b))), want, rtol=1e-4, atol=1e-5
    )

    # a preconditioner generated against a different partition is refused
    M_other = dist_scalar_jacobi(
        DistCsr.from_matrix(sparse.csr_from_dense(a), Partition.uniform(96, 1))
    )
    with pytest.raises(ValueError, match="partition"):
        dist_preconditioner(Ad, M_other)
    # per-shard adaptive precision selection cannot stack: explicit dtype only
    with pytest.raises(ValueError, match="uniform storage precision"):
        dist_scalar_jacobi(Ad, adaptive=True)
    with pytest.raises(ValueError, match="uniform storage precision"):
        dist_block_jacobi(Ad, block_size=4, adaptive=True)


@pytest.mark.parametrize("solver", ("bicgstab", "gmres"))
def test_nonsym_solver_parity(solver, require_devices):
    require_devices(4)
    rng = np.random.default_rng(11)
    a, _, _ = spd_system()
    a = a + np.triu(rng.normal(size=a.shape).astype(np.float32) * 0.05, 1)
    x = rng.normal(size=96).astype(np.float32)
    b = (a @ x).astype(np.float32)
    A = sparse.csr_from_dense(a)
    fn = getattr(krylov, solver)
    stop = Stop(max_iters=300)
    single = fn(A, jnp.asarray(b), stop=stop)
    dist = fn(
        DistCsr.from_matrix(A, Partition.uniform(96, 4)), jnp.asarray(b),
        stop=stop,
    )
    assert bool(dist.converged)
    assert abs(int(dist.iterations) - int(single.iterations)) <= 1
    np.testing.assert_allclose(
        np.asarray(dist.x), np.asarray(single.x), rtol=2e-3, atol=2e-4
    )


def test_cg_8shard_acceptance_subprocess(run_with_devices):
    """The acceptance criterion, spawn-isolated so it ALWAYS runs: CG on a
    DistCsr across 8 forced host devices matches the single-device solve on
    the convergence-regression matrix — iterations ±1, rtol 1e-10 in f64."""
    run_with_devices("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp
        from repro import sparse
        from repro.distributed import DistCsr, Partition
        from repro.solvers import krylov
        from repro.solvers.common import Stop

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(3)
        n = 96
        a = np.zeros((n, n))
        for i in range(n):
            a[i, i] = 4.0
            if i > 0:
                a[i, i - 1] = a[i - 1, i] = -1.0
            if i > 2:
                a[i, i - 3] = a[i - 3, i] = -0.5
        b = a @ rng.normal(size=n)
        A = sparse.csr_from_dense(a)
        stop = Stop(max_iters=500, reduction_factor=1e-12)
        single = krylov.cg(A, jnp.asarray(b), stop=stop)
        Ad = DistCsr.from_matrix(A, Partition.uniform(n, 8))
        dist = krylov.cg(Ad, jnp.asarray(b), stop=stop)
        assert bool(dist.converged)
        assert abs(int(dist.iterations) - int(single.iterations)) <= 1
        np.testing.assert_allclose(
            np.asarray(dist.x), np.asarray(single.x), rtol=1e-10, atol=1e-12
        )
        print("DIST CG ACCEPTANCE OK", int(dist.iterations))
    """)
