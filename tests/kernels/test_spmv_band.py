"""Interpret-mode validation of the ELL SpMV's shifted-slice path.

Diagonal-aligned ELL (``Ell.offsets`` set) feeds the kernel shifted slices of
``x`` in place of the gather ``x[col_idx]``.  Both ``spmv_ell`` and the fused
``spmv_dot_ell`` must match the reference space and the gather path of the
same storage, on boundary rows, on row counts that leave a partial row
block, in f32 and bf16 storage.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_executor, registry
from repro.kernels.spmv_dot.kernel import spmv_dot_ell
from repro.kernels.spmv_ell.kernel import spmv_ell
from repro.sparse import gallery
from repro.sparse.formats import ell_from_csr_host

PALLAS = make_executor("pallas_interpret")
REFERENCE = make_executor("reference")


def _system(n_side, dim):
    build = gallery.poisson_2d if dim == 2 else gallery.poisson_3d
    A = ell_from_csr_host(*build(n_side))
    assert A.offsets is not None
    return A


def _vectors(n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    w = jnp.asarray(rng.normal(size=n), jnp.float32)
    return x, w


# 1,600 rows: a full row block of 1,024 and a partial one; 343 rows: one
# block, not a multiple of 128
CASES = [(40, 2), (7, 3)]


@pytest.mark.parametrize("n_side,dim", CASES)
@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_spmv_ell_band_matches_reference_and_gather(n_side, dim, storage):
    A = _system(n_side, dim).astype(jnp.dtype(storage))
    x, _ = _vectors(A.shape[0])
    op = registry.operation("spmv_ell")
    y = op(A, x, executor=PALLAS)
    y_gather = op(dataclasses.replace(A, offsets=None), x, executor=PALLAS)
    y_ref = op(A, x, executor=REFERENCE)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, y_gather, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_side,dim", CASES)
@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_spmv_dot_ell_band_matches_reference_and_gather(n_side, dim, storage):
    A = _system(n_side, dim).astype(jnp.dtype(storage))
    x, w = _vectors(A.shape[0], seed=1)
    op = registry.operation("spmv_dot_ell")
    y, d = op(A, x, w, executor=PALLAS)
    y_g, d_g = op(dataclasses.replace(A, offsets=None), x, w, executor=PALLAS)
    y_ref, d_ref = op(A, x, w, executor=REFERENCE)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, y_g, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(d), float(d_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(d), float(d_g), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bm,bk", [(1024, 2), (2048, 3), (1024, 8)])
def test_band_kernel_block_geometry(bm, bk):
    # block_k below k pads the slot axis with zero slabs; above it, clamps
    A = _system(40, 2)
    x, w = _vectors(A.shape[0], seed=2)
    y = spmv_ell(A.col_idx, A.values, x, offsets=A.offsets,
                 block_m=bm, block_k=bk, interpret=True)
    y2, d = spmv_dot_ell(A.col_idx, A.values, x, w, offsets=A.offsets,
                         block_m=bm, block_k=bk, interpret=True)
    y_ref = spmv_ell(A.col_idx, A.values, x, block_m=bm, block_k=bk,
                     interpret=True)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y2, y_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(d), float(jnp.vdot(w, y_ref)), rtol=1e-5)


def test_band_boundary_rows_see_zero_outside_x():
    # every row of a 3x3x3 grid but the centre is a boundary row; x large
    # at both ends catches a slice that reads past the padding
    A = _system(3, 3)
    n = A.shape[0]
    x = jnp.arange(1, n + 1, dtype=jnp.float32) * 1e3
    dense = np.zeros((n, n), np.float32)
    cols, vals = np.asarray(A.col_idx), np.asarray(A.values)
    for r in range(n):
        for c, v in zip(cols[r], vals[r]):
            dense[r, c] += v
    y = registry.operation("spmv_ell")(A, x, executor=PALLAS)
    np.testing.assert_allclose(y, dense @ np.asarray(x), rtol=1e-6)


def test_band_path_builds_no_gather_and_names_its_kernel():
    A = _system(7, 3)
    x, w = _vectors(A.shape[0])
    band = str(jax.make_jaxpr(
        lambda A, x, w: registry.operation("spmv_dot_ell")(A, x, w, executor=PALLAS)
    )(A, x, w))
    gather = str(jax.make_jaxpr(
        lambda A, x: registry.operation("spmv_ell")(A, x, executor=PALLAS)
    )(dataclasses.replace(A, offsets=None), x))
    assert "spmv_dot_ell_band" in band and " gather[" not in band
    assert "spmv_ell_band" not in gather and " gather[" in gather


def test_cg_iterations_match_the_gather_path():
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    A = _system(12, 2)
    b, _ = _vectors(A.shape[0], seed=4)
    stop = Stop(max_iters=200, reduction_factor=1e-6)
    band = krylov.cg(A, b, executor=PALLAS, stop=stop)
    gather = krylov.cg(dataclasses.replace(A, offsets=None), b,
                       executor=PALLAS, stop=stop)
    assert abs(int(band.iterations) - int(gather.iterations)) <= 1
    np.testing.assert_allclose(band.x, gather.x, rtol=1e-4, atol=1e-5)
