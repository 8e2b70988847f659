"""Interpret-mode validation of the fused-reduction Pallas kernels.

``spmv_dot_ell`` (SpMV emitting w·y in the same pass) and ``axpy_norm``
(axpy emitting ‖z‖²) against their ref.py oracles and dense numpy, across
block geometries that exercise tail padding on both grid axes.
"""

import numpy as np
import jax.numpy as jnp
import pytest
from _hyp_compat import given, settings, st

from repro import sparse
from repro.kernels.axpy_norm.kernel import axpy_norm
from repro.kernels.axpy_norm.ref import axpy_norm_ref
from repro.kernels.spmv_dot.kernel import spmv_dot_ell
from repro.kernels.spmv_dot.ref import spmv_dot_ell_ref


# -- spmv_dot_ell ----------------------------------------------------------------

@pytest.mark.parametrize("bm,bk", [(1024, 8), (2048, 16), (37, 5)])
def test_spmv_dot_ell_blocks(rng, bm, bk):
    # 2500 rows: several row blocks of 1024 plus a padded tail
    a = rng.normal(size=(2500, 150)).astype(np.float32)
    a[rng.random(a.shape) < 0.85] = 0
    A = sparse.ell_from_dense(a)
    x = jnp.asarray(rng.normal(size=(150,)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(2500,)).astype(np.float32))
    y, d = spmv_dot_ell(A.col_idx, A.values, x, w, block_m=bm, block_k=bk,
                        interpret=True)
    y_ref, d_ref = spmv_dot_ell_ref(A.col_idx, A.values, x, w)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(d), float(d_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(y_ref), a @ np.asarray(x), rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        float(d_ref), float(np.asarray(w) @ (a @ np.asarray(x))),
        rtol=1e-3,
    )


@given(m=st.integers(1, 120), seed=st.integers(0, 99))
@settings(max_examples=10)
def test_spmv_dot_ell_sweep(m, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, m)).astype(np.float32)
    a[rng.random(a.shape) < 0.8] = 0
    A = sparse.ell_from_dense(a)
    x = jnp.asarray(rng.normal(size=(m,)).astype(np.float32))
    y, d = spmv_dot_ell(A.col_idx, A.values, x, x, interpret=True)
    y_ref, d_ref = spmv_dot_ell_ref(A.col_idx, A.values, x, x)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(d), float(d_ref), rtol=1e-4, atol=1e-3)


# -- axpy_norm -------------------------------------------------------------------

@pytest.mark.parametrize("block_n", [1024, 2048, 100])
def test_axpy_norm_blocks(rng, block_n):
    n = 2777  # several blocks of 1024 plus a padded tail
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    z, ss = axpy_norm(-0.37, x, y, block_n=block_n, interpret=True)
    z_ref, ss_ref = axpy_norm_ref(-0.37, x, y)
    np.testing.assert_allclose(z, z_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(ss), float(ss_ref), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(z_ref), -0.37 * np.asarray(x) + np.asarray(y), atol=1e-6
    )


@given(n=st.integers(1, 3000), seed=st.integers(0, 99))
@settings(max_examples=10)
def test_axpy_norm_sweep(n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    alpha = float(rng.normal())
    z, ss = axpy_norm(alpha, x, y, interpret=True)
    z_ref, ss_ref = axpy_norm_ref(alpha, x, y)
    np.testing.assert_allclose(z, z_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(ss), float(ss_ref), rtol=1e-4, atol=1e-5)


def test_axpy_norm_traced_alpha(rng):
    # alpha arrives as a traced scalar inside solver loops — the (1, 1)
    # operand path must accept a jax array, not only a python float
    import jax

    x = jnp.asarray(rng.normal(size=(500,)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(500,)).astype(np.float32))

    def f(a):
        return axpy_norm(a, x, y, interpret=True)[1]

    got = jax.jit(f)(jnp.float32(0.5))
    _, want = axpy_norm_ref(0.5, x, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
