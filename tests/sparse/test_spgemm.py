"""SpGEMM / sparse-transpose unit tests: degenerates, invariants, algebra.

The conformance matrix in ``tests/conformance`` pins cross-executor
agreement; this module pins the *semantics* of the operation itself against
dense numpy oracles — including the degenerate structures SpGEMM is most
likely to mishandle (empty rows, rows whose products cancel, rectangular
operands) and the output invariants every space must share bit-for-bit
(column-sorted, duplicate-free rows; pattern a pure function of the operand
patterns).
"""

import numpy as np
import jax.numpy as jnp
import pytest
from _hyp_compat import given, settings, st

from repro import sparse
from repro.core import make_executor
from repro.sparse import Csr, csr_from_arrays, csr_from_dense, spgemm, sptranspose


def _rand_sparse(m, n, density, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, n)).astype(np.float32)
    return np.where(rng.random((m, n)) < density, a, 0.0)


def _dense(C: Csr) -> np.ndarray:
    return np.asarray(sparse.to_dense(C, executor=make_executor("reference")))


def _assert_csr_invariants(C: Csr):
    """Column-sorted, duplicate-free rows; indptr consistent with indices."""
    indptr = np.asarray(C.indptr)
    indices = np.asarray(C.indices)
    assert indptr[0] == 0 and indptr[-1] == indices.size
    assert np.all(np.diff(indptr) >= 0)
    for i in range(C.shape[0]):
        row = indices[indptr[i]: indptr[i + 1]]
        assert np.all(np.diff(row) > 0), f"row {i} not strictly sorted: {row}"


def test_spgemm_matches_dense():
    a = _rand_sparse(17, 23, 0.3, 0)
    b = _rand_sparse(23, 11, 0.3, 1)
    C = spgemm(csr_from_dense(a), csr_from_dense(b))
    _assert_csr_invariants(C)
    np.testing.assert_allclose(_dense(C), a @ b, atol=1e-4, rtol=1e-4)


def test_spgemm_rectangular_chain():
    """(m,k)·(k,n) with all three extents distinct — shape plumbing."""
    a = _rand_sparse(5, 31, 0.4, 2)
    b = _rand_sparse(31, 13, 0.4, 3)
    C = spgemm(csr_from_dense(a), csr_from_dense(b))
    assert C.shape == (5, 13)
    np.testing.assert_allclose(_dense(C), a @ b, atol=1e-4, rtol=1e-4)


def test_spgemm_empty_rows():
    """Rows of A with no entries must come out empty, not crash or shift."""
    a = _rand_sparse(9, 9, 0.5, 4)
    a[0] = 0.0
    a[4] = 0.0
    a[8] = 0.0
    b = _rand_sparse(9, 9, 0.5, 5)
    b[:, 2] = 0.0
    C = spgemm(csr_from_dense(a), csr_from_dense(b))
    _assert_csr_invariants(C)
    indptr = np.asarray(C.indptr)
    for i in (0, 4, 8):
        assert indptr[i] == indptr[i + 1]
    np.testing.assert_allclose(_dense(C), a @ b, atol=1e-4, rtol=1e-4)


def test_spgemm_structural_zeros_kept():
    """Products that cancel numerically stay in the pattern — the pattern is
    a pure function of the operand patterns (the serve-cache contract)."""
    # A row [1, -1] against B rows that sum to zero in column 0
    A = csr_from_arrays([0, 2], [0, 1], np.float32([1.0, -1.0]), (1, 2))
    B = csr_from_arrays([0, 1, 2], [0, 0], np.float32([3.0, 3.0]), (2, 1))
    C = spgemm(A, B)
    assert C.nnz == 1  # structurally present...
    np.testing.assert_allclose(np.asarray(C.values), [0.0], atol=1e-6)


def test_spgemm_zero_nnz_and_zero_dim():
    empty = csr_from_arrays([0, 0, 0], [], np.zeros(0, np.float32), (2, 3))
    b = csr_from_dense(_rand_sparse(3, 4, 0.5, 6))
    C = spgemm(empty, b)
    assert C.shape == (2, 4) and C.nnz == 0
    none = csr_from_arrays([0], [], np.zeros(0, np.float32), (0, 3))
    C0 = spgemm(none, b)
    assert C0.shape == (0, 4) and C0.nnz == 0


def test_spgemm_type_and_shape_errors():
    a = csr_from_dense(_rand_sparse(4, 4, 0.5, 7))
    with pytest.raises(TypeError):
        spgemm(a, np.eye(4, dtype=np.float32))
    b = csr_from_dense(_rand_sparse(5, 4, 0.5, 8))
    with pytest.raises(ValueError):
        spgemm(a, b)


def test_sptranspose_matches_dense():
    a = _rand_sparse(13, 7, 0.4, 9)
    T = sptranspose(csr_from_dense(a))
    assert T.shape == (7, 13)
    _assert_csr_invariants(T)
    np.testing.assert_allclose(_dense(T), a.T, atol=1e-6)


def test_sptranspose_involution():
    a = _rand_sparse(11, 17, 0.3, 10)
    A = csr_from_dense(a)
    TT = sptranspose(sptranspose(A))
    np.testing.assert_array_equal(np.asarray(TT.indptr), np.asarray(A.indptr))
    np.testing.assert_array_equal(
        np.asarray(TT.indices), np.asarray(A.indices)
    )
    np.testing.assert_allclose(
        np.asarray(TT.values), np.asarray(A.values), atol=1e-6
    )


def test_sptranspose_empty():
    empty = csr_from_arrays([0, 0], [], np.zeros(0, np.float32), (1, 5))
    T = sptranspose(empty)
    assert T.shape == (5, 1) and T.nnz == 0


@settings(max_examples=8)
@given(
    m=st.integers(1, 24),
    k=st.integers(1, 24),
    n=st.integers(1, 24),
    density=st.floats(0.05, 0.7),
    seed=st.integers(0, 10_000),
)
def test_spgemm_transpose_identity(m, k, n, density, seed):
    """``(Aᵀ·B)ᵀ == Bᵀ·A`` — the algebra the Galerkin product R·A·P leans on
    (R = Pᵀ), checked against the dense oracle on both sides."""
    a = _rand_sparse(k, m, density, seed)
    b = _rand_sparse(k, n, density, seed + 1)
    A = csr_from_dense(a)
    B = csr_from_dense(b)
    lhs = sptranspose(spgemm(sptranspose(A), B))
    rhs = spgemm(sptranspose(B), A)
    np.testing.assert_array_equal(
        np.asarray(lhs.indptr), np.asarray(rhs.indptr)
    )
    np.testing.assert_array_equal(
        np.asarray(lhs.indices), np.asarray(rhs.indices)
    )
    np.testing.assert_allclose(
        np.asarray(lhs.values), np.asarray(rhs.values), atol=1e-3, rtol=1e-3
    )
    np.testing.assert_allclose(_dense(lhs), (a.T @ b).T, atol=1e-3, rtol=1e-3)


@settings(max_examples=6)
@given(
    n=st.integers(1, 20),
    density=st.floats(0.05, 0.8),
    seed=st.integers(0, 10_000),
)
def test_spgemm_structure_identical_across_executors(n, density, seed):
    """The host coalesce pass is shared, so the output structure must be
    bitwise-identical in every kernel space (values to float tolerance)."""
    import repro.kernels  # noqa: F401 — populate the pallas space

    a = _rand_sparse(n, n, density, seed)
    b = _rand_sparse(n, n, density, seed + 1)
    A, B = csr_from_dense(a), csr_from_dense(b)
    ref = spgemm(A, B, executor=make_executor("reference"))
    for kind in ("xla", "pallas_interpret"):
        got = spgemm(A, B, executor=make_executor(kind))
        np.testing.assert_array_equal(
            np.asarray(got.indptr), np.asarray(ref.indptr)
        )
        np.testing.assert_array_equal(
            np.asarray(got.indices), np.asarray(ref.indices)
        )
        np.testing.assert_allclose(
            np.asarray(got.values), np.asarray(ref.values),
            atol=1e-4, rtol=1e-4,
        )


def test_spgemm_in_chunks_matches_the_reference(monkeypatch):
    """The xla space expands a product in chunks of one padded shape; the
    last chunk's padding contributes nothing."""
    from repro.sparse import ops

    a = _rand_sparse(13, 11, 0.4, 21)
    b = _rand_sparse(11, 9, 0.4, 22)
    A, B = csr_from_dense(a), csr_from_dense(b)
    ref = spgemm(A, B, executor=make_executor("reference"))
    monkeypatch.setattr(ops, "_EXPAND_CHUNK", 7)
    got = spgemm(A, B, executor=make_executor("xla"))
    np.testing.assert_array_equal(np.asarray(got.indptr), np.asarray(ref.indptr))
    np.testing.assert_array_equal(np.asarray(got.indices), np.asarray(ref.indices))
    np.testing.assert_allclose(np.asarray(got.values), np.asarray(ref.values),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(_dense(got), a @ b, atol=1e-4, rtol=1e-4)
