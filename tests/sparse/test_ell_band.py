"""Diagonal-aligned ELL slots: when ``ell_from_csr_host`` picks them, what it
stores, and that every other pattern keeps the left-packed layout."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sparse
from repro.batch import batch_ell_from_list
from repro.observability import metrics, trace
from repro.sparse import gallery
from repro.sparse.formats import csr_host_arrays, ell_from_csr_host, ell_packed


def _csr(a):
    c = sparse.csr_from_dense(a)
    return np.asarray(c.indptr), np.asarray(c.indices), np.asarray(c.values)


def _tridiag(n):
    a = 2 * np.eye(n, dtype=np.float32)
    a += np.diag(np.full(n - 1, -1.0, np.float32), 1)
    a += np.diag(np.full(n - 1, -1.0, np.float32), -1)
    return _csr(a) + ((n, n),)


def _packed_loop(indptr, indices, values, m, k):
    """Left-packed layout, one row at a time."""
    cols = np.zeros((m, k), np.int32)
    vals = np.zeros((m, k), values.dtype)
    for r in range(m):
        lo, hi = int(indptr[r]), int(indptr[r + 1])
        cols[r, : hi - lo] = indices[lo:hi]
        vals[r, : hi - lo] = values[lo:hi]
    return cols, vals


BANDED = {
    "tridiag_1d": (lambda: _tridiag(9), (-1, 0, 1)),
    "poisson_2d": (lambda: gallery.poisson_2d(5), (-5, -1, 0, 1, 5)),
    "poisson_3d": (lambda: gallery.poisson_3d(4), (-16, -4, -1, 0, 1, 4, 16)),
}


@pytest.mark.parametrize("name", sorted(BANDED))
def test_banded_patterns_take_the_aligned_layout(name):
    build, expected = BANDED[name]
    indptr, indices, values, shape = build()
    A = ell_from_csr_host(indptr, indices, values, shape)
    assert A.offsets == expected
    m, k = A.values.shape
    assert k == len(expected) and A.nnz == m * k
    cols, vals = np.asarray(A.col_idx), np.asarray(A.values)
    rows = np.arange(m)[:, None]
    want = rows + np.asarray(expected)[None, :]
    inside = (want >= 0) & (want < shape[1])
    dense = sparse.convert(sparse.csr_from_arrays(indptr, indices, values, shape), "dense")
    dense = np.asarray(dense.values)
    stored = np.where(inside, dense[rows, np.clip(want, 0, shape[1] - 1)], 0)
    # slot q of row r holds column r + offsets[q], or the padding (col 0, 0)
    present = inside & (stored != 0)
    np.testing.assert_array_equal(cols, np.where(present, want, 0))
    np.testing.assert_array_equal(vals, np.where(present, stored, 0))


def _irregular():
    rng = np.random.default_rng(3)
    a = rng.normal(size=(23, 23)).astype(np.float32)
    a[rng.random(a.shape) < 0.75] = 0
    return _csr(a) + ((23, 23),)


def _many_offsets():
    # two entries a row, but on n distinct diagonals (a reversal plus the
    # diagonal)
    n = 12
    a = np.eye(n, dtype=np.float32)[::-1] + 3 * np.eye(n, dtype=np.float32)
    return _csr(a) + ((n, n),)


def _rectangular():
    a = np.zeros((6, 9), np.float32)
    for r in range(6):
        a[r, r : r + 3] = r + 1.0
    return _csr(a) + ((6, 9),)


@pytest.mark.parametrize(
    "build", [_irregular, _many_offsets, _rectangular],
    ids=["irregular", "more_offsets_than_width", "rectangular"],
)
def test_other_patterns_keep_the_packed_layout(build):
    indptr, indices, values, shape = build()
    A = ell_from_csr_host(indptr, indices, values, shape)
    assert A.offsets is None
    k = int(np.diff(indptr).max())
    cols, vals = _packed_loop(indptr, indices, values, shape[0], k)
    np.testing.assert_array_equal(np.asarray(A.col_idx), cols)
    np.testing.assert_array_equal(np.asarray(A.values), vals)


def test_unsorted_or_repeated_columns_keep_the_packed_layout():
    # a tridiagonal pattern, with row 1 listed out of order and row 3 holding
    # its diagonal twice (CSR sums repeats)
    indptr = np.array([0, 2, 5, 7, 11, 13])
    indices = np.array([0, 1, 2, 1, 0, 1, 2, 2, 3, 3, 4, 3, 4])
    values = np.arange(1, 14, dtype=np.float32)
    A = ell_from_csr_host(indptr, indices, values, (5, 5))
    assert A.offsets is None
    cols, vals = _packed_loop(indptr, indices, values, 5, 4)
    np.testing.assert_array_equal(np.asarray(A.col_idx), cols)
    np.testing.assert_array_equal(np.asarray(A.values), vals)


@pytest.mark.parametrize("name", sorted(BANDED))
def test_csr_triplet_round_trips(name):
    indptr, indices, values, shape = BANDED[name][0]()
    A = ell_from_csr_host(indptr, indices, values, shape)
    got = csr_host_arrays(A)
    for g, want in zip(got, (indptr, indices, values)):
        np.testing.assert_array_equal(g, want)


def test_wider_max_nnz_pads_past_the_offsets():
    indptr, indices, values, shape = _tridiag(7)
    A = ell_from_csr_host(indptr, indices, values, shape, max_nnz=5)
    assert A.offsets == (-1, 0, 1) and A.values.shape == (7, 5)
    assert not np.asarray(A.values)[:, 3:].any()
    x = np.arange(7, dtype=np.float32)
    dense = np.asarray(sparse.convert(A, "dense").values)
    np.testing.assert_allclose(np.asarray(A.apply(jnp.asarray(x))), dense @ x)


def test_new_values_on_one_pattern_share_the_static_metadata():
    indptr, indices, values, shape = gallery.poisson_3d(4)
    A1 = ell_from_csr_host(indptr, indices, values, shape)
    A2 = ell_from_csr_host(indptr, indices, 2.0 * values + 1.0, shape)
    assert jax.tree_util.tree_structure(A1) == jax.tree_util.tree_structure(A2)
    traces = []

    @jax.jit
    def f(A, x):
        traces.append(1)
        return A.values.sum() + x.sum()

    x = jnp.ones(shape[0])
    f(A1, x)
    f(A2, x)
    assert len(traces) == 1


def test_layout_is_counted_and_named_on_the_span():
    before = {
        layout: metrics.counter("sparse.ell_layout", layout=layout).value
        for layout in ("band", "packed")
    }
    tracer = trace.enable()
    try:
        ell_from_csr_host(*BANDED["poisson_2d"][0]())
        ell_from_csr_host(*_irregular())
    finally:
        trace.disable()
    after = {
        layout: metrics.counter("sparse.ell_layout", layout=layout).value
        for layout in ("band", "packed")
    }
    assert after["band"] - before["band"] == 1
    assert after["packed"] - before["packed"] == 1
    spans = [e for e in tracer.events if e["name"] == "sparse.ell_from_csr_host"]
    assert [(e["args"]["layout"], e["args"]["offsets"]) for e in spans[-2:]] == [
        ("band", 5), ("packed", 0)
    ]


def test_structure_preserving_operations_keep_the_layout():
    A = ell_from_csr_host(*BANDED["poisson_2d"][0]())
    assert A.astype(jnp.bfloat16).offsets == A.offsets
    assert A.transpose().offsets == A.offsets


def test_ell_packed_matches_the_packed_conversion():
    indptr, indices, values, shape = BANDED["poisson_3d"][0]()
    A = ell_from_csr_host(indptr, indices, values, shape)
    P = ell_packed(A)
    cols, vals = _packed_loop(indptr, indices, values, shape[0], A.max_nnz)
    assert P.offsets is None
    np.testing.assert_array_equal(np.asarray(P.col_idx), cols)
    np.testing.assert_array_equal(np.asarray(P.values), vals)
    assert ell_packed(P) is P


def test_batch_ell_receives_tail_padding():
    a = np.asarray(
        sparse.convert(sparse.csr_from_arrays(*_tridiag(6)), "dense").values
    )
    mats = [sparse.ell_from_dense(a), sparse.ell_from_dense(2 * a)]
    assert mats[0].offsets == (-1, 0, 1)
    B = batch_ell_from_list(mats)
    want, _ = _packed_loop(*_csr(a), 6, 3)
    np.testing.assert_array_equal(np.asarray(B.col_idx), want)
    np.testing.assert_allclose(
        np.asarray(B.system(1).apply(jnp.ones(6))), (2 * a) @ np.ones(6)
    )


def test_gather_path_of_an_aligned_matrix_is_the_same_operator():
    A = ell_from_csr_host(*BANDED["poisson_3d"][0]())
    G = dataclasses.replace(A, offsets=None)
    x = jnp.asarray(np.random.default_rng(0).normal(size=A.shape[1]), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(A.apply(x)), np.asarray(G.apply(x)), rtol=1e-6, atol=1e-6
    )


def test_distributed_blocks_do_not_see_the_layout():
    from repro.distributed import DistEll, Partition

    A = ell_from_csr_host(*BANDED["poisson_3d"][0]())
    part = Partition.from_part_sizes([16, 24, 24])
    band, packed = (DistEll.from_matrix(B, part) for B in (A, ell_packed(A)))
    for f in dataclasses.fields(DistEll):
        a, b = getattr(band, f.name), getattr(packed, f.name)
        if isinstance(a, jax.Array):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert a == b
    assert band.local_block(0).offsets is None
