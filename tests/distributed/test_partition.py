"""Property tests for the Partition abstraction (host-side, no devices).

Hypothesis-driven (the deterministic ``_hyp_compat`` shim when hypothesis is
absent): local<->global index round-trips, coverage/disjointness of the row
ranges, the padded-layout bijection, and halo-column-set correctness of the
matrix split against a brute-force reference.
"""

import numpy as np
import pytest

from _hyp_compat import given, settings, st

from repro import sparse
from repro.distributed import Partition, split_by_rows


@settings(max_examples=20)
@given(n=st.integers(0, 300), parts=st.integers(1, 9))
def test_uniform_coverage_and_disjointness(n, parts):
    p = Partition.uniform(n, parts)
    assert p.num_parts == parts
    assert p.global_size == n
    assert sum(p.part_sizes) == n
    # contiguous, ordered, disjoint by construction of offsets; check cover
    seen = np.concatenate(
        [np.arange(*p.range_of(q)) for q in range(parts)]
    ) if n else np.zeros(0, np.int64)
    np.testing.assert_array_equal(seen, np.arange(n))
    # balanced: sizes differ by at most one
    assert max(p.part_sizes) - min(p.part_sizes) <= 1


@settings(max_examples=20)
@given(n=st.integers(1, 300), parts=st.integers(1, 9), seed=st.integers(0, 999))
def test_local_global_round_trip(n, parts, seed):
    rng = np.random.default_rng(seed)
    # ragged and empty parts both appear in these random sizes
    sizes = rng.multinomial(n, np.ones(parts) / parts)
    p = Partition.from_part_sizes(sizes)
    rows = rng.integers(0, n, size=min(n, 64))
    q, loc = p.to_local(rows)
    np.testing.assert_array_equal(p.to_global(q, loc), rows)
    # local indices are in range of their part
    assert (loc >= 0).all() and (loc < np.asarray(sizes)[q]).all()
    # part_of agrees with the ranges
    for r, part in zip(rows, q):
        lo, hi = p.range_of(int(part))
        assert lo <= r < hi


@settings(max_examples=12)
@given(n=st.integers(1, 200), parts=st.integers(1, 8))
def test_padded_layout_bijection(n, parts):
    import jax.numpy as jnp

    p = Partition.uniform(n, parts)
    x = np.random.default_rng(0).normal(size=n).astype(np.float32)
    xp = np.asarray(p.pad(jnp.asarray(x)))
    assert xp.shape == (parts, p.max_part_size)
    # padding slots are zero, real slots carry the global values
    assert np.all(xp[~p.pad_mask] == 0.0)
    np.testing.assert_array_equal(np.asarray(p.unpad(jnp.asarray(xp))), x)
    # every real slot is hit exactly once
    assert p.pad_mask.sum() == n


def test_validation_errors():
    with pytest.raises(ValueError):
        Partition((1, 3))  # must start at 0
    with pytest.raises(ValueError):
        Partition((0, 5, 3))  # decreasing
    with pytest.raises(ValueError):
        Partition.from_part_sizes([4, -1])
    with pytest.raises(IndexError):
        Partition.uniform(10, 2).part_of([10])


@settings(max_examples=10)
@given(n=st.integers(1, 60), parts=st.integers(1, 6), seed=st.integers(0, 999))
def test_halo_column_sets_match_brute_force(n, parts, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(n, n)).astype(np.float32)
    a[rng.random((n, n)) > 0.25] = 0.0
    A = sparse.csr_from_dense(a)
    part = Partition.uniform(n, parts)
    indptr, indices, values = sparse.csr_host_arrays(A)
    split = split_by_rows(indptr, indices, values, part)
    for p in range(parts):
        lo, hi = part.range_of(p)
        # brute force: every column with a nonzero in this row block that
        # falls outside the block's own range
        rows, cols = np.nonzero(a[lo:hi])
        want = np.unique(cols[(cols < lo) | (cols >= hi)])
        np.testing.assert_array_equal(split[p]["halo_cols"], want)
        # and the split reassembles the exact row block
        li, lj, lv = split[p]["local"]
        hi_, hj, hv = split[p]["halo"]
        block = np.zeros((hi - lo, n), np.float32)
        lrows = np.repeat(np.arange(hi - lo), np.diff(li))
        block[lrows, lj + lo] = lv
        hrows = np.repeat(np.arange(hi - lo), np.diff(hi_))
        if len(hrows):
            block[hrows, split[p]["halo_cols"][hj]] = hv
        np.testing.assert_allclose(block, a[lo:hi])


@settings(max_examples=10)
@given(n=st.integers(1, 60), parts=st.integers(1, 6), seed=st.integers(0, 999))
def test_ell_shard_arrays_match_row_loop(n, parts, seed):
    """The vectorized per-shard ELL packing equals a row-by-row packing."""
    from repro.distributed.matrix import _ell_arrays

    rng = np.random.default_rng(seed)
    a = rng.normal(size=(n, n)).astype(np.float32)
    a[rng.random((n, n)) > 0.25] = 0.0
    A = sparse.csr_from_dense(a)
    part = Partition.uniform(n, parts)
    split = split_by_rows(*sparse.csr_host_arrays(A), part)
    L = part.max_part_size
    for info in split:
        for key in ("interior", "boundary", "halo"):
            ip, j, v = info[key]
            k = max(1, int(np.diff(ip).max()) if len(ip) > 1 else 0)
            cols, vals = _ell_arrays(ip, j, v, L, k)
            want_c = np.zeros((L, k), np.int32)
            want_v = np.zeros((L, k), v.dtype)
            for r in range(len(ip) - 1):
                lo, hi = ip[r], ip[r + 1]
                want_c[r, : hi - lo] = j[lo:hi]
                want_v[r, : hi - lo] = v[lo:hi]
            np.testing.assert_array_equal(cols, want_c)
            np.testing.assert_array_equal(vals, want_v)
