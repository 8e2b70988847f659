"""Batched sparse formats — gko::batch::matrix::{Csr, Ell} analogues.

Ginkgo's batched functionality solves thousands of small independent sparse
systems in one kernel launch.  The dominant application pattern (chemistry
networks, cells of a discretized PDE) produces systems that share one sparsity
pattern and differ only in values, so both formats here store **one** index
structure and a value tensor with a leading batch axis — Ginkgo's
shared-pattern fast path made the storage invariant:

* :class:`BatchCsr` — shared ``indptr``/``indices``, values ``(nb, nnz)``;
* :class:`BatchEll` — shared ``col_idx (m, k)``, values ``(nb, m, k)``.

Conversion from a *heterogeneous* list of single-system matrices computes the
union sparsity pattern host-side (setup time, numpy — like ``convert_to``) and
fills the entries a system lacks with explicit zeros: SpMV and the solvers are
agnostic to which zeros are structural.

Both classes are frozen JAX pytrees: the batch axis of ``values`` is a normal
array axis, so the whole matrix shards across devices with a single
``NamedSharding`` on that axis (see :mod:`repro.launch.batch_solve`).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.batch.linop import BatchLinOp
from repro.sparse.formats import Csr, Ell, _nbytes, ell_packed

__all__ = [
    "BatchCsr",
    "BatchEll",
    "batch_csr_from_list",
    "batch_ell_from_list",
    "batch_csr_from_dense",
    "batch_ell_from_dense",
    "batch_ell_from_batch_csr",
]


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields)
    )
    return cls


class BatchMatrixLinOp(BatchLinOp):
    """Common BatchLinOp behavior for the batched formats.

    ``apply`` dispatches through the batched operation registry
    (:func:`repro.batch.ops.apply_batch`) — kernels untouched.
    """

    def _apply(self, X, executor):
        from repro.batch import ops

        return ops.apply_batch(self, X, executor=executor)

    def astype(self, dtype) -> "BatchMatrixLinOp":
        """Same shared structure, values cast (the mixed-precision hook)."""
        return dataclasses.replace(self, values=self.values.astype(dtype))


@dataclasses.dataclass(frozen=True)
class BatchCsr(BatchMatrixLinOp):
    """Batch of CSR matrices sharing one sparsity pattern.

    One index structure, stacked values — the storage Ginkgo's
    ``batch::matrix::Csr`` uses when ``num_stored_elems`` is uniform.
    """

    indptr: jax.Array  # (m+1,) int32 — shared
    indices: jax.Array  # (nnz,) int32 — shared
    values: jax.Array  # (nb, nnz)
    shape: Tuple[int, int]  # static, per-system

    @property
    def num_batch(self) -> int:
        return self.values.shape[0]

    @property
    def nnz(self) -> int:
        """Stored entries per system (shared pattern)."""
        return self.values.shape[1]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.indptr, self.indices, self.values)

    def system(self, i: int) -> Csr:
        """Extract one system as a single-system ``Csr`` view."""
        return Csr(self.indptr, self.indices, self.values[i], self.shape)


_register(BatchCsr, ["indptr", "indices", "values"], ["shape"])


@dataclasses.dataclass(frozen=True)
class BatchEll(BatchMatrixLinOp):
    """Batch of ELL matrices sharing one column-index block.

    Padding follows the single-system convention: ``col_idx == 0`` with a zero
    value, so gathers stay in-bounds without predication on every system.
    """

    col_idx: jax.Array  # (m, max_nnz) int32 — shared
    values: jax.Array  # (nb, m, max_nnz)
    shape: Tuple[int, int]  # static, per-system

    @property
    def num_batch(self) -> int:
        return self.values.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.values.shape[2]

    @property
    def nnz(self) -> int:
        """Stored entries per system (``m * max_nnz``, incl. padding)."""
        return int(self.values.shape[1] * self.values.shape[2])

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.col_idx, self.values)

    def system(self, i: int) -> Ell:
        return Ell(self.col_idx, self.values[i], self.shape)


_register(BatchEll, ["col_idx", "values"], ["shape"])


# -- host-side constructors (setup-time, numpy) --------------------------------


def _check_uniform_shapes(mats: Sequence) -> Tuple[int, int]:
    if not mats:
        raise ValueError("cannot batch an empty list of matrices")
    shape = tuple(mats[0].shape)
    for i, m in enumerate(mats):
        if tuple(m.shape) != shape:
            raise ValueError(
                f"batched systems must share a shape: system 0 is {shape}, "
                f"system {i} is {tuple(m.shape)}"
            )
    return shape


def _shared_csr_pattern(mats: Sequence[Csr]) -> bool:
    p0, i0 = np.asarray(mats[0].indptr), np.asarray(mats[0].indices)
    return all(
        np.array_equal(np.asarray(m.indptr), p0)
        and np.array_equal(np.asarray(m.indices), i0)
        for m in mats[1:]
    )


def batch_csr_from_list(mats: Sequence[Csr]) -> BatchCsr:
    """Stack single-system CSR matrices into one BatchCsr.

    Identical patterns take the fast path (stack values, zero copies of the
    index arrays); heterogeneous patterns are rebuilt on the union pattern
    with explicit zeros for the entries a system lacks.
    """
    shape = _check_uniform_shapes(mats)
    if _shared_csr_pattern(mats):
        return BatchCsr(
            indptr=mats[0].indptr,
            indices=mats[0].indices,
            values=jnp.stack([m.values for m in mats]),
            shape=shape,
        )

    m_rows = shape[0]
    # union pattern: per row, the sorted union of every system's column set
    row_cols: List[np.ndarray] = []
    for r in range(m_rows):
        cols = [
            np.asarray(mat.indices)[
                int(np.asarray(mat.indptr)[r]) : int(np.asarray(mat.indptr)[r + 1])
            ]
            for mat in mats
        ]
        row_cols.append(np.unique(np.concatenate(cols)) if cols else np.zeros(0, np.int32))
    indptr = np.zeros(m_rows + 1, np.int64)
    indptr[1:] = np.cumsum([c.size for c in row_cols])
    indices = (
        np.concatenate(row_cols).astype(np.int32)
        if m_rows
        else np.zeros(0, np.int32)
    )
    dtype = np.asarray(mats[0].values).dtype
    values = np.zeros((len(mats), int(indptr[-1])), dtype)
    for b, mat in enumerate(mats):
        mp, mi, mv = (
            np.asarray(mat.indptr),
            np.asarray(mat.indices),
            np.asarray(mat.values),
        )
        for r in range(m_rows):
            lo, hi = int(indptr[r]), int(indptr[r + 1])
            pos = lo + np.searchsorted(indices[lo:hi], mi[mp[r] : mp[r + 1]])
            values[b, pos] = mv[mp[r] : mp[r + 1]]
    return BatchCsr(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(indices),
        values=jnp.asarray(values),
        shape=shape,
    )


def _shared_ell_pattern(mats: Sequence[Ell]) -> bool:
    c0 = np.asarray(mats[0].col_idx)
    return all(
        m.col_idx.shape == mats[0].col_idx.shape
        and np.array_equal(np.asarray(m.col_idx), c0)
        for m in mats[1:]
    )


def batch_ell_from_list(mats: Sequence[Ell]) -> BatchEll:
    """Stack single-system ELL matrices into one BatchEll.

    Identical column blocks take the fast path; otherwise each row's union
    column set (padded to the batch-wide max width) becomes the shared block.
    Diagonal-aligned inputs are left-packed first: the batch slot tables
    expect a row's padding at its tail.
    """
    shape = _check_uniform_shapes(mats)
    mats = [ell_packed(m) for m in mats]
    if _shared_ell_pattern(mats):
        return BatchEll(
            col_idx=mats[0].col_idx,
            values=jnp.stack([m.values for m in mats]),
            shape=shape,
        )

    m_rows = shape[0]
    dtype = np.asarray(mats[0].values).dtype
    # per-row union of stored columns across the batch; padding entries
    # (col 0, value 0) may enter the union as structural zeros — harmless,
    # they contribute nothing to SpMV
    row_cols = []
    for r in range(m_rows):
        cols = np.unique(
            np.concatenate([np.asarray(mat.col_idx)[r] for mat in mats])
        )
        row_cols.append(cols)
    k = max((c.size for c in row_cols), default=1)
    col_idx = np.zeros((m_rows, k), np.int32)
    values = np.zeros((len(mats), m_rows, k), dtype)
    for r in range(m_rows):
        cols = row_cols[r]
        col_idx[r, : cols.size] = cols
        for b, mat in enumerate(mats):
            mc = np.asarray(mat.col_idx)[r]
            mv = np.asarray(mat.values)[r]
            pos = np.searchsorted(cols, mc)
            # scatter-add so duplicate padding columns (col 0, value 0)
            # cannot clobber a real entry at column 0
            np.add.at(values[b, r], pos, mv)
    return BatchEll(
        col_idx=jnp.asarray(col_idx),
        values=jnp.asarray(values),
        shape=shape,
    )


def batch_csr_from_dense(stack: np.ndarray) -> BatchCsr:
    """(nb, m, n) dense stack -> BatchCsr on the union pattern."""
    from repro.sparse.formats import csr_from_dense

    return batch_csr_from_list([csr_from_dense(a) for a in np.asarray(stack)])


def batch_ell_from_dense(stack: np.ndarray) -> BatchEll:
    """(nb, m, n) dense stack -> BatchEll on the union pattern."""
    from repro.sparse.formats import ell_from_dense

    return batch_ell_from_list([ell_from_dense(a) for a in np.asarray(stack)])


def batch_ell_from_batch_csr(A: BatchCsr, max_nnz: int | None = None) -> BatchEll:
    """BatchCsr -> BatchEll (shared pattern is preserved by construction)."""
    indptr = np.asarray(A.indptr)
    indices = np.asarray(A.indices)
    values = np.asarray(A.values)  # (nb, nnz)
    m = A.shape[0]
    row_nnz = np.diff(indptr)
    k = int(max_nnz if max_nnz is not None else (row_nnz.max() if m else 0))
    k = max(k, 1)
    cols = np.zeros((m, k), np.int32)
    vals = np.zeros((A.num_batch, m, k), values.dtype)
    for r in range(m):
        n = row_nnz[r]
        if n > k:
            raise ValueError(f"row {r} has {n} nnz > max_nnz {k}")
        cols[r, :n] = indices[indptr[r] : indptr[r] + n]
        vals[:, r, :n] = values[:, indptr[r] : indptr[r] + n]
    return BatchEll(jnp.asarray(cols), jnp.asarray(vals), A.shape)
