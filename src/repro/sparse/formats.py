"""Sparse matrix formats — COO, CSR, ELL, SELL-P (Ginkgo's format set).

Each format is a frozen JAX pytree (device arrays + static metadata) so it can
flow through ``jit`` / ``pjit`` and be sharded.  Construction/conversion happens
host-side in numpy (setup time, like Ginkgo's ``convert_to``); the `apply`
(SpMV) path is executor-dispatched (see :mod:`repro.sparse.ops`).

TPU adaptations (DESIGN.md §2):

* ELL stores row-major ``(m, max_nnz)`` blocks; padding uses column 0 with a
  zero value so gathers stay in-bounds without predication.
* ELL slots follow one of two layouts, picked by the pattern alone.  A square
  pattern whose entries lie on at most ``max_nnz`` distinct diagonals
  (offsets ``col - row``, as in a stencil or a banded matrix), each row's
  columns strictly ascending, stores the entry of offset ``offsets[q]`` in
  slot ``q`` of every row; a row that lacks that diagonal keeps the padding
  ``(col 0, value 0)`` there, and ``Ell.offsets`` records the ascending
  offsets, so the SpMV can read ``x`` as shifted contiguous slices instead of
  gathering it.  Any other pattern is left-packed: each row's entries in its
  first slots in CSR order, padding at the tail, ``Ell.offsets is None``.
  Both store ``(m, max_nnz)``.
* SELL-P uses slice size ``C = 8`` (one sublane) by default instead of
  Ginkgo's GPU default 64, and pads each slice's column count to a multiple of
  ``stride_factor`` so slice-local blocks stay vector-aligned.  Values are laid
  out per-slice column-major — ``(cols_in_slice, C)`` contiguous per slice —
  exactly Ginkgo's layout, flattened into one buffer with ``slice_sets``
  offsets.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.linop import LinOp
from repro.observability import metrics, trace

__all__ = [
    "Coo",
    "Csr",
    "Ell",
    "Sellp",
    "Dense",
    "convert",
    "csr_host_arrays",
    "csr_slice_rows_host",
    "ell_packed",
]


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields)
    )
    return cls


def _nbytes(*arrays: jax.Array) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


class MatrixLinOp(LinOp):
    """Common LinOp behavior for every sparse/dense format.

    ``apply`` keeps dispatching through the operation registry and the
    executor's kernel-space chain (:func:`repro.sparse.ops.apply`) — the
    format classes gaining a LinOp face changes nothing below the dispatch
    layer.  Formats carry no ``executor`` field (they are sharded pytrees);
    the executor threads in from the apply call or the ambient context.
    """

    def _apply(self, b, executor):
        from repro.sparse import ops

        return ops.apply(self, b, executor=executor)

    def astype(self, dtype) -> "MatrixLinOp":
        """Same structure, values cast to ``dtype`` (indices untouched).

        The mixed-precision hook: ``A.astype(jnp.float32)`` is the reduced-
        precision operator the IR inner solve runs against.
        """
        return dataclasses.replace(self, values=self.values.astype(dtype))

    def transpose(self):
        """Transpose via the host CSR hub (setup time, concrete values only).

        Dense/Coo/Csr override with direct (and tracer-safe) paths; the
        padded formats route through :func:`csr_host_arrays` and rebuild in
        their own format, so ``Transpose(A)`` works for every format.
        """
        indptr, indices, values = csr_host_arrays(self)
        m, n = self.shape
        t_indptr, t_indices, t_values = _transpose_host(
            indptr, indices, values, m, n
        )
        tT = convert(
            Csr(
                indptr=jnp.asarray(t_indptr, jnp.int32),
                indices=jnp.asarray(t_indices, jnp.int32),
                values=jnp.asarray(t_values),
                shape=(n, m),
            ),
            type(self),
        )
        return tT


@dataclasses.dataclass(frozen=True)
class Dense(MatrixLinOp):
    """Row-major dense matrix (gko::matrix::Dense)."""

    values: jax.Array  # (m, n)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.values.shape

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def nnz(self) -> int:
        """Stored entries (dense stores every entry)."""
        return int(self.values.size)

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.values)

    def transpose(self) -> "Dense":
        return Dense(self.values.T)


_register(Dense, ["values"], [])


@dataclasses.dataclass(frozen=True)
class Coo(MatrixLinOp):
    """Coordinate format; row indices kept sorted (Ginkgo requires sorted COO)."""

    row_idx: jax.Array  # (nnz,) int32, sorted
    col_idx: jax.Array  # (nnz,) int32
    values: jax.Array  # (nnz,)
    shape: Tuple[int, int]  # static

    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.row_idx, self.col_idx, self.values)

    def transpose(self) -> "Coo":
        """Transpose: swap indices, restore row order.

        Structure work is host-side (indices must be concrete); the values
        are permuted on-device, so a ``Coo`` built inside a trace from a
        static pattern and *traced* values transposes cleanly (the implicit-
        layer backward relies on this).
        """
        r = np.asarray(self.col_idx)
        c = np.asarray(self.row_idx)
        order = np.lexsort((c, r))
        return Coo(
            row_idx=jnp.asarray(r[order], jnp.int32),
            col_idx=jnp.asarray(c[order], jnp.int32),
            values=jnp.take(self.values, jnp.asarray(order), axis=0),
            shape=(self.shape[1], self.shape[0]),
        )


_register(Coo, ["row_idx", "col_idx", "values"], ["shape"])


@dataclasses.dataclass(frozen=True)
class Csr(MatrixLinOp):
    """Compressed sparse row."""

    indptr: jax.Array  # (m+1,) int32
    indices: jax.Array  # (nnz,) int32
    values: jax.Array  # (nnz,)
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.indptr, self.indices, self.values)

    def transpose(self) -> "Csr":
        """Transpose via the sorted triplet.

        Structure work (the permutation) is host-side and needs concrete
        ``indptr``/``indices``; the values are permuted on-device with a
        single gather, so a ``Csr`` built inside a trace from a static
        pattern and *traced* values transposes cleanly — the implicit-layer
        backward (``Transpose(A)`` under ``jit``) relies on this.
        """
        indptr = np.asarray(self.indptr, np.int64)
        indices = np.asarray(self.indices, np.int64)
        m = self.shape[0]
        rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        tr, tc = indices, rows  # swapped
        order = np.lexsort((tc, tr))
        t_indptr = np.zeros(self.shape[1] + 1, np.int64)
        np.add.at(t_indptr, tr + 1, 1)
        return Csr(
            indptr=jnp.asarray(np.cumsum(t_indptr), jnp.int32),
            indices=jnp.asarray(tc[order], jnp.int32),
            values=jnp.take(self.values, jnp.asarray(order), axis=0),
            shape=(self.shape[1], self.shape[0]),
        )


_register(Csr, ["indptr", "indices", "values"], ["shape"])


@dataclasses.dataclass(frozen=True)
class Ell(MatrixLinOp):
    """ELLPACK: fixed ``max_nnz`` entries per row, zero-padded.

    Padding entries have ``col_idx == 0`` and ``value == 0`` (in-bounds gather,
    zero contribution) — the predication-free TPU idiom.

    ``offsets`` (static) is ``None`` for the left-packed layout: a row's
    entries fill its first slots and the padding sits at the tail.  When it
    is a tuple, the slots are diagonal-aligned (:func:`ell_from_csr_host`
    picks this for a square pattern with at most ``max_nnz`` distinct
    offsets): slot ``q`` of row ``r`` holds column ``r + offsets[q]``, or the
    padding where the row lacks that diagonal, and slots past
    ``len(offsets)`` are padding in every row.  ``offsets`` ascend, so each
    row's entries stay in ascending column order.
    """

    col_idx: jax.Array  # (m, max_nnz) int32
    values: jax.Array  # (m, max_nnz)
    shape: Tuple[int, int]
    offsets: Optional[Tuple[int, ...]] = None

    @property
    def max_nnz(self) -> int:
        return self.values.shape[1]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def nnz(self) -> int:
        """Stored entries ``m * max_nnz`` (Ginkgo's num_stored_elements:
        padding is read by the kernel, so it is what memory bounds see)."""
        return int(self.values.size)

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.col_idx, self.values)


_register(Ell, ["col_idx", "values"], ["shape", "offsets"])


@dataclasses.dataclass(frozen=True)
class Sellp(MatrixLinOp):
    """SELL-P (sliced ELL with padding) — Ginkgo's GPU throughput format.

    Rows are grouped into slices of ``slice_size`` (C).  Each slice stores its
    own padded column count (a multiple of ``stride_factor``); slice ``i``'s
    values occupy ``slice_sets[i]*C : slice_sets[i+1]*C`` of the flat buffers,
    laid out column-major within the slice (column-contiguous groups of C).

    ``slice_cols`` (static-shaped device array) and ``slice_sets`` are part of
    the pytree; ``max_slice_cols`` is static so Pallas grids can size to it.
    """

    col_idx: jax.Array  # (total_padded_nnz,) int32
    values: jax.Array  # (total_padded_nnz,)
    slice_sets: jax.Array  # (num_slices+1,) int32 — column offsets per slice
    slice_cols: jax.Array  # (num_slices,) int32 — padded cols per slice
    shape: Tuple[int, int]
    slice_size: int  # static (C)
    stride_factor: int  # static
    max_slice_cols: int  # static — max(slice_cols), for grid sizing

    @property
    def num_slices(self) -> int:
        return self.slice_cols.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def nnz(self) -> int:
        """Stored (slice-padded) entries — what the kernels stream."""
        return int(self.values.size)

    @property
    def memory_bytes(self) -> int:
        return _nbytes(self.col_idx, self.values, self.slice_sets, self.slice_cols)

    def transpose(self) -> "Sellp":
        """Transpose preserving this matrix's slice layout parameters."""
        indptr, indices, values = csr_host_arrays(self)
        m, n = self.shape
        t_indptr, t_indices, t_values = _transpose_host(
            indptr, indices, values, m, n
        )
        return sellp_from_csr_host(
            t_indptr, t_indices, t_values, (n, m),
            slice_size=self.slice_size, stride_factor=self.stride_factor,
        )


_register(
    Sellp,
    ["col_idx", "values", "slice_sets", "slice_cols"],
    ["shape", "slice_size", "stride_factor", "max_slice_cols"],
)


def _transpose_host(
    indptr: np.ndarray, indices: np.ndarray, values: np.ndarray, m: int, n: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Transpose a host CSR triplet of an ``(m, n)`` matrix (setup time)."""
    rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
    order = np.lexsort((rows, indices))
    t_indptr = np.zeros(n + 1, np.int64)
    np.add.at(t_indptr, indices + 1, 1)
    return np.cumsum(t_indptr), rows[order], values[order]


# -- host-side constructors (setup-time, numpy) --------------------------------


def coo_from_dense(a: np.ndarray, dtype=None) -> Coo:
    a = np.asarray(a)
    r, c = np.nonzero(a)
    order = np.lexsort((c, r))
    r, c = r[order], c[order]
    v = a[r, c]
    return Coo(
        row_idx=jnp.asarray(r, jnp.int32),
        col_idx=jnp.asarray(c, jnp.int32),
        values=jnp.asarray(v, dtype or a.dtype),
        shape=a.shape,
    )


def csr_from_dense(a: np.ndarray, dtype=None) -> Csr:
    a = np.asarray(a)
    m = a.shape[0]
    r, c = np.nonzero(a)
    order = np.lexsort((c, r))
    r, c = r[order], c[order]
    v = a[r, c]
    indptr = np.zeros(m + 1, np.int32)
    np.add.at(indptr, r + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return Csr(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(c, jnp.int32),
        values=jnp.asarray(v, dtype or a.dtype),
        shape=a.shape,
    )


def csr_from_arrays(indptr, indices, values, shape) -> Csr:
    return Csr(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(indices, jnp.int32),
        values=jnp.asarray(values),
        shape=tuple(shape),
    )


def _rows_ascend(indptr, indices) -> bool:
    """Whether each row's column indices strictly ascend."""
    up = np.diff(indices) > 0
    starts = indptr[1:-1]
    # a step from one row's last entry to the next row's first may go down
    up[starts[(starts > 0) & (starts < indices.size)] - 1] = True
    return bool(up.all())


def _ell_arrays(filled: np.ndarray, indices, values):
    """``(cols, vals)`` holding the CSR entries, in order, in the ``True``
    slots of ``filled`` (row-major); the other slots hold the padding."""
    cols = np.zeros(filled.shape, np.int32)
    vals = np.zeros(filled.shape, values.dtype)
    cols[filled] = indices
    vals[filled] = values
    return cols, vals


def ell_from_csr_host(indptr, indices, values, shape, max_nnz=None) -> Ell:
    """Host-side CSR -> ELL (span ``sparse.ell_from_csr_host``).

    Diagonal-aligned slots when the pattern is square, each row's columns
    strictly ascend, and it has at most ``max_nnz`` distinct offsets
    ``col - row``; the left-packed layout otherwise (module docstring).
    Each conversion counts its layout in
    ``sparse.ell_layout{layout=band|packed}``.
    """
    with trace.span("sparse.ell_from_csr_host", cat="sparse") as span:
        indptr = np.asarray(indptr)
        indices = np.asarray(indices)
        values = np.asarray(values)
        m, n = shape
        row_nnz = np.diff(indptr)
        k = int(max_nnz if max_nnz is not None else (row_nnz.max() if m else 0))
        k = max(k, 1)
        bad = np.flatnonzero(row_nnz > k)
        if bad.size:
            raise ValueError(
                f"row {int(bad[0])} has {int(row_nnz[bad[0]])} nnz > max_nnz {k}"
            )
        offsets = None
        # a canonical pattern (each row's columns strictly ascending, so no
        # entry repeats and no two share a slot) on few diagonals
        if m == n and indices.size and _rows_ascend(indptr, indices):
            rows = np.repeat(np.arange(m, dtype=np.int64), row_nnz)
            diag = indices - rows
            diag += n - 1  # offset col - row as a bin in [0, 2n - 1)
            present = np.bincount(diag) > 0
            if np.count_nonzero(present) <= k:
                offsets = np.flatnonzero(present) - (n - 1)
                # an entry's slot is its offset's rank among the offsets
                filled = np.zeros((m, k), bool)
                filled[rows, (np.cumsum(present) - 1)[diag]] = True
        if offsets is None:
            filled = np.arange(k) < row_nnz[:, None]
        cols, vals = _ell_arrays(filled, indices, values)
        layout = "packed" if offsets is None else "band"
        metrics.counter("sparse.ell_layout", layout=layout).inc()
        span.annotate(layout=layout, offsets=0 if offsets is None else offsets.size)
        return Ell(
            jnp.asarray(cols),
            jnp.asarray(vals),
            tuple(shape),
            None if offsets is None else tuple(int(o) for o in offsets),
        )


def ell_packed(A: Ell) -> Ell:
    """``A`` in the left-packed layout (``A`` itself when it already is).

    For consumers whose slot tables assume a row's padding at its tail
    (:class:`repro.batch.BatchEll`).  Explicit stored zeros are dropped, as in
    :func:`csr_host_arrays`; the width stays ``A.max_nnz``.
    """
    if A.offsets is None:
        return A
    indptr, indices, values = csr_host_arrays(A)
    filled = np.arange(A.max_nnz) < np.diff(indptr)[:, None]
    cols, vals = _ell_arrays(filled, indices, values)
    return Ell(jnp.asarray(cols), jnp.asarray(vals), A.shape)


def ell_from_dense(a: np.ndarray, dtype=None) -> Ell:
    c = csr_from_dense(a, dtype)
    return ell_from_csr_host(
        np.asarray(c.indptr), np.asarray(c.indices), np.asarray(c.values), c.shape
    )


def sellp_from_csr_host(
    indptr,
    indices,
    values,
    shape,
    slice_size: int = 8,
    stride_factor: int = 8,
) -> Sellp:
    """Host-side CSR -> SELL-P with Ginkgo's slice layout."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    values = np.asarray(values)
    m, _ = shape
    C = slice_size
    # an empty matrix gets zero slices — not one phantom padded slice whose
    # (col 0, value 0) entries would gather out of bounds from an empty x
    num_slices = (m + C - 1) // C
    row_nnz = np.diff(indptr) if m else np.zeros(0, np.int64)

    slice_cols = np.zeros(num_slices, np.int32)
    for s in range(num_slices):
        rows = row_nnz[s * C : min((s + 1) * C, m)]
        w = int(rows.max()) if rows.size else 0
        # pad to stride_factor (Ginkgo's stride alignment), at least one column
        w = max(w, 1)
        slice_cols[s] = ((w + stride_factor - 1) // stride_factor) * stride_factor

    slice_sets = np.zeros(num_slices + 1, np.int32)
    slice_sets[1:] = np.cumsum(slice_cols)
    total = int(slice_sets[-1]) * C

    cols = np.zeros(total, np.int32)
    vals = np.zeros(total, values.dtype)
    for s in range(num_slices):
        base = slice_sets[s] * C
        for r in range(C):
            row = s * C + r
            if row >= m:
                continue
            n = row_nnz[row]
            src = slice(indptr[row], indptr[row] + n)
            # column-major within slice: entry (col j, row r) at base + j*C + r
            dst = base + np.arange(n) * C + r
            cols[dst] = indices[src]
            vals[dst] = values[src]
    return Sellp(
        col_idx=jnp.asarray(cols),
        values=jnp.asarray(vals),
        slice_sets=jnp.asarray(slice_sets),
        slice_cols=jnp.asarray(slice_cols),
        shape=tuple(shape),
        slice_size=C,
        stride_factor=stride_factor,
        max_slice_cols=int(slice_cols.max()) if num_slices else 0,
    )


def sellp_from_dense(a: np.ndarray, slice_size=8, stride_factor=8) -> Sellp:
    c = csr_from_dense(a)
    return sellp_from_csr_host(
        np.asarray(c.indptr),
        np.asarray(c.indices),
        np.asarray(c.values),
        c.shape,
        slice_size=slice_size,
        stride_factor=stride_factor,
    )


# -- host-side conversion between formats (gko ConvertibleTo) ------------------


def csr_host_arrays(A) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(indptr, indices, values)`` numpy triplet for any format (host-side).

    Setup-time extraction (Ginkgo's ``convert_to`` hub format): explicit
    stored zeros in the padded formats (ELL / SELL-P padding slots) are
    dropped — they are storage artifacts, not matrix entries.
    """
    if isinstance(A, Csr):
        return (
            np.asarray(A.indptr, np.int64),
            np.asarray(A.indices, np.int64),
            np.asarray(A.values),
        )
    if isinstance(A, Coo):
        r = np.asarray(A.row_idx)
        c = np.asarray(A.col_idx)
        v = np.asarray(A.values)
        m = A.shape[0]
        indptr = np.zeros(m + 1, np.int64)
        np.add.at(indptr, r + 1, 1)
        return np.cumsum(indptr), c.astype(np.int64), v
    if isinstance(A, Dense):
        a = np.asarray(A.values)
        r, c = np.nonzero(a)
        m = a.shape[0]
        indptr = np.zeros(m + 1, np.int64)
        np.add.at(indptr, r + 1, 1)
        return np.cumsum(indptr), c.astype(np.int64), a[r, c]
    if isinstance(A, Ell):
        cols = np.asarray(A.col_idx)
        vals = np.asarray(A.values)
        keep = vals != 0
        m = A.shape[0]
        counts = keep.sum(axis=1)
        indptr = np.zeros(m + 1, np.int64)
        indptr[1:] = np.cumsum(counts)
        return indptr, cols[keep].astype(np.int64), vals[keep]
    if isinstance(A, Sellp):
        m = A.shape[0]
        C = A.slice_size
        slice_sets = np.asarray(A.slice_sets)
        cols = np.asarray(A.col_idx)
        vals = np.asarray(A.values)
        rows_c, rows_v = [[] for _ in range(m)], [[] for _ in range(m)]
        for s in range(A.num_slices):
            lo, hi = int(slice_sets[s]), int(slice_sets[s + 1])
            width = hi - lo
            bc = cols[lo * C : hi * C].reshape(width, C)
            bv = vals[lo * C : hi * C].reshape(width, C)
            for r in range(min(C, m - s * C)):
                keep = bv[:, r] != 0
                rows_c[s * C + r].extend(bc[keep, r].tolist())
                rows_v[s * C + r].extend(bv[keep, r].tolist())
        counts = np.array([len(rc) for rc in rows_c], np.int64)
        indptr = np.zeros(m + 1, np.int64)
        indptr[1:] = np.cumsum(counts)
        indices = (
            np.asarray([c for rc in rows_c for c in rc], np.int64)
            if indptr[-1]
            else np.zeros(0, np.int64)
        )
        values = (
            np.asarray([v for rv in rows_v for v in rv], vals.dtype)
            if indptr[-1]
            else np.zeros(0, vals.dtype)
        )
        return indptr, indices, values
    raise TypeError(f"cannot extract a CSR triplet from {type(A)}")


def csr_slice_rows_host(
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    lo: int,
    hi: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row block ``[lo, hi)`` of a host CSR triplet (setup time).

    The partition-aware split primitive behind the distributed formats: the
    returned triplet is a self-contained CSR over ``hi - lo`` rows (indptr
    rebased to 0), with column indices untouched (still global) and per-row
    entry order preserved.
    """
    indptr = np.asarray(indptr)
    if not (0 <= lo <= hi <= len(indptr) - 1):
        raise ValueError(
            f"row range [{lo}, {hi}) outside [0, {len(indptr) - 1})"
        )
    start, stop = int(indptr[lo]), int(indptr[hi])
    return (
        (indptr[lo : hi + 1] - start).astype(np.int64),
        np.asarray(indices)[start:stop].astype(np.int64),
        np.asarray(values)[start:stop],
    )


_CONVERT_TARGETS = {
    "coo": Coo,
    "csr": Csr,
    "ell": Ell,
    "sellp": Sellp,
    "dense": Dense,
}


def convert(A, target, **kwargs):
    """Convert any format to another — Ginkgo's ``ConvertibleTo`` surface.

    ``target`` is a format class or name (``"coo"`` / ``"csr"`` / ``"ell"`` /
    ``"sellp"`` / ``"dense"``); ``kwargs`` forward to the target constructor
    (``slice_size`` / ``stride_factor`` for SELL-P, ``max_nnz`` for ELL).
    Conversion routes host-side through the CSR triplet (setup time) and
    drops explicit stored zeros, matching the from-dense constructors.
    """
    if isinstance(target, str):
        try:
            target = _CONVERT_TARGETS[target.lower()]
        except KeyError:
            raise KeyError(
                f"unknown format {target!r}; known: {sorted(_CONVERT_TARGETS)}"
            ) from None
    if type(A) is target and not kwargs:
        return A
    indptr, indices, values = csr_host_arrays(A)
    m, n = A.shape
    if target is Csr:
        return Csr(
            indptr=jnp.asarray(indptr, jnp.int32),
            indices=jnp.asarray(indices, jnp.int32),
            values=jnp.asarray(values),
            shape=(m, n),
        )
    if target is Coo:
        rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        return Coo(
            row_idx=jnp.asarray(rows, jnp.int32),
            col_idx=jnp.asarray(indices, jnp.int32),
            values=jnp.asarray(values),
            shape=(m, n),
        )
    if target is Ell:
        return ell_from_csr_host(indptr, indices, values, (m, n), **kwargs)
    if target is Sellp:
        return sellp_from_csr_host(indptr, indices, values, (m, n), **kwargs)
    if target is Dense:
        rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        out = np.zeros((m, n), values.dtype if values.size else np.dtype(A.dtype))
        np.add.at(out, (rows, indices), values)
        return Dense(jnp.asarray(out))
    raise TypeError(f"unknown conversion target {target!r}")
