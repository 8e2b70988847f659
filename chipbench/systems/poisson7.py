"""The 7-point 3-D Poisson operator, written plainly: the yardstick's copy.

``L`` is the finite-difference Laplacian on an ``n_side``³ grid with
Dirichlet boundaries (diagonal 6, ``-1`` for each of the six neighbours
inside the grid), row-major over ``(i, j, k)``.  A configuration may shift
its diagonal: ``A = L + c I`` is one backward-Euler step of the heat
equation with ``c = 1 / dt``.

:func:`build` makes the CSR arrays the program is handed, rows in order and
columns ascending within each row, with no sort.  :func:`device_operator`
is the same operator as shifted slices in ``jax.numpy``, which the control
runs.  Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: stencil points in ascending column order: (axis, step); axis 3 is the diagonal
_POINTS = ((0, -1), (1, -1), (2, -1), (3, 0), (2, 1), (1, 1), (0, 1))
DIAGONAL = 6.0
#: number of symmetry images :meth:`HostCsr.image` knows
IMAGES = 32


@dataclasses.dataclass(frozen=True)
class HostCsr:
    """Row-ordered host CSR with the diagonal's position in every row."""

    indptr: np.ndarray  # (n + 1,) int64
    indices: np.ndarray  # (nnz,) int32
    values: np.ndarray  # (nnz,) float32, diagonal 6
    diag_pos: np.ndarray  # (n,) int64
    n_side: int
    images = IMAGES

    @property
    def n(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return self.indices.shape[0]

    def image(self, x: np.ndarray, k: int) -> np.ndarray:
        """Image ``k`` (of :data:`IMAGES`) of a grid vector under a symmetry
        of the operator: bits 0-2 reflect ``i``, ``j``, ``k``; bit 3 swaps
        ``i`` and ``j``; bit 4 flips the sign.  Each maps runs of 8 along
        ``k`` onto such runs when 8 divides ``n_side``, so block-Jacobi with
        blocks of 8 (or fewer dividing ``n_side``) is mapped onto itself
        too, and CG does the same work on every image."""
        u = x.reshape(self.n_side, self.n_side, self.n_side)
        for axis in range(3):
            if k >> axis & 1:
                u = np.flip(u, axis)
        if k >> 3 & 1:
            u = u.transpose(1, 0, 2)
        u = -u if k >> 4 & 1 else u
        return np.ascontiguousarray(u).reshape(-1)

    def shifted_values(self, shift: float) -> np.ndarray:
        """Values of ``L + shift I``, in the stored precision."""
        if shift == 0.0:
            return self.values
        values = self.values.copy()
        values[self.diag_pos] = np.float32(DIAGONAL + shift)
        return values


def host_csr(n_side: int) -> HostCsr:
    """``L`` on an ``n_side``³ grid as row-ordered CSR (float32 values)."""
    s = int(n_side)
    n = s ** 3
    idx = np.arange(n, dtype=np.int32)
    coord = (idx // (s * s), (idx // s) % s, idx % s)
    stride = (s * s, s, 1)
    cols = np.empty((n, len(_POINTS)), np.int32)
    keep = np.ones((n, len(_POINTS)), bool)
    for t, (axis, step) in enumerate(_POINTS):
        if axis == 3:
            cols[:, t] = idx
            continue
        cols[:, t] = idx + step * stride[axis]
        keep[:, t] = coord[axis] > 0 if step < 0 else coord[axis] < s - 1
    vals = np.full(cols.shape, -1.0, np.float32)
    vals[:, 3] = DIAGONAL
    counts = keep.sum(axis=1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    diag_pos = indptr[:-1] + keep[:, :3].sum(axis=1)
    return HostCsr(indptr, cols[keep], vals[keep], diag_pos, s)


def build(spec: dict) -> HostCsr:
    """The operator a configuration's ``system`` names; the ``rows`` and
    ``nnz`` it states, where it states them, have to be the operator's."""
    s = host_csr(int(spec["n_side"]))
    for key, have in (("rows", s.n), ("nnz", s.nnz)):
        if key in spec and int(spec[key]) != have:
            raise ValueError(f"system states {key} {spec[key]}, the grid has {have}")
    return s


def device_operator(spec: dict, shift: float):
    """``x -> (L + shift I) x`` on the device, in ``x``'s dtype."""
    n_side = int(spec["n_side"])
    return lambda x: device_apply(x, n_side, DIAGONAL + shift)


def device_apply(x, n_side: int, diagonal):
    """``(diagonal I - neighbours) x`` as shifted slices, in ``x``'s dtype."""
    import jax.numpy as jnp

    u = x.reshape(n_side, n_side, n_side)
    y = jnp.asarray(diagonal, x.dtype) * u
    for axis in range(3):
        lo = [(0, 0)] * 3
        hi = [(0, 0)] * 3
        lo[axis] = (1, 0)
        hi[axis] = (0, 1)
        head = [slice(None)] * 3
        tail = [slice(None)] * 3
        head[axis] = slice(None, -1)
        tail[axis] = slice(1, None)
        y = y - jnp.pad(u[tuple(head)], lo) - jnp.pad(u[tuple(tail)], hi)
    return y.reshape(-1)
