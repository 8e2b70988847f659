"""Mesh-sharded matrix formats — gko::experimental::distributed::Matrix.

A distributed matrix row-partitions a square operator ``A`` into one shard
per part of a :class:`~repro.distributed.partition.Partition`.  Each shard
stores TWO blocks (exactly Ginkgo's local/non-local decomposition):

* the **local** block — columns inside the shard's own row range, with
  column indices rebased to the shard, applied against the shard's own
  ``x`` chunk with no communication;
* the **halo** (non-local) block — columns owned by other shards, compressed
  onto the shard's *halo column set* (the unique remote columns it touches),
  applied against the gathered remote entries.

The local block is further split row-wise at partition time into an
**interior** class (rows touching no halo column) and a **boundary** class
(rows that do): the apply issues the halo ``all_gather`` first and runs the
interior SpMV while the collective is in flight — halo-exchange/compute
overlap, with the row classification decided once on the host.

SpMV is then ``y_p = A_int_p x_p + A_bnd_p x_p + A_halo_p
gather(x)[halo_cols_p]`` under ``shard_map`` over the mesh data axis: one
``all_gather`` of the padded ``x`` shards per apply, followed by the
host-precomputed halo-column gather.
Both block SpMVs dispatch through the ordinary format registry, so every
shard's local kernel still resolves tile geometry via
``Executor.launch_config`` — the per-target tuning tables apply per shard.

Shards are padded to uniform shapes (rows to ``Lmax``, nnz/halo widths to the
per-matrix maxima) so the whole matrix is one stacked pytree with a leading
part axis — shardable with a single ``P("data", ...)`` spec.  Padding follows
the repo's predication-free convention: index 0 + value 0 (in-bounds gather,
zero contribution).
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.linop import LinOp, MatrixFreeOp
from repro.observability import trace
from repro.distributed.partition import Partition
from repro.sparse.formats import (
    Csr,
    Ell,
    csr_host_arrays,
    csr_slice_rows_host,
)

__all__ = ["DistLinOp", "DistCsr", "DistEll", "split_by_rows", "shard_specs"]

#: the mesh axis every distributed operator shards over
DATA_AXIS = "data"


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields)
    )
    return cls


def shard_specs(tree):
    """PartitionSpec pytree sharding every leaf's leading part axis."""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda l: P(DATA_AXIS, *([None] * (l.ndim - 1))), tree
    )


# =============================================================================
# Host-side split (setup time, numpy) — Ginkgo's build_local_nonlocal
# =============================================================================


def split_by_rows(indptr, indices, values, partition: Partition) -> List[dict]:
    """Split a host CSR triplet into per-part local + halo blocks.

    Returns one dict per part with keys ``local`` (CSR triplet over the
    shard's square diagonal block, columns rebased), ``halo`` (CSR triplet
    whose columns index into ``halo_cols``), and ``halo_cols`` (sorted unique
    global columns this part needs from other parts).

    The local block is additionally classified row-wise for the
    overlap-capable formats: ``interior`` holds the local entries of rows
    that touch NO halo column (computable before any communication lands)
    and ``boundary`` the local entries of rows that do.  The two are
    row-disjoint and together exactly the ``local`` triplet — the
    compute/communication overlap split, decided once at partition time.
    """
    indptr = np.asarray(indptr, np.int64)
    parts = []
    for p in range(partition.num_parts):
        lo, hi = partition.range_of(p)
        ip, j, v = csr_slice_rows_host(indptr, indices, values, lo, hi)
        rows = np.repeat(np.arange(hi - lo, dtype=np.int64), np.diff(ip))
        is_local = (j >= lo) & (j < hi)

        def _triplet(sel, cols):
            counts = np.zeros(hi - lo + 1, np.int64)
            np.add.at(counts, rows[sel] + 1, 1)
            return (np.cumsum(counts), cols, v[sel])

        has_halo = np.zeros(hi - lo, bool)
        has_halo[rows[~is_local]] = True
        is_int = is_local & ~has_halo[rows]
        is_bnd = is_local & has_halo[rows]
        halo_cols = np.unique(j[~is_local])
        parts.append(
            {
                "local": _triplet(is_local, j[is_local] - lo),
                "interior": _triplet(is_int, j[is_int] - lo),
                "boundary": _triplet(is_bnd, j[is_bnd] - lo),
                "halo": _triplet(
                    ~is_local, np.searchsorted(halo_cols, j[~is_local])
                ),
                "halo_cols": halo_cols,
            }
        )
    return parts


def _stack_csr(triplets, n_rows_pad: int, pad_nnz: int):
    """Stack per-part CSR triplets into padded (P, ...) arrays."""
    P = len(triplets)
    indptr = np.zeros((P, n_rows_pad + 1), np.int32)
    indices = np.zeros((P, pad_nnz), np.int32)
    values = None
    for p, (ip, j, v) in enumerate(triplets):
        if values is None:
            values = np.zeros((P, pad_nnz), v.dtype)
        rows = len(ip) - 1
        indptr[p, : rows + 1] = ip
        indptr[p, rows + 1 :] = ip[-1]  # padding rows are empty
        indices[p, : len(j)] = j
        values[p, : len(v)] = v
    return indptr, indices, values


def _ell_arrays(ip, j, v, n_rows_pad: int, k: int):
    """One part's CSR triplet -> padded row-major ELL arrays."""
    cols = np.zeros((n_rows_pad, k), np.int32)
    vals = np.zeros((n_rows_pad, k), v.dtype)
    # entry t of the CSR stream lands at (row[t], t - ip[row[t]])
    rows = np.repeat(np.arange(len(ip) - 1, dtype=np.int64), np.diff(ip))
    pos = np.arange(len(j), dtype=np.int64) - np.asarray(ip)[:-1][rows]
    cols[rows, pos] = j
    vals[rows, pos] = v
    return cols, vals


# =============================================================================
# The distributed LinOp base
# =============================================================================


class DistLinOp(LinOp):
    """Base of the mesh-sharded operators (gko::experimental::distributed).

    Subclasses are stacked pytrees whose array leaves carry a leading part
    axis; ``local_operator`` builds the per-shard operator INSIDE a
    ``shard_map`` body (leaves sliced to leading size 1), and the global
    ``_apply`` wraps exactly that body in ``shard_map`` over the data axis —
    so ``A @ x`` on a replicated global vector and a sharded solver iteration
    run the same per-shard code.
    """

    is_distributed = True
    axis_name = DATA_AXIS

    #: ordered value-array field names (first one defines the dtype)
    _value_fields: Tuple[str, ...] = ()

    # -- subclass surface: per-shard apply pieces ------------------------------
    def _local_blocks(self, executor):
        """(interior, boundary_or_None, halo_block_or_None, halo_map) for THIS
        shard.  ``boundary``/``halo`` are ``None`` when the shard touches no
        remote column (then ``interior`` is the whole diagonal block)."""
        raise NotImplementedError

    def local_operator(self, executor=None) -> LinOp:
        """Per-shard operator; each part of its matvec runs in a named scope
        (``<ClassName>.halo_exchange``, ``.interior``, ``.boundary``,
        ``.halo``), which names its device ops on a profiler trace."""
        part = self.partition
        Lmax = part.max_part_size
        interior, boundary, halo, halo_map = self._local_blocks(executor)
        cls = type(self).__name__

        def matvec(x_l):
            from repro.sparse import ops as sparse_ops

            if halo is None:
                with jax.named_scope(f"{cls}.interior"):
                    return sparse_ops.apply(interior, x_l, executor=executor)
            # issue the collective FIRST, then the interior SpMV: interior
            # rows touch no halo column, so XLA's latency-hiding scheduler is
            # free to run that matvec while the all_gather is in flight; only
            # the boundary/halo contributions wait on the gathered x.
            with jax.named_scope(f"{cls}.halo_exchange"):
                xg = jax.lax.all_gather(x_l, self.axis_name, tiled=True)
            with jax.named_scope(f"{cls}.interior"):
                y = sparse_ops.apply(interior, x_l, executor=executor)
            with jax.named_scope(f"{cls}.boundary"):
                y = y + sparse_ops.apply(boundary, x_l, executor=executor)
            with jax.named_scope(f"{cls}.halo_exchange"):
                x_halo = xg[halo_map]
            with jax.named_scope(f"{cls}.halo"):
                return y + sparse_ops.apply(halo, x_halo, executor=executor)

        return MatrixFreeOp(matvec, shape=(Lmax, Lmax), dtype=self.dtype)

    # -- the global apply (replicated global vector in / out) ------------------
    def _apply(self, x, executor):
        from repro.launch.mesh import make_shard_mesh
        from jax.sharding import PartitionSpec as P

        part = self.partition
        mesh = make_shard_mesh(part.num_parts, self.axis_name)
        leaves, treedef = jax.tree_util.tree_flatten(self)
        xp = part.pad(x)

        def body(shard_leaves, x_l):
            shard = jax.tree_util.tree_unflatten(treedef, shard_leaves)
            op = shard.local_operator(executor=executor)
            return op.apply(x_l[0])[None]

        vec_spec = P(self.axis_name, *([None] * (xp.ndim - 1)))
        yp = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(shard_specs(leaves), vec_spec),
            out_specs=vec_spec,
            # the local SpMV may be a Pallas kernel, which carries no
            # varying-axes types; the output is sharded, not replicated
            check_vma=False,
        )(leaves, xp)
        return part.unpad(yp)

    # -- common reporting ------------------------------------------------------
    @property
    def dtype(self):
        return getattr(self, self._value_fields[0]).dtype

    @property
    def memory_bytes(self) -> int:
        return sum(
            int(l.size) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(self)
        )

    @property
    def num_halo_cols(self) -> Tuple[int, ...]:
        """Per-part halo-column-set sizes (communication volume metric)."""
        return self._halo_counts

    def astype(self, dtype) -> "DistLinOp":
        return dataclasses.replace(
            self,
            **{
                f: getattr(self, f).astype(dtype)
                for f in self._value_fields
            },
        )


def _halo_map_padded(parts, partition: Partition) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Stack per-part halo column sets as padded-global gather indices."""
    counts = tuple(len(p["halo_cols"]) for p in parts)
    h_max = max(counts) if counts else 0
    halo_map = np.zeros((partition.num_parts, h_max), np.int32)
    for p, info in enumerate(parts):
        cols = info["halo_cols"]
        # padded-global coordinates: what an all_gather of padded x shards
        # yields; padding entries point at slot 0 and pair with zero values
        halo_map[p, : len(cols)] = partition.padded_index(cols)
    return halo_map, counts


# =============================================================================
# DistCsr
# =============================================================================


@dataclasses.dataclass(frozen=True)
class DistCsr(DistLinOp):
    """Row-partitioned CSR: per-shard interior + boundary + halo CSR blocks.

    The diagonal (local) block is stored split by row class — ``int_*`` for
    rows touching no halo column, ``bnd_*`` for rows that do — so the apply
    can run the interior SpMV while the halo ``all_gather`` is in flight.
    """

    int_indptr: jax.Array  # (P, Lmax+1) i32
    int_indices: jax.Array  # (P, K_int) i32, shard-local columns
    int_values: jax.Array  # (P, K_int)
    bnd_indptr: jax.Array  # (P, Lmax+1) i32
    bnd_indices: jax.Array  # (P, K_bnd) i32, shard-local columns
    bnd_values: jax.Array  # (P, K_bnd)
    halo_indptr: jax.Array  # (P, Lmax+1) i32
    halo_indices: jax.Array  # (P, K_halo) i32, into the halo column set
    halo_values: jax.Array  # (P, K_halo)
    halo_map: jax.Array  # (P, H_max) i32, padded-global gather indices
    shape: Tuple[int, int]  # static (n, n)
    nnz: int  # static — true nonzeros (flops metric)
    partition: Partition  # static
    _halo_counts: Tuple[int, ...]  # static — true halo sizes per part

    _value_fields = ("int_values", "bnd_values", "halo_values")

    @classmethod
    def from_matrix(cls, A, partition: Partition) -> "DistCsr":
        indptr, indices, values, n = _square_host_csr(A, partition)
        parts = split_by_rows(indptr, indices, values, partition)
        Lmax = partition.max_part_size
        k_int = max(1, max(len(p["interior"][2]) for p in parts))
        k_bnd = max(1, max(len(p["boundary"][2]) for p in parts))
        k_halo = max(1, max(len(p["halo"][2]) for p in parts))
        ii, ij, iv = _stack_csr([p["interior"] for p in parts], Lmax, k_int)
        bi, bj, bv = _stack_csr([p["boundary"] for p in parts], Lmax, k_bnd)
        hi_, hj, hv = _stack_csr([p["halo"] for p in parts], Lmax, k_halo)
        halo_map, counts = _halo_map_padded(parts, partition)
        return cls(
            int_indptr=jnp.asarray(ii),
            int_indices=jnp.asarray(ij),
            int_values=jnp.asarray(iv),
            bnd_indptr=jnp.asarray(bi),
            bnd_indices=jnp.asarray(bj),
            bnd_values=jnp.asarray(bv),
            halo_indptr=jnp.asarray(hi_),
            halo_indices=jnp.asarray(hj),
            halo_values=jnp.asarray(hv),
            halo_map=jnp.asarray(halo_map),
            shape=(n, n),
            nnz=int(len(values)),
            partition=partition,
            _halo_counts=counts,
        )

    def local_block(self, p: int) -> Csr:
        """Part ``p``'s padded square diagonal block as a plain Csr.

        Re-merges the interior/boundary row classes (row-disjoint by
        construction) into one CSR on the host — the shape the per-shard
        preconditioner generators expect.
        """
        L = self.partition.max_part_size
        iip = np.asarray(self.int_indptr[p], np.int64)
        bip = np.asarray(self.bnd_indptr[p], np.int64)
        ij = np.asarray(self.int_indices[p])[: iip[-1]]
        iv = np.asarray(self.int_values[p])[: iip[-1]]
        bj = np.asarray(self.bnd_indices[p])[: bip[-1]]
        bv = np.asarray(self.bnd_values[p])[: bip[-1]]
        rows = np.concatenate(
            [
                np.repeat(np.arange(L, dtype=np.int64), np.diff(iip)),
                np.repeat(np.arange(L, dtype=np.int64), np.diff(bip)),
            ]
        )
        order = np.argsort(rows, kind="stable")
        indptr = np.cumsum(
            np.concatenate([[0], np.diff(iip) + np.diff(bip)])
        ).astype(np.int32)
        return Csr(
            jnp.asarray(indptr),
            jnp.asarray(np.concatenate([ij, bj])[order]),
            jnp.asarray(np.concatenate([iv, bv])[order]),
            shape=(L, L),
        )

    def _local_blocks(self, executor):
        L = self.partition.max_part_size
        h_max = self.halo_map.shape[-1]
        interior = Csr(
            self.int_indptr[0], self.int_indices[0], self.int_values[0],
            shape=(L, L),
        )
        if h_max == 0:
            return interior, None, None, None
        boundary = Csr(
            self.bnd_indptr[0], self.bnd_indices[0], self.bnd_values[0],
            shape=(L, L),
        )
        halo = Csr(
            self.halo_indptr[0], self.halo_indices[0], self.halo_values[0],
            shape=(L, h_max),
        )
        return interior, boundary, halo, self.halo_map[0]


_register(
    DistCsr,
    [
        "int_indptr", "int_indices", "int_values",
        "bnd_indptr", "bnd_indices", "bnd_values",
        "halo_indptr", "halo_indices", "halo_values", "halo_map",
    ],
    ["shape", "nnz", "partition", "_halo_counts"],
)


# =============================================================================
# DistEll
# =============================================================================


@dataclasses.dataclass(frozen=True)
class DistEll(DistLinOp):
    """Row-partitioned ELL: per-shard interior + boundary + halo ELL blocks.

    Padding entries use the format's own (col 0, value 0) convention in both
    the shard-local and halo-column index spaces.  As in :class:`DistCsr`,
    the diagonal block is split row-wise into interior (no halo columns in
    the row) and boundary classes so the interior SpMV overlaps the halo
    ``all_gather``; each class carries its own ELL width (``k_int`` /
    ``k_bnd``), so the split often *shrinks* stored bytes when boundary rows
    are the long ones.
    """

    int_col_idx: jax.Array  # (P, Lmax, k_int) i32
    int_values: jax.Array  # (P, Lmax, k_int)
    bnd_col_idx: jax.Array  # (P, Lmax, k_bnd) i32
    bnd_values: jax.Array  # (P, Lmax, k_bnd)
    halo_col_idx: jax.Array  # (P, Lmax, k_halo) i32, into the halo column set
    halo_values: jax.Array  # (P, Lmax, k_halo)
    halo_map: jax.Array  # (P, H_max) i32
    shape: Tuple[int, int]
    nnz: int
    partition: Partition
    _halo_counts: Tuple[int, ...]

    _value_fields = ("int_values", "bnd_values", "halo_values")

    @classmethod
    def from_matrix(cls, A, partition: Partition) -> "DistEll":
        with trace.span("DistEll.from_matrix", cat="distributed"):
            return cls._from_matrix(A, partition)

    @classmethod
    def _from_matrix(cls, A, partition: Partition) -> "DistEll":
        indptr, indices, values, n = _square_host_csr(A, partition)
        parts = split_by_rows(indptr, indices, values, partition)
        Lmax = partition.max_part_size

        def max_row_nnz(key):
            return max(
                1,
                max(
                    (int(np.diff(p[key][0]).max()) if len(p[key][0]) > 1 else 0)
                    for p in parts
                ),
            )

        k_int, k_bnd = max_row_nnz("interior"), max_row_nnz("boundary")
        k_halo = max_row_nnz("halo")
        ic = np.zeros((partition.num_parts, Lmax, k_int), np.int32)
        iv = np.zeros((partition.num_parts, Lmax, k_int), values.dtype)
        bc = np.zeros((partition.num_parts, Lmax, k_bnd), np.int32)
        bv = np.zeros((partition.num_parts, Lmax, k_bnd), values.dtype)
        hc = np.zeros((partition.num_parts, Lmax, k_halo), np.int32)
        hv = np.zeros((partition.num_parts, Lmax, k_halo), values.dtype)
        for p, info in enumerate(parts):
            ic[p], iv[p] = _ell_arrays(*info["interior"], Lmax, k_int)
            bc[p], bv[p] = _ell_arrays(*info["boundary"], Lmax, k_bnd)
            hc[p], hv[p] = _ell_arrays(*info["halo"], Lmax, k_halo)
        halo_map, counts = _halo_map_padded(parts, partition)
        return cls(
            int_col_idx=jnp.asarray(ic),
            int_values=jnp.asarray(iv),
            bnd_col_idx=jnp.asarray(bc),
            bnd_values=jnp.asarray(bv),
            halo_col_idx=jnp.asarray(hc),
            halo_values=jnp.asarray(hv),
            halo_map=jnp.asarray(halo_map),
            shape=(n, n),
            nnz=int(len(values)),
            partition=partition,
            _halo_counts=counts,
        )

    def local_block(self, p: int) -> Ell:
        # interior and boundary are row-disjoint; concatenating along the
        # width axis re-merges them (the inactive class contributes only
        # (col 0, value 0) padding slots — zero by the ELL convention)
        L = self.partition.max_part_size
        return Ell(
            jnp.concatenate([self.int_col_idx[p], self.bnd_col_idx[p]], axis=1),
            jnp.concatenate([self.int_values[p], self.bnd_values[p]], axis=1),
            shape=(L, L),
        )

    def _local_blocks(self, executor):
        L = self.partition.max_part_size
        h_max = self.halo_map.shape[-1]
        interior = Ell(self.int_col_idx[0], self.int_values[0], shape=(L, L))
        if h_max == 0:
            return interior, None, None, None
        boundary = Ell(self.bnd_col_idx[0], self.bnd_values[0], shape=(L, L))
        halo = Ell(self.halo_col_idx[0], self.halo_values[0], shape=(L, h_max))
        return interior, boundary, halo, self.halo_map[0]


_register(
    DistEll,
    [
        "int_col_idx", "int_values", "bnd_col_idx", "bnd_values",
        "halo_col_idx", "halo_values", "halo_map",
    ],
    ["shape", "nnz", "partition", "_halo_counts"],
)


def _square_host_csr(A, partition: Partition):
    """Validate + extract the host CSR triplet of a square operand."""
    m, n = A.shape
    if m != n:
        raise ValueError(
            f"distributed formats row-partition SQUARE operators, got {A.shape}"
        )
    if partition.global_size != n:
        raise ValueError(
            f"partition covers {partition.global_size} rows but A has {n}"
        )
    indptr, indices, values = csr_host_arrays(A)
    return indptr, indices, values, n
