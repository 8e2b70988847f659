"""Mesh-sharded vectors — gko::experimental::distributed::Vector.

A :class:`DistVector` is the padded shard stack of a global vector: shape
``(P, Lmax)`` with one row per part of the partition and padding slots zeroed
(see :class:`~repro.distributed.partition.Partition`).  BLAS-1 runs under
``shard_map`` over the data axis: ``axpy``/``scal`` are purely shard-local,
``dot``/``norm2`` reduce locally through the executor-dispatched kernels and
then ``psum`` — with padding masked via
:func:`repro.distributed.sharding.zero_shard_padding`, so a ragged partition
never double-counts (the padded-shard bug this module's tests pin).

These are the same reduction semantics the distributed solvers get from
``repro.sparse.ops.distributed_blas``; the module-level functions here are
the standalone surface (parity tests, drivers).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.partition import Partition

__all__ = [
    "DistVector",
    "dist_dot",
    "dist_norm2",
    "dist_axpy",
    "dist_scal",
]


@dataclasses.dataclass(frozen=True)
class DistVector:
    """Padded shard stack of a global vector (+ its partition)."""

    local: jax.Array  # (P, Lmax); padding slots zero by construction
    partition: Partition  # static

    @classmethod
    def from_global(cls, x, partition: Partition) -> "DistVector":
        return cls(local=partition.pad(x), partition=partition)

    def to_global(self) -> jax.Array:
        return self.partition.unpad(self.local)

    @property
    def shape(self) -> Tuple[int]:
        return (self.partition.global_size,)

    @property
    def dtype(self):
        return self.local.dtype


jax.tree_util.register_dataclass(
    DistVector, data_fields=["local"], meta_fields=["partition"]
)


def _check_same_partition(x: DistVector, y: DistVector):
    if x.partition != y.partition:
        # two stacks can agree in shape while laying out different global
        # rows per slot — pairing them would be silently wrong, not an error
        raise ValueError(
            f"DistVector partitions differ ({x.partition.offsets} vs "
            f"{y.partition.offsets}); repartition one operand first"
        )


def _shard_map_blas(partition: Partition, body, *operands):
    """Run a per-shard BLAS body over the partition's mesh.

    ``body(mask_l, *shard_operands)`` receives this shard's pad mask plus the
    operands sliced to leading part-axis size 1; scalar results come back
    stacked ``(P,)`` (identical across shards after the psum).
    """
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_shard_mesh
    from repro.distributed.matrix import DATA_AXIS

    mesh = make_shard_mesh(partition.num_parts, DATA_AXIS)
    mask = jnp.asarray(partition.pad_mask)
    args = (mask,) + operands
    specs = tuple(P(DATA_AXIS, *([None] * (a.ndim - 1))) for a in args)
    return jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=P(DATA_AXIS))(
        *args
    )


def dist_dot(x: DistVector, y: DistVector, *, executor=None) -> jax.Array:
    """Global ``<x, y>`` via per-shard dispatched dot + ``psum``."""
    from repro.sparse import ops as sparse_ops
    from repro.distributed.matrix import DATA_AXIS

    _check_same_partition(x, y)

    def body(m_l, x_l, y_l):
        with sparse_ops.distributed_blas(DATA_AXIS, m_l[0]):
            return sparse_ops.dot(x_l[0], y_l[0], executor=executor)[None]

    return _shard_map_blas(x.partition, body, x.local, y.local)[0]


def dist_norm2(x: DistVector, *, executor=None) -> jax.Array:
    """Global ``||x||_2`` via per-shard masked sum of squares + ``psum``."""
    from repro.sparse import ops as sparse_ops
    from repro.distributed.matrix import DATA_AXIS

    def body(m_l, x_l):
        with sparse_ops.distributed_blas(DATA_AXIS, m_l[0]):
            return sparse_ops.norm2(x_l[0], executor=executor)[None]

    return _shard_map_blas(x.partition, body, x.local)[0]


def dist_axpy(alpha, x: DistVector, y: DistVector, *, executor=None) -> DistVector:
    """``alpha * x + y`` — shard-local, no communication."""
    from repro.sparse import ops as sparse_ops

    _check_same_partition(x, y)
    return dataclasses.replace(
        y, local=sparse_ops.axpy(alpha, x.local, y.local, executor=executor)
    )


def dist_scal(alpha, x: DistVector, *, executor=None) -> DistVector:
    """``alpha * x`` — shard-local, no communication."""
    from repro.sparse import ops as sparse_ops

    return dataclasses.replace(
        x, local=sparse_ops.scal(alpha, x.local, executor=executor)
    )
