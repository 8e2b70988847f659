"""Shard-local preconditioners for distributed solves.

Distributed block-Jacobi is block-local BY CONSTRUCTION: blocks never
straddle a shard boundary, so each shard generates its preconditioner from
its own padded diagonal block and applies it with zero communication — the
standard distributed Jacobi semantics (and exactly how Ginkgo applies
``preconditioner::Jacobi`` to a ``distributed::Matrix``: on the local block).

Generation is host-side per part, reusing the single-device generators
(:func:`repro.solvers.common.jacobi_preconditioner`,
:func:`repro.precond.block_jacobi`) on each shard's padded local block;
padding rows carry a zero diagonal, which both generators regularize to an
identity action — harmless, since padded residual slots are zero and every
cross-shard reduction is masked anyway.

``adaptive`` storage: an explicit storage dtype is supported (uniform across
shards, so the stacked pytree stays rectangular); the per-block
condition-rule ``adaptive=True`` is rejected — it would pick different
precision-class splits per shard and the stack would go ragged.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.linop import LinOp
from repro.distributed.partition import Partition

__all__ = ["DistScalarJacobi", "DistBlockJacobi", "dist_preconditioner"]


@dataclasses.dataclass(frozen=True)
class DistScalarJacobi(LinOp):
    """Stacked per-shard scalar Jacobi: ``M^-1 v = inv_diag * v`` per shard."""

    inv_diag: jax.Array  # (P, Lmax)
    partition: Partition  # static

    is_distributed = True

    @property
    def shape(self):
        n = self.partition.global_size
        return (n, n)

    @property
    def dtype(self):
        return self.inv_diag.dtype

    @property
    def storage_bytes(self) -> int:
        return int(self.inv_diag.size) * self.inv_diag.dtype.itemsize

    def local_operator(self, executor=None) -> LinOp:
        from repro.solvers.common import ScalarJacobi

        return ScalarJacobi(self.inv_diag[0])

    def _apply(self, v, executor):
        # global-vector apply (outside shard_map): purely diagonal, so pad /
        # multiply / unpad needs no collective at all
        part = self.partition
        return part.unpad(self.inv_diag.astype(v.dtype) * part.pad(v))


jax.tree_util.register_dataclass(
    DistScalarJacobi, data_fields=["inv_diag"], meta_fields=["partition"]
)


@dataclasses.dataclass(frozen=True)
class DistBlockJacobi(LinOp):
    """Stacked per-shard block-Jacobi (uniform storage precision).

    Each shard applies a plain :class:`repro.precond.BlockJacobi` built from
    its slice of the stacked inverted blocks — the apply dispatches through
    the ``block_jacobi_apply`` kernel family like the single-device path.
    Every shard's blocks are uniform with one storage class, so block ``b`` of
    a shard holds its padded rows ``[b*bs, (b+1)*bs)``: the row layout, with
    no index maps.
    """

    inv_blocks: jax.Array  # (P, nb, bs, bs) in the storage dtype
    partition: Partition  # static
    block_size: int  # static

    is_distributed = True

    @property
    def shape(self):
        n = self.partition.global_size
        return (n, n)

    @property
    def dtype(self):
        return self.inv_blocks.dtype

    @property
    def storage_bytes(self) -> int:
        return int(self.inv_blocks.size) * self.inv_blocks.dtype.itemsize

    def local_operator(self, executor=None) -> LinOp:
        from repro.precond import BlockJacobi

        return BlockJacobi(
            inv_blocks=(self.inv_blocks[0],),
            gather_idx=None,
            scatter_idx=None,
            n=self.partition.max_part_size,
            block_size=self.block_size,
            num_blocks=self.inv_blocks.shape[1],
            executor=executor,
        )

    def _apply(self, v, executor):
        # global-vector apply (outside shard_map): block-diagonal, so every
        # shard's apply is independent — pad, batched small matvec over the
        # stacked inverted blocks, unpad.  (The sharded solver path instead
        # applies per shard through the block_jacobi_apply kernel family.)
        part = self.partition
        vp = part.pad(v)  # (P, Lmax)
        nparts, nb, bs, _ = self.inv_blocks.shape
        lmax = vp.shape[1]
        g = jnp.concatenate(
            [vp, jnp.zeros((nparts, nb * bs - lmax), vp.dtype)], axis=1
        ).reshape(nparts, nb, bs)  # zeros under a ragged last block
        y = jnp.einsum(
            "pnij,pnj->pni", self.inv_blocks.astype(vp.dtype), g
        ).reshape(nparts, nb * bs)
        return part.unpad(y[:, :lmax])


jax.tree_util.register_dataclass(
    DistBlockJacobi,
    data_fields=["inv_blocks"],
    meta_fields=["partition", "block_size"],
)


def dist_scalar_jacobi(A, *, adaptive: Union[bool, str] = False, executor=None):
    """Per-shard scalar Jacobi from a distributed matrix's local blocks."""
    from repro.solvers.common import jacobi_preconditioner

    if adaptive is True:
        # per-shard range checks could pick fp16 on one shard and bf16 on
        # another; jnp.stack would then silently promote to f32, defeating
        # the storage reduction — demand an explicit uniform dtype instead
        raise ValueError(
            "distributed scalar Jacobi needs a uniform storage precision "
            "across shards: pass an explicit dtype (adaptive='float16') "
            "instead of adaptive=True"
        )
    inv = jnp.stack(
        [
            jacobi_preconditioner(
                A.local_block(p), executor=executor, adaptive=adaptive
            ).inv_diag
            for p in range(A.partition.num_parts)
        ]
    )
    return DistScalarJacobi(inv_diag=inv, partition=A.partition)


def dist_block_jacobi(
    A,
    block_size: int = None,
    *,
    adaptive: Union[bool, str] = False,
    executor=None,
):
    """Per-shard block-Jacobi from a distributed matrix's local blocks."""
    from repro.precond import block_jacobi

    if adaptive is True:
        raise ValueError(
            "distributed block-Jacobi needs a uniform storage precision "
            "across shards: pass an explicit dtype (adaptive='float16') "
            "instead of adaptive=True"
        )
    per_part = [
        block_jacobi(
            A.local_block(p),
            block_size=block_size,
            adaptive=adaptive,
            executor=executor,
        )
        for p in range(A.partition.num_parts)
    ]
    # uniform blocks + uniform (or no) adaptive class => exactly one stacked
    # precision tensor per part, all the same shape, in the row layout
    assert all(
        len(bj.inv_blocks) == 1 and bj.layout == "row" for bj in per_part
    )
    return DistBlockJacobi(
        inv_blocks=jnp.stack([bj.inv_blocks[0] for bj in per_part]),
        partition=A.partition,
        block_size=per_part[0].block_size,
    )


def dist_preconditioner(A, kind, *, executor=None, **opts):
    """Resolve a distributed solve's ``M=`` argument.

    ``None`` / ``"identity"`` -> no preconditioner; ``"jacobi"`` /
    ``"block_jacobi"`` generate shard-locally from ``A``'s local blocks;
    an already-distributed LinOp passes through.  A non-distributed LinOp or
    bare callable is rejected — it could not apply shard-locally.
    """
    from repro.core.linop import Identity

    if kind is None or isinstance(kind, Identity):
        if opts:
            raise ValueError(
                f"identity preconditioner takes no options, got {sorted(opts)}"
            )
        return None
    if isinstance(kind, str):
        if kind == "identity":
            return dist_preconditioner(A, None, executor=executor, **opts)
        if kind == "jacobi":
            return dist_scalar_jacobi(A, executor=executor, **opts)
        if kind == "block_jacobi":
            return dist_block_jacobi(A, executor=executor, **opts)
        raise ValueError(
            f"unknown distributed preconditioner kind {kind!r} "
            "(identity | jacobi | block_jacobi)"
        )
    if getattr(kind, "is_distributed", False):
        if opts:
            raise ValueError(
                "precond_opts is only meaningful when M is a kind name"
            )
        m_part = getattr(kind, "partition", None)
        if m_part is not None and m_part != A.partition:
            # a partition mismatch would either crash with an opaque shape
            # error inside the shard_map body or — with equal part counts but
            # different offsets — silently apply shard inverses to the wrong
            # rows; refuse loudly instead
            raise ValueError(
                f"preconditioner partition {m_part.offsets} does not match "
                f"the matrix partition {A.partition.offsets}; regenerate the "
                "preconditioner against this matrix"
            )
        return kind
    raise TypeError(
        f"{type(kind).__name__} cannot precondition a distributed solve: "
        "pass a kind name ('jacobi' / 'block_jacobi') or a distributed "
        "preconditioner built against the matrix's partition"
    )
