"""Block-Jacobi preconditioner with adaptive per-block storage precision.

The real ``gko::preconditioner::Jacobi``: the matrix's diagonal blocks are
discovered host-side (setup time, like Ginkgo's ``generate``), extracted
format-aware from CSR/ELL/SELL-P/COO/Dense without densifying, explicitly
inverted by a batched Gauss-Jordan with partial pivoting, and applied as a
batched small-matvec through the executor-dispatched ``block_jacobi_apply``
kernel family (reference / xla / pallas spaces, tile geometry from the
launch-configuration table).

Adaptive precision (arXiv:2006.16852 §"adaptive precision block-Jacobi"):
each inverted block is stored in the cheapest precision that preserves the
preconditioner quality.  A per-block 1-norm condition estimate
``kappa = ||B||_1 * ||B^-1||_1`` drives the rule

    store in precision p  iff  kappa * u_p <= tau

with ``u_p`` the unit roundoff of p (fp16: 2^-11, bf16: 2^-8) and ``tau`` the
quality budget; fp16 additionally requires the inverse's entries to fit its
narrow exponent range, with bf16 as the wide-range 16-bit fallback —
otherwise the block stays in full precision.  Storage is *decoupled from
arithmetic*: blocks are grouped into per-precision stacked sub-batches
(static shapes — the apply stays jittable) and upcast to the vector's dtype
inside the apply kernel, so reduced precision only shrinks the memory
footprint and bandwidth, never the arithmetic.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.batch.linop import BatchLinOp
from repro.core import registry
from repro.core.linop import LinOp
from repro.observability import metrics, trace
from repro.sparse.formats import csr_host_arrays

__all__ = [
    "ADAPTIVE_TAU",
    "BlockJacobi",
    "BatchBlockJacobiPattern",
    "block_jacobi",
    "batch_block_jacobi",
    "batch_block_jacobi_pattern",
    "batch_block_jacobi_blocks",
    "batch_block_jacobi_factors",
    "batch_block_jacobi_from_factors",
    "natural_blocks",
    "uniform_block_ptrs",
    "invert_blocks",
    "select_block_precisions",
    "unit_roundoff",
]

#: default quality budget for the adaptive storage-precision rule.
ADAPTIVE_TAU = 1e-2

#: largest finite fp16 magnitude (bf16 shares fp32's exponent range).
_FP16_MAX = 65504.0


def unit_roundoff(dtype) -> float:
    """Unit roundoff ``u = eps/2`` of a floating storage dtype.

    The quantity the adaptive-precision rule multiplies by the condition
    estimate (``kappa * u_p <= tau``); also what mixed-precision IR
    (:mod:`repro.solvers.ir`) uses to budget its inner-solve tolerance.
    fp16 -> 2^-11, bf16 -> 2^-8, f32 -> 2^-24, f64 -> 2^-53.
    """
    return float(jnp.finfo(jnp.dtype(dtype)).eps) / 2.0

# the kernel spaces (reference/xla/pallas) bind in repro.kernels.block_jacobi
block_jacobi_apply_op = registry.operation(
    "block_jacobi_apply", "batched small-matvec y[b] = inv_blocks[b] @ v[b]"
)


# =============================================================================
# Block discovery (host-side, setup time)
# =============================================================================


def uniform_block_ptrs(n: int, block_size: int) -> np.ndarray:
    """Uniform partition of [0, n) into ceil(n / block_size) blocks."""
    if block_size <= 0:
        raise ValueError(f"block_size must be positive, got {block_size}")
    return np.append(np.arange(0, n, block_size, dtype=np.int64), n)


def _host_csr(A) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(indptr, indices, values) numpy triplet for any single-system format.

    Delegates to :func:`repro.sparse.formats.csr_host_arrays` — the shared
    setup-time conversion hub (Ginkgo's ``convert_to``); explicit stored
    zeros in padded formats are dropped — they contribute nothing to the
    blocks.
    """
    try:
        return csr_host_arrays(A)
    except TypeError:
        raise TypeError(f"cannot extract diagonal blocks from {type(A)}") from None


def natural_blocks(A, max_block_size: int = 8) -> np.ndarray:
    """Supervariable-agglomeration block discovery (Ginkgo's natural blocks).

    Consecutive rows join one block while they are coupled — row ``i+1`` has a
    nonzero in some column the block already spans (or vice versa) — and the
    block stays within ``max_block_size``.  Returns block pointers ``(nb+1,)``.
    """
    indptr, indices, _ = _host_csr(A)
    n = A.shape[0]
    ptrs = [0]
    start = 0
    for i in range(1, n):
        size = i - start
        if size >= max_block_size:
            ptrs.append(i)
            start = i
            continue
        row = indices[indptr[i] : indptr[i + 1]]
        coupled = bool(((row >= start) & (row < i)).any())
        if not coupled:
            # symmetric check: does any block row reach column i?
            for j in range(start, i):
                cols = indices[indptr[j] : indptr[j + 1]]
                if ((cols == i)).any():
                    coupled = True
                    break
        if not coupled:
            ptrs.append(i)
            start = i
    ptrs.append(n)
    return np.asarray(ptrs, np.int64)


def _extract_blocks_host(A, block_ptrs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Padded diagonal-block tensor ``(nb, bs, bs)`` + per-block sizes.

    Format-aware gather over the sparsity structure — no densification.
    Padding rows/cols carry an identity diagonal; structurally empty rows
    inside a real block also fall back to identity (the regularization the
    scale-only predecessor applied via a diagonal ridge).
    """
    indptr, indices, values = _host_csr(A)
    sizes = np.diff(block_ptrs).astype(np.int64)
    nb = len(sizes)
    bs = int(sizes.max()) if nb else 1
    dtype = values.dtype if values.size else np.float32
    blocks = np.zeros((nb, bs, bs), dtype)
    # each stored entry lands in its row's block when its column is inside
    rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64), np.diff(indptr))
    blk = np.searchsorted(block_ptrs, rows, side="right") - 1
    lo = block_ptrs[blk]
    keep = (indices >= lo) & (indices < block_ptrs[blk + 1])
    blocks[blk[keep], (rows - lo)[keep], (indices - lo)[keep]] = values[keep]
    local = np.arange(bs)[None, :]
    # identity padding beyond the block's true size, and the empty-row
    # fallback: a structurally zero row cannot be inverted
    unit = (local >= sizes[:, None]) | ~blocks.any(axis=2)
    b_idx, l_idx = np.nonzero(unit)
    blocks[b_idx, l_idx, l_idx] = 1.0
    return blocks, sizes


# =============================================================================
# Batched Gauss-Jordan inversion (device, jittable)
# =============================================================================


def _gauss_jordan(a: jax.Array):
    """Invert one (bs, bs) block by Gauss-Jordan with partial pivoting.

    Returns ``(inverse, ok)``: ``ok`` is False when some elimination step
    found no usable pivot — the block is rank-deficient and the "inverse"
    (computed with the zero pivot substituted by 1 to keep the loop finite)
    is garbage the caller must discard.
    """
    bs = a.shape[0]
    aug = jnp.concatenate([a, jnp.eye(bs, dtype=a.dtype)], axis=1)

    def step(k, carry):
        aug, ok = carry
        col = aug[:, k]
        eligible = jnp.arange(bs) >= k
        p = jnp.argmax(jnp.where(eligible, jnp.abs(col), -1.0))
        rk, rp = aug[k], aug[p]
        aug = aug.at[k].set(rp).at[p].set(rk)
        piv = aug[k, k]
        ok = ok & (jnp.abs(piv) > 0)
        piv = jnp.where(jnp.abs(piv) > 0, piv, jnp.ones_like(piv))
        row = aug[k] / piv
        aug = aug.at[k].set(row)
        factors = aug[:, k].at[k].set(0.0)
        return aug - factors[:, None] * row[None, :], ok

    aug, ok = jax.lax.fori_loop(0, bs, step, (aug, jnp.asarray(True)))
    return aug[:, bs:], ok


@jax.jit
def invert_blocks(blocks: jax.Array) -> jax.Array:
    """Batched explicit inversion of ``(nb, bs, bs)`` diagonal blocks.

    Gauss-Jordan with partial pivoting (Ginkgo inverts Jacobi blocks the same
    way on GPUs — one subwarp per block).  Rank-deficient blocks (pivot
    exhausted mid-elimination) and any non-finite results degrade to an
    identity fallback rather than silently preconditioning with garbage.
    """
    inv, ok = jax.vmap(_gauss_jordan)(blocks)
    bad = ~ok[:, None, None] | ~jnp.all(
        jnp.isfinite(inv), axis=(-2, -1), keepdims=True
    )
    eye = jnp.eye(blocks.shape[-1], dtype=blocks.dtype)
    return jnp.where(bad, eye, inv)


# =============================================================================
# Adaptive storage-precision selection (host, setup time)
# =============================================================================


def _masked_norm1(t: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Per-block 1-norm restricted to each block's true (size, size) corner."""
    nb, bs, _ = t.shape
    idx = np.arange(bs)
    valid = idx[None, :] < sizes[:, None]  # (nb, bs)
    masked = np.abs(t) * valid[:, :, None] * valid[:, None, :]
    return masked.sum(axis=1).max(axis=1)  # max column sum


def select_block_precisions(
    blocks: np.ndarray,
    inv_blocks: np.ndarray,
    sizes: np.ndarray,
    *,
    tau: float = ADAPTIVE_TAU,
) -> np.ndarray:
    """Per-block storage class: 0 = full precision, 1 = bf16, 2 = fp16.

    The cheapest storage whose unit roundoff keeps ``kappa * u_p`` under the
    quality budget; fp16 preferred among the 16-bit classes (more mantissa)
    when the inverse's magnitudes fit its exponent range, bf16 as the
    wide-range fallback.
    """
    kappa = np.maximum(
        _masked_norm1(blocks, sizes) * _masked_norm1(inv_blocks, sizes), 1.0
    )
    maxabs = np.abs(inv_blocks).reshape(len(blocks), -1).max(axis=1)
    fits_fp16 = (kappa * unit_roundoff(jnp.float16) <= tau) & (maxabs < _FP16_MAX)
    fits_bf16 = kappa * unit_roundoff(jnp.bfloat16) <= tau
    return np.where(fits_fp16, 2, np.where(fits_bf16, 1, 0)).astype(np.int32)


def _storage_classes(base_dtype) -> Tuple:
    return (jnp.dtype(base_dtype), jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def _class_ids(adaptive, blocks_np, inv_np, sizes, tau, base_dtype) -> np.ndarray:
    nb = len(blocks_np)
    if adaptive is False or adaptive is None:
        return np.zeros(nb, np.int32)
    if adaptive is True:
        return select_block_precisions(blocks_np, inv_np, sizes, tau=tau)
    # explicit dtype: force every block into that storage class
    forced = jnp.dtype(adaptive)
    for cid, d in enumerate(_storage_classes(base_dtype)):
        if d == forced:
            return np.full(nb, cid, np.int32)
    raise ValueError(
        f"adaptive={adaptive!r} is not a supported storage dtype "
        f"(expected True/False or one of {_storage_classes(base_dtype)})"
    )


# =============================================================================
# The preconditioner object
# =============================================================================


@dataclasses.dataclass(frozen=True, eq=False)
class BlockJacobi(LinOp):
    """Generated block-Jacobi preconditioner LinOp: ``M^{-1} v`` via inverted
    blocks.

    ``inv_blocks`` holds one stacked sub-batch per storage precision present
    (class-ordered, static shapes).  Two layouts map vector rows to
    (block, local-row) slots in that class order:

    * **row** (``gather_idx``/``scatter_idx`` are ``None``): block ``b`` holds
      rows ``[b*bs, (b+1)*bs)`` — uniform blocks whose class order is the
      row order.  The apply reshapes the vector, padding a ragged last block.
    * **gathered**: the host-precomputed maps gather the vector into the
      slots and scatter the result back — natural or varying blocks, and
      adaptive storage whose classes interleave.

    A LinOp — use directly as a solver's ``M`` or inside any operator
    composition.
    """

    inv_blocks: Tuple[jax.Array, ...]
    gather_idx: Optional[jax.Array]  # (nb, bs) int32; n = zero-pad slot
    scatter_idx: Optional[jax.Array]  # (n,) int32 into the flat (nb*bs,) output
    n: int
    block_size: int  # bs (padded/max block size)
    num_blocks: int
    executor: Optional[object] = None

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.inv_blocks[0].dtype if self.inv_blocks else None

    @property
    def layout(self) -> str:
        """``"row"`` or ``"gathered"`` (class docstring)."""
        return "row" if self.gather_idx is None else "gathered"

    @property
    def storage_dtypes(self) -> Tuple[str, ...]:
        return tuple(str(t.dtype) for t in self.inv_blocks)

    @property
    def precision_counts(self) -> Tuple[Tuple[str, int], ...]:
        return tuple((str(t.dtype), int(t.shape[0])) for t in self.inv_blocks)

    @property
    def storage_bytes(self) -> int:
        """Bytes held by the inverted-block storage (the adaptive metric)."""
        return sum(int(t.size) * t.dtype.itemsize for t in self.inv_blocks)

    def _apply(self, v: jax.Array, executor) -> jax.Array:
        if not self.inv_blocks:  # degenerate 0-row system
            return v
        if self.gather_idx is None:
            padded = self.num_blocks * self.block_size
            if padded != self.n:  # zeros under a ragged last block
                v = jnp.concatenate([v, jnp.zeros((padded - self.n,), v.dtype)])
            vp = v.reshape(self.num_blocks, self.block_size)
        else:
            vpad = jnp.concatenate([v, jnp.zeros((1,), v.dtype)])
            vp = vpad[self.gather_idx]  # (nb, bs), class-ordered
        outs = []
        off = 0
        for t in self.inv_blocks:
            nbc = t.shape[0]
            outs.append(
                block_jacobi_apply_op(
                    t, jax.lax.slice_in_dim(vp, off, off + nbc), executor=executor
                )
            )
            off += nbc
        y = jnp.concatenate(outs, axis=0).reshape(-1)
        if self.scatter_idx is None:
            return y[: self.n]
        return y[self.scatter_idx]

    def transpose(self) -> "BlockJacobi":
        """``M^{-T}``: the same block structure with each inverted block
        transposed — ``(blockdiag(B_i)^{-1})^T = blockdiag(B_i^{-T})``."""
        return dataclasses.replace(
            self,
            inv_blocks=tuple(jnp.swapaxes(t, -1, -2) for t in self.inv_blocks),
        )


# a pytree, so a solve can be jitted with the preconditioner as an argument
jax.tree_util.register_dataclass(
    BlockJacobi,
    data_fields=["inv_blocks", "gather_idx", "scatter_idx"],
    meta_fields=["n", "block_size", "num_blocks", "executor"],
)


def block_jacobi(
    A,
    block_size: Optional[int] = None,
    *,
    blocks: Optional[Sequence[int]] = None,
    adaptive: Union[bool, str, jnp.dtype] = False,
    tau: float = ADAPTIVE_TAU,
    executor=None,
) -> BlockJacobi:
    """Generate the block-Jacobi preconditioner for ``A``.

    ``blocks`` pins explicit block pointers (e.g. from :func:`natural_blocks`);
    otherwise the partition is uniform with ``block_size`` (default: the
    executor's cooperative-subgroup width, Ginkgo's subwarp-tuned storage).
    ``adaptive=True`` turns on per-block storage-precision selection;
    a dtype forces every block into that storage.
    """
    with trace.span("block_jacobi.generate", cat="precond") as span:
        M = _generate(A, block_size, blocks, adaptive, tau, executor)
        metrics.counter("block_jacobi.layout", layout=M.layout).inc()
        span.annotate(layout=M.layout)
        return M


def _block_maps(
    block_ptrs: np.ndarray, order: np.ndarray, bs: int
) -> Tuple[np.ndarray, np.ndarray]:
    """The gathered layout's ``(gather, scatter)`` maps.

    Row ``r`` sits at local slot ``r - lo`` of its block, which sits at
    position ``pos_of[block]`` of the class ``order``: ``gather`` is
    ``(nb, bs)`` int32 with ``n`` in the zero-pad slots, ``scatter`` is
    ``(n,)`` int32 into the flat ``(nb*bs,)`` apply output.
    """
    n, nb = int(block_ptrs[-1]), len(block_ptrs) - 1
    sizes = np.diff(block_ptrs)
    gather = np.full((nb, bs), n, np.int32)
    scatter = np.zeros(n, np.int32)
    pos_of = np.empty(nb, np.int64)
    pos_of[order] = np.arange(nb)
    r = np.arange(n, dtype=np.int64)
    blk = np.repeat(np.arange(nb, dtype=np.int64), sizes)
    slot, pos = r - block_ptrs[blk], pos_of[blk]
    gather[pos, slot] = r
    scatter[r] = pos * bs + slot
    return gather, scatter


def _generate(A, block_size, blocks, adaptive, tau, executor) -> BlockJacobi:
    """:func:`block_jacobi`'s steps, each in a ``block_jacobi.<step>`` span."""
    n = A.shape[0]
    if blocks is not None:
        block_ptrs = np.asarray(blocks, np.int64)
        if block_ptrs[0] != 0 or block_ptrs[-1] != n or (np.diff(block_ptrs) <= 0).any():
            raise ValueError(
                f"block pointers must cover [0, {n}) with positive sizes, "
                f"got {block_ptrs}"
            )
    else:
        if block_size is None:
            from repro.core.executor import current_executor

            ex = executor if executor is not None else current_executor()
            block_size = ex.hw.subgroup_size
        block_ptrs = uniform_block_ptrs(n, block_size)

    with trace.span("block_jacobi.extract", cat="precond"):
        blocks_np, sizes = _extract_blocks_host(A, block_ptrs)
    nb, bs = blocks_np.shape[0], blocks_np.shape[1]
    with trace.span("block_jacobi.invert", cat="precond"):
        inv = invert_blocks(jnp.asarray(blocks_np))
        inv_np = np.asarray(inv)
    base_dtype = inv.dtype

    with trace.span("block_jacobi.classify", cat="precond"):
        class_id = _class_ids(adaptive, blocks_np, inv_np, sizes, tau, base_dtype)
        order = np.argsort(class_id, kind="stable")

    # the row layout needs no maps: every block but a ragged last one holds
    # bs rows, and the class order is the row order
    row = bool((sizes[:-1] == bs).all()) and bool((order == np.arange(nb)).all())
    gather_idx = scatter_idx = None
    if not row:
        with trace.span("block_jacobi.maps", cat="precond"):
            gather, scatter = _block_maps(block_ptrs, order, bs)
            gather_idx, scatter_idx = jnp.asarray(gather), jnp.asarray(scatter)

    with trace.span("block_jacobi.upload", cat="precond"):
        classes = _storage_classes(base_dtype)
        tensors = []
        sorted_ids = class_id[order]
        for cid, dtype in enumerate(classes):
            members = order[sorted_ids == cid]
            if len(members) == 0:
                continue
            tensors.append(jnp.asarray(inv_np[members]).astype(dtype))

    return BlockJacobi(
        inv_blocks=tuple(tensors),
        gather_idx=gather_idx,
        scatter_idx=scatter_idx,
        n=n,
        block_size=bs,
        num_blocks=nb,
        executor=executor,
    )


# =============================================================================
# Batched variant — gko::batch::preconditioner::Jacobi with bs > 1
# =============================================================================


@dataclasses.dataclass(frozen=True, eq=False)
class BatchBlockJacobi(BatchLinOp):
    """Per-system block-Jacobi over a shared-pattern batch — a BatchLinOp.

    Blocks of all systems are flattened into one class-ordered stack (the
    per-precision sub-batches span the whole batch), so the apply is the same
    executor-dispatched batched small-matvec as the single-system path.
    """

    inv_blocks: Tuple[jax.Array, ...]  # per class, (count, bs, bs)
    perm: jax.Array  # (ns*nblocks,) int32 flat (system, block) -> class order
    inv_perm: jax.Array  # inverse permutation
    gather_idx: jax.Array  # (nblocks, bs) int32 into a padded system row
    n: int
    num_blocks: int  # per system
    block_size: int
    executor: Optional[object] = None

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.inv_blocks[0].dtype if self.inv_blocks else None

    @property
    def storage_bytes(self) -> int:
        return sum(int(t.size) * t.dtype.itemsize for t in self.inv_blocks)

    @property
    def precision_counts(self) -> Tuple[Tuple[str, int], ...]:
        return tuple((str(t.dtype), int(t.shape[0])) for t in self.inv_blocks)

    def _apply(self, V: jax.Array, executor) -> jax.Array:
        ns = V.shape[0]
        Vpad = jnp.concatenate([V, jnp.zeros((ns, 1), V.dtype)], axis=1)
        vp = Vpad[:, self.gather_idx]  # (ns, nblocks, bs)
        flat = vp.reshape(ns * self.num_blocks, self.block_size)[self.perm]
        outs = []
        off = 0
        for t in self.inv_blocks:
            nbc = t.shape[0]
            outs.append(
                block_jacobi_apply_op(
                    t,
                    jax.lax.slice_in_dim(flat, off, off + nbc),
                    executor=executor,
                )
            )
            off += nbc
        y = jnp.concatenate(outs, axis=0)[self.inv_perm]
        y = y.reshape(ns, self.num_blocks * self.block_size)
        return y[:, : self.n]


def _batch_slot_table(A, block_ptrs: np.ndarray, bs: int) -> np.ndarray:
    """(nblocks, bs, bs) table of flat value slots (+1; 0 = structurally absent).

    Built once from the shared sparsity pattern — per-system block extraction
    is then a single gather over each system's value row.
    """
    from repro.batch.formats import BatchCsr, BatchEll

    nb = len(block_ptrs) - 1
    table = np.zeros((nb, bs, bs), np.int64)
    if isinstance(A, BatchCsr):
        indptr = np.asarray(A.indptr)
        indices = np.asarray(A.indices)
        for b in range(nb):
            lo, hi = int(block_ptrs[b]), int(block_ptrs[b + 1])
            for i in range(lo, hi):
                for t in range(int(indptr[i]), int(indptr[i + 1])):
                    j = int(indices[t])
                    if lo <= j < hi:
                        table[b, i - lo, j - lo] = t + 1
        return table
    if isinstance(A, BatchEll):
        cols = np.asarray(A.col_idx)  # (m, k)
        m, k = cols.shape
        for b in range(nb):
            lo, hi = int(block_ptrs[b]), int(block_ptrs[b + 1])
            for i in range(lo, min(hi, m)):
                for q in range(k):
                    j = int(cols[i, q])
                    # ELL padding is (col 0, value 0) at the row's tail; CSR
                    # column order means a *real* col-0 entry sits at q == 0,
                    # so any later col-0 slot is padding and must not
                    # overwrite the real slot in the table
                    if j == 0 and q > 0:
                        continue
                    if lo <= j < hi:
                        table[b, i - lo, j - lo] = i * k + q + 1
        return table
    raise TypeError(f"unknown batched format {type(A)}")


# -----------------------------------------------------------------------------
# Generate/apply split (Ginkgo's generate, factored into two tiers)
#
# Tier 1 — *pattern*: everything derivable from the shared sparsity structure
# alone (block pointers, value-slot table, gather map, padding identity).
# Tier 2 — *values*: the per-system numeric work (block gather + batched
# Gauss-Jordan inversion).  A pattern-keyed setup cache stores tier 1 once per
# sparsity pattern and tier 2 once per value set; repeat-pattern traffic pays
# only tier 2, repeat-values traffic pays neither.
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class BatchBlockJacobiPattern:
    """Values-independent half of batched block-Jacobi generation.

    Built once per sparsity pattern; combined with any ``(ns, nnz)`` value
    tensor sharing that pattern it yields the inverted factors via
    :func:`batch_block_jacobi_factors`.
    """

    block_ptrs: np.ndarray  # (nblocks+1,) host-side
    sizes: np.ndarray  # (nblocks,) true block sizes
    slot_table: np.ndarray  # (nblocks, bs, bs) flat value slots (+1; 0 absent)
    pad_add: jax.Array  # (nblocks, bs, bs) identity padding addend
    gather_idx: jax.Array  # (nblocks, bs) int32 into a padded system row
    n: int
    num_blocks: int
    block_size: int

    @property
    def storage_bytes(self) -> int:
        """Host + device bytes the cached pattern tier holds."""
        return int(
            self.block_ptrs.nbytes + self.sizes.nbytes + self.slot_table.nbytes
            + self.pad_add.size * self.pad_add.dtype.itemsize
            + self.gather_idx.size * self.gather_idx.dtype.itemsize
        )


def batch_block_jacobi_pattern(
    A, block_size: Optional[int] = None, *, executor=None
) -> BatchBlockJacobiPattern:
    """Pattern-tier generation: block discovery + slot tables, no values read."""
    n = A.shape[0]
    if block_size is None:
        from repro.core.executor import current_executor

        ex = executor if executor is not None else current_executor()
        block_size = ex.hw.subgroup_size
    block_ptrs = uniform_block_ptrs(n, block_size)
    sizes = np.diff(block_ptrs).astype(np.int64)
    nb = len(sizes)
    bs = int(sizes.max()) if nb else 1

    table = _batch_slot_table(A, block_ptrs, bs)

    # identity on padding rows/cols beyond each block's true size
    pad_diag = np.zeros((nb, bs), np.float32)
    idx = np.arange(bs)
    pad_diag[idx[None, :] >= sizes[:, None]] = 1.0
    pad_add = jnp.asarray(pad_diag[:, :, None] * np.eye(bs))

    gather = np.full((nb, bs), n, np.int32)
    for b in range(nb):
        lo, size = int(block_ptrs[b]), int(sizes[b])
        gather[b, :size] = np.arange(lo, lo + size, dtype=np.int32)

    return BatchBlockJacobiPattern(
        block_ptrs=block_ptrs,
        sizes=sizes,
        slot_table=table,
        pad_add=pad_add,
        gather_idx=jnp.asarray(gather),
        n=n,
        num_blocks=nb,
        block_size=bs,
    )


def batch_block_jacobi_blocks(
    values: jax.Array, pattern: BatchBlockJacobiPattern
) -> jax.Array:
    """Per-system diagonal blocks ``(ns*nblocks, bs, bs)`` gathered from a
    ``(ns, nnz_flat)`` value tensor through the pattern's slot table."""
    ns = values.shape[0]
    flat_vals = values.reshape(ns, -1)
    nb, bs = pattern.num_blocks, pattern.block_size
    padded = jnp.concatenate(
        [jnp.zeros((ns, 1), flat_vals.dtype), flat_vals], axis=1
    )
    blocks = padded[:, jnp.asarray(pattern.slot_table.reshape(-1))].reshape(
        ns, nb, bs, bs
    )
    blocks = blocks + pattern.pad_add[None]
    # per-system empty-row fallback: a block row that gathered only zeros
    # (structurally empty row, or a system whose stored entries there are all
    # zero) gets an identity diagonal — the same rule the single-system
    # extraction applies host-side.  Structural detection via the slot table
    # is not enough: an ELL padding slot at q == 0 is indistinguishable from
    # a real col-0 entry, so the check must look at the gathered values.
    row_zero = jnp.all(blocks == 0, axis=3)  # (ns, nb, bs)
    eye = jnp.asarray(np.eye(bs, dtype=np.float32))
    blocks = blocks + row_zero[..., None] * eye
    return blocks.reshape(ns * nb, bs, bs)


def batch_block_jacobi_factors(
    values: jax.Array, pattern: BatchBlockJacobiPattern
) -> jax.Array:
    """Values-tier generation: gather blocks and invert them in one batch.

    The expensive numeric half of generate — exactly what a setup cache
    stores per (pattern, values) pair.
    """
    return invert_blocks(batch_block_jacobi_blocks(values, pattern))


def batch_block_jacobi_from_factors(
    inv: jax.Array,
    ns: int,
    pattern: BatchBlockJacobiPattern,
    *,
    executor=None,
) -> BatchBlockJacobi:
    """Assemble the BatchLinOp from precomputed inverted factors.

    Single storage class, identity permutation — bitwise the same operator
    :func:`batch_block_jacobi` builds with ``adaptive=False``, but without
    re-running discovery or inversion (the cache-hit apply path).
    """
    ar = jnp.arange(ns * pattern.num_blocks, dtype=jnp.int32)
    return BatchBlockJacobi(
        inv_blocks=(inv,),
        perm=ar,
        inv_perm=ar,
        gather_idx=pattern.gather_idx,
        n=pattern.n,
        num_blocks=pattern.num_blocks,
        block_size=pattern.block_size,
        executor=executor,
    )


def batch_block_jacobi(
    A,
    block_size: Optional[int] = None,
    *,
    adaptive: Union[bool, str, jnp.dtype] = False,
    tau: float = ADAPTIVE_TAU,
    executor=None,
) -> BatchBlockJacobi:
    """Per-system block-Jacobi for a shared-pattern batched matrix.

    Composes the two generation tiers (pattern, then values); the serve-path
    setup cache calls the tiers separately and reuses their products.
    """
    ns = A.num_batch
    pattern = batch_block_jacobi_pattern(A, block_size, executor=executor)
    nb, bs = pattern.num_blocks, pattern.block_size
    flat_blocks = batch_block_jacobi_blocks(A.values.reshape(ns, -1), pattern)
    inv = invert_blocks(flat_blocks)
    if adaptive is False or adaptive is None:
        return batch_block_jacobi_from_factors(inv, ns, pattern,
                                               executor=executor)

    inv_np = np.asarray(inv)
    base_dtype = inv.dtype
    flat_sizes = np.tile(pattern.sizes, ns)
    class_id = _class_ids(
        adaptive, np.asarray(flat_blocks), inv_np, flat_sizes, tau, base_dtype
    )
    order = np.argsort(class_id, kind="stable")
    inv_perm = np.empty_like(order)
    inv_perm[order] = np.arange(len(order))

    classes = _storage_classes(base_dtype)
    tensors = []
    sorted_ids = class_id[order]
    for cid, dtype in enumerate(classes):
        members = order[sorted_ids == cid]
        if len(members) == 0:
            continue
        tensors.append(jnp.asarray(inv_np[members]).astype(dtype))

    return BatchBlockJacobi(
        inv_blocks=tuple(tensors),
        perm=jnp.asarray(order.astype(np.int32)),
        inv_perm=jnp.asarray(inv_perm.astype(np.int32)),
        gather_idx=pattern.gather_idx,
        n=pattern.n,
        num_blocks=nb,
        block_size=bs,
        executor=executor,
    )
