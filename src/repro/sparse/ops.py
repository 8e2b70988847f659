"""Executor-dispatched sparse operations (SpMV per format) + BLAS-1 kernels.

Reference space = sequential-semantics oracle (straightforward scatter/gather).
XLA space       = segment-sum / one-shot vectorized formulations the compiler
                  can fuse (Ginkgo's "OpenMP" slot).
Pallas space    = registered from ``repro.kernels.spmv_sellp`` / ``..._ell``
                  (hardware-native; bound when ``repro`` is imported).

``apply(A, x)`` mirrors ``gko::LinOp::apply`` — dispatch on format type, then on
executor kernel space.
"""

from __future__ import annotations

import contextlib
import contextvars

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import registry
from repro.sparse.formats import Coo, Csr, Dense, Ell, Sellp, csr_from_arrays

__all__ = [
    "apply",
    "to_dense",
    "dot",
    "axpy",
    "scal",
    "norm2",
    "distributed_blas",
    "spmv_dot",
    "axpy_norm",
    "dot_batch",
    "has_fused_ops",
    "spgemm",
    "sptranspose",
]

# =============================================================================
# SpMV — COO
# =============================================================================

spmv_coo = registry.operation(
    "spmv_coo", "y = A @ x for sorted COO (scatter-add semantics)"
)


@spmv_coo.register("reference")
def _spmv_coo_ref(ex, A: Coo, x: jax.Array) -> jax.Array:
    m = A.shape[0]
    y = jnp.zeros((m,) + x.shape[1:], dtype=jnp.result_type(A.values, x))
    contrib = A.values[:, None] * x[A.col_idx] if x.ndim == 2 else A.values * x[A.col_idx]
    return y.at[A.row_idx].add(contrib)


@spmv_coo.register("xla")
def _spmv_coo_xla(ex, A: Coo, x: jax.Array) -> jax.Array:
    # segment-sum over sorted rows; indices_are_sorted lets XLA lower a
    # contiguous scatter (the TPU-friendly form of the paper's COO kernel,
    # which on GPUs uses atomicAdd — no TPU analogue, see DESIGN.md).
    contrib = A.values[:, None] * x[A.col_idx] if x.ndim == 2 else A.values * x[A.col_idx]
    return jax.ops.segment_sum(
        contrib, A.row_idx, num_segments=A.shape[0], indices_are_sorted=True
    )


# =============================================================================
# SpMV — CSR
# =============================================================================

spmv_csr = registry.operation("spmv_csr", "y = A @ x for CSR")


def _csr_row_ids(A: Csr) -> jax.Array:
    nnz = A.values.shape[0]
    return (
        jnp.searchsorted(A.indptr, jnp.arange(nnz, dtype=jnp.int32), side="right")
        .astype(jnp.int32)
        - 1
    )


@spmv_csr.register("reference")
def _spmv_csr_ref(ex, A: Csr, x: jax.Array) -> jax.Array:
    rows = _csr_row_ids(A)
    y = jnp.zeros((A.shape[0],) + x.shape[1:], dtype=jnp.result_type(A.values, x))
    contrib = A.values[:, None] * x[A.indices] if x.ndim == 2 else A.values * x[A.indices]
    return y.at[rows].add(contrib)


@spmv_csr.register("xla")
def _spmv_csr_xla(ex, A: Csr, x: jax.Array) -> jax.Array:
    rows = _csr_row_ids(A)
    contrib = A.values[:, None] * x[A.indices] if x.ndim == 2 else A.values * x[A.indices]
    return jax.ops.segment_sum(
        contrib, rows, num_segments=A.shape[0], indices_are_sorted=True
    )


# =============================================================================
# SpMV — ELL
# =============================================================================

spmv_ell = registry.operation("spmv_ell", "y = A @ x for ELLPACK")


@spmv_ell.register("reference")
def _spmv_ell_ref(ex, A: Ell, x: jax.Array) -> jax.Array:
    # gather x per (row, k) then reduce over k — padding contributes 0.
    gathered = x[A.col_idx]  # (m, k) or (m, k, nrhs)
    if x.ndim == 2:
        return jnp.einsum("mk,mkr->mr", A.values, gathered)
    return jnp.sum(A.values * gathered, axis=1)


@spmv_ell.register("xla")
def _spmv_ell_xla(ex, A: Ell, x: jax.Array) -> jax.Array:
    return _spmv_ell_ref(ex, A, x)


# =============================================================================
# SpMV — SELL-P
# =============================================================================

spmv_sellp = registry.operation("spmv_sellp", "y = A @ x for SELL-P")


@spmv_sellp.register("reference")
def _spmv_sellp_ref(ex, A: Sellp, x: jax.Array) -> jax.Array:
    """Oracle: direct readback of the slice layout, one slice at a time.

    Python loop over slices (static count) — sequential reference semantics,
    mirroring Ginkgo's reference kernel.
    """
    if x.ndim != 1:
        raise NotImplementedError("reference SELL-P spmv is single-rhs")
    m = A.shape[0]
    C = A.slice_size
    y = jnp.zeros((m,), dtype=jnp.result_type(A.values, x))
    import numpy as np

    slice_sets = np.asarray(A.slice_sets)
    for s in range(A.num_slices):
        lo, hi = int(slice_sets[s]), int(slice_sets[s + 1])
        width = hi - lo
        block_v = A.values[lo * C : hi * C].reshape(width, C)
        block_c = A.col_idx[lo * C : hi * C].reshape(width, C)
        contrib = (block_v * x[block_c]).sum(axis=0)  # (C,)
        rows = jnp.arange(C) + s * C
        y = y.at[rows].add(jnp.where(rows < m, contrib, 0.0))
    return y


@spmv_sellp.register("xla")
def _spmv_sellp_xla(ex, A: Sellp, x: jax.Array) -> jax.Array:
    """Vectorized: one flat gather + segment reduction into rows.

    Element t of the flat buffer belongs to slice s(t), local column j, local
    row r = t % C; its output row is s*C + r.  We compute output rows with a
    searchsorted over slice_sets (flat index // C gives the column-set index).
    """
    if x.ndim != 1:
        raise NotImplementedError("xla SELL-P spmv is single-rhs")
    C = A.slice_size
    total = A.values.shape[0]
    t = jnp.arange(total, dtype=jnp.int32)
    colset = t // C  # global column-set index in [0, slice_sets[-1])
    s = (
        jnp.searchsorted(A.slice_sets, colset, side="right").astype(jnp.int32) - 1
    )
    r = t % C
    out_row = s * C + r
    contrib = A.values * x[A.col_idx]
    y = jax.ops.segment_sum(contrib, out_row, num_segments=A.num_slices * C)
    return y[: A.shape[0]]


# =============================================================================
# Dense apply + to_dense
# =============================================================================

spmv_dense = registry.operation("spmv_dense", "y = A @ x (dense)")


@spmv_dense.register("reference")
def _spmv_dense_ref(ex, A: Dense, x: jax.Array) -> jax.Array:
    return A.values @ x


@spmv_dense.register("xla")
def _spmv_dense_xla(ex, A: Dense, x: jax.Array) -> jax.Array:
    return A.values @ x


to_dense_op = registry.operation("sparse_to_dense", "densify any format")


@to_dense_op.register("reference")
def _to_dense_ref(ex, A) -> jax.Array:
    if isinstance(A, Dense):
        return A.values
    if isinstance(A, Coo):
        out = jnp.zeros(A.shape, A.values.dtype)
        return out.at[A.row_idx, A.col_idx].add(A.values)
    if isinstance(A, Csr):
        rows = _csr_row_ids(A)
        out = jnp.zeros(A.shape, A.values.dtype)
        return out.at[rows, A.indices].add(A.values)
    if isinstance(A, Ell):
        m, k = A.values.shape
        rows = jnp.broadcast_to(jnp.arange(m)[:, None], (m, k))
        out = jnp.zeros(A.shape, A.values.dtype)
        return out.at[rows, A.col_idx].add(A.values)
    if isinstance(A, Sellp):
        x = jnp.eye(A.shape[1], dtype=A.values.dtype)
        cols = [_spmv_sellp_ref(ex, A, x[:, j]) for j in range(A.shape[1])]
        return jnp.stack(cols, axis=1)
    raise TypeError(f"unknown format {type(A)}")


# =============================================================================
# apply — gko::LinOp::apply
# =============================================================================

_FORMAT_OP = {
    Coo: spmv_coo,
    Csr: spmv_csr,
    Ell: spmv_ell,
    Sellp: spmv_sellp,
    Dense: spmv_dense,
}


def apply(A, x: jax.Array, *, executor=None) -> jax.Array:
    """``A.apply(x)``: format-dispatch then executor-dispatch.

    Composed / non-format LinOps (``Sum``, ``Composition``, solvers, ...)
    delegate to their own ``apply`` — this function stays the single entry
    point for "apply any operator" while the format fast path below keeps
    dispatching straight into the kernel registry.
    """
    try:
        op = _FORMAT_OP[type(A)]
    except KeyError:
        from repro.core.linop import LinOp
        from repro.sparse.formats import MatrixLinOp

        # a MatrixLinOp not in the table is an unregistered *format* — its
        # _apply would bounce right back here, so fail loudly instead
        if isinstance(A, LinOp) and not isinstance(A, MatrixLinOp):
            return A.apply(x, executor=executor)
        raise TypeError(f"no spmv registered for format {type(A)}") from None
    m, n = A.shape
    if m == 0 or n == 0:
        # degenerate operand: no kernel may launch (zero-size grids) and the
        # padding convention (col 0) has no column 0 to gather — the product
        # is empty or zero by definition
        return jnp.zeros((m,) + x.shape[1:], dtype=jnp.result_type(A.dtype, x))
    return op(A, x, executor=executor)


def to_dense(A, *, executor=None) -> jax.Array:
    if 0 in A.shape:
        return jnp.zeros(A.shape, A.dtype)
    return to_dense_op(A, executor=executor)


# =============================================================================
# BLAS-1 kernels used by the Krylov solvers (Ginkgo registers these per backend)
# =============================================================================

dot_op = registry.operation("blas_dot")
axpy_op = registry.operation("blas_axpy")
scal_op = registry.operation("blas_scal")
norm2_op = registry.operation("blas_norm2")


@dot_op.register("reference")
def _dot_ref(ex, x, y):
    return jnp.vdot(x, y)


@dot_op.register("xla")
def _dot_xla(ex, x, y):
    return jnp.vdot(x, y)


@axpy_op.register("reference")
def _axpy_ref(ex, alpha, x, y):
    return alpha * x + y


@axpy_op.register("xla")
def _axpy_xla(ex, alpha, x, y):
    return alpha * x + y


@scal_op.register("reference")
def _scal_ref(ex, alpha, x):
    return alpha * x


@scal_op.register("xla")
def _scal_xla(ex, alpha, x):
    return alpha * x


@norm2_op.register("reference")
def _norm2_ref(ex, x):
    return jnp.sqrt(jnp.vdot(x, x).real)


@norm2_op.register("xla")
def _norm2_xla(ex, x):
    return jnp.sqrt(jnp.vdot(x, x).real)


# =============================================================================
# Fused apply-with-reduction ops (arXiv:2011.08879 §kernels)
# =============================================================================
#
# Ginkgo's hand-tuned kernels fuse the reduction into the apply so the Krylov
# hot path streams each vector through HBM once instead of three times:
#
# * ``spmv_dot_*``  — SpMV that emits ``w · y`` in the same pass (CG's
#   ``p·Ap``, BiCGSTAB's ``r̂·v``);
# * ``axpy_norm``   — ``z = alpha*x + y`` plus ``z·z`` (the residual update
#   and the stopping-criterion norm, one pass).
#
# These are OPTIONAL ops: solvers probe :func:`has_fused_ops` (capability
# probe on the registry) and gracefully fall back to the unfused path when a
# backend doesn't advertise them.  The reference/xla implementations below are
# deliberately the *literal unfused composition*, so enabling the fused path
# on those spaces is bitwise-neutral — the fallback-parity contract the tests
# pin.  The pallas space registers truly fused kernels from
# ``repro.kernels.spmv_dot`` / ``repro.kernels.axpy_norm``.

spmv_dot_csr_op = registry.operation(
    "spmv_dot_csr", "(y, w·y) = (A @ x, fused dot) for CSR"
)
spmv_dot_ell_op = registry.operation(
    "spmv_dot_ell", "(y, w·y) = (A @ x, fused dot) for ELLPACK"
)
axpy_norm_op = registry.operation(
    "axpy_norm", "(z, z·z) with z = alpha*x + y, fused"
)


@spmv_dot_csr_op.register("reference")
def _spmv_dot_csr_ref(ex, A: Csr, x, w):
    y = _spmv_csr_ref(ex, A, x)
    return y, jnp.vdot(w, y)


@spmv_dot_csr_op.register("xla")
def _spmv_dot_csr_xla(ex, A: Csr, x, w):
    y = _spmv_csr_xla(ex, A, x)
    return y, jnp.vdot(w, y)


@spmv_dot_ell_op.register("reference")
def _spmv_dot_ell_ref(ex, A: Ell, x, w):
    y = _spmv_ell_ref(ex, A, x)
    return y, jnp.vdot(w, y)


@spmv_dot_ell_op.register("xla")
def _spmv_dot_ell_xla(ex, A: Ell, x, w):
    y = _spmv_ell_xla(ex, A, x)
    return y, jnp.vdot(w, y)


def _axpy_norm_impl(ex, alpha, x, y):
    # shared 1-D / batched (nb, n) formulation: the batched solvers reuse this
    # exact op, so single and batched paths share one fused implementation
    if jnp.ndim(x) == 2:
        a = alpha[:, None] if jnp.ndim(alpha) == 1 else alpha
        z = a * x + y
        return z, jnp.einsum("bn,bn->b", z, z)
    z = alpha * x + y
    return z, jnp.vdot(z, z)


axpy_norm_op.register("reference")(_axpy_norm_impl)
_axpy_norm_xla = axpy_norm_op.register("xla")(_axpy_norm_impl)


_FUSED_SPMV_OP = {Csr: spmv_dot_csr_op, Ell: spmv_dot_ell_op}


def has_fused_ops(A, *, executor=None) -> bool:
    """Capability probe: can this executor serve the fused iteration ops for
    operand ``A``?  False for formats/operators without a fused SpMV (solvers
    then keep the unfused path — graceful degradation, never an error)."""
    from repro.core.executor import current_executor

    op = _FUSED_SPMV_OP.get(type(A))
    if op is None:
        return False
    ex = executor if executor is not None else current_executor()
    return op.supports(ex) and axpy_norm_op.supports(ex)


def spmv_dot(A, x, w=None, *, executor=None):
    """Fused SpMV + dot: ``(y, w·y)`` with ``w`` defaulting to ``x``.

    Under the distributed-reduction context the dot partial is masked and
    ``psum``'d like every reduction (the SpMV output stays shard-local); the
    solver layer normally disables the fused path per shard instead, but the
    wrapper stays correct either way.
    """
    w = x if w is None else w
    op = _FUSED_SPMV_OP[type(A)]
    ctx = _DIST_BLAS.get()
    if ctx is None:
        return op(A, x, w, executor=executor)
    axis_name, mask = ctx
    y = apply(A, x, executor=executor)
    local = dot_op(_masked(w, mask), _masked(y, mask), executor=executor)
    return y, jax.lax.psum(local, axis_name)


def axpy_norm(alpha, x, y, *, executor=None):
    """Fused axpy + squared-norm: ``(z, ‖z‖²)`` with ``z = alpha*x + y``."""
    ctx = _DIST_BLAS.get()
    if ctx is None:
        return axpy_norm_op(alpha, x, y, executor=executor)
    axis_name, mask = ctx
    z = axpy_op(alpha, x, y, executor=executor)
    zm = _masked(z, mask)
    local = dot_op(zm, zm, executor=executor)
    return z, jax.lax.psum(local, axis_name)


# -- the distributed-reduction context ----------------------------------------
#
# Inside a ``shard_map`` body, a vector is one padded shard of the global
# vector: ``dot``/``norm2`` must reduce locally (still executor-dispatched)
# and then ``psum`` over the mesh axis, with padding slots masked out of the
# operands.  The distributed solver layer (:mod:`repro.distributed.solvers`)
# opens this context around the UNCHANGED solver source — the Krylov methods
# never learn whether their reductions are local or global, exactly Ginkgo's
# ``distributed::Vector`` story.  ``axpy``/``scal`` are elementwise and need
# no collective.

_DIST_BLAS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_distributed_blas", default=None
)


@contextlib.contextmanager
def distributed_blas(axis_name: str, mask=None):
    """Make ``dot``/``norm2`` global over ``axis_name`` (psum of the local
    partial) with padding slots of the shard masked by ``mask`` (bool,
    broadcastable; ``None`` = no padding)."""
    token = _DIST_BLAS.set((axis_name, mask))
    try:
        yield
    finally:
        _DIST_BLAS.reset(token)


def _masked(x, mask):
    # zero the padding slots so a ragged partition never double-counts them
    # (the padded-shard bug); lazy import keeps the layering one-directional
    # everywhere outside this trace-time hook.
    from repro.distributed.sharding import zero_shard_padding

    return zero_shard_padding(x, mask)


def dot(x, y, *, executor=None):
    ctx = _DIST_BLAS.get()
    if ctx is None:
        return dot_op(x, y, executor=executor)
    axis_name, mask = ctx
    # mask BOTH operands: 0 * non-finite padding would still be NaN
    local = dot_op(_masked(x, mask), _masked(y, mask), executor=executor)
    return jax.lax.psum(local, axis_name)


def axpy(alpha, x, y, *, executor=None):
    return axpy_op(alpha, x, y, executor=executor)


def scal(alpha, x, *, executor=None):
    return scal_op(alpha, x, executor=executor)


def norm2(x, *, executor=None):
    ctx = _DIST_BLAS.get()
    if ctx is None:
        return norm2_op(x, executor=executor)
    axis_name, mask = ctx
    xm = _masked(x, mask)
    # local sum of squares through the dispatched dot, global psum, one sqrt —
    # bit-for-bit the shape Stop.threshold expects from a global norm
    local = dot_op(xm, xm, executor=executor)
    return jnp.sqrt(jax.lax.psum(local, axis_name).real)


# =============================================================================
# Sparse-sparse composition: SpGEMM and sparse transpose
# =============================================================================
#
# ``gko::Csr::apply(Csr)`` — the setup-path workhorse behind algebraic
# multigrid's Galerkin triple product R·A·P.  Unlike the SpMV hot path, the
# *structure* of the result is data-dependent (row nnz of C = A·B is unknown
# until computed), so every space runs a host-side structure pass:
#
#   1. row-nnz upper-bound pass — expand each a_ik into the length of B's row
#      k (the classical "symbolic" upper bound, before duplicate merging);
#   2. numeric expansion — produce the (row, col, a_ik·b_kj) triplets (this is
#      the flop-carrying pass: a sequential merge in the reference space, a
#      device gather-multiply in the xla space, which the pallas executor
#      falls back to as well);
#   3. coalesce — sort triplets by (row, col), merge duplicates, build indptr.
#
# All three spaces share steps 1 and 3 bit-for-bit, so the output *structure*
# is identical across executors (the conformance contract); only step 2's
# arithmetic differs in summation order, covered by the usual float tolerance.
# Structural nonzeros are kept even when numerically zero — Ginkgo semantics,
# and what keeps the pattern a pure function of the operand patterns (the
# property the serve-cache pattern tier relies on).

spgemm_op = registry.operation(
    "spgemm", "C = A @ B for CSR pairs (sparse-sparse composition)"
)
sptranspose_op = registry.operation(
    "sptranspose", "B = A^T for CSR (sorted column-major permutation)"
)


def _empty_csr(m: int, n: int, dtype) -> Csr:
    return csr_from_arrays(
        np.zeros(m + 1, np.int64), np.zeros(0, np.int32),
        np.zeros(0, dtype), (m, n),
    )


def _spgemm_maps(A: Csr, B: Csr):
    """Host structure pass: expansion maps for C = A·B.

    Returns ``(rows_a, b_start, b_len)`` where entry t of A contributes
    products against ``b_len[t]`` entries of B starting at ``b_start[t]``
    and lands in output row ``rows_a[t]``.
    """
    ai = np.asarray(A.indptr)
    ac = np.asarray(A.indices)
    bi = np.asarray(B.indptr)
    rows_a = np.repeat(np.arange(A.shape[0], dtype=np.int64), np.diff(ai))
    return rows_a, bi[ac], np.diff(bi)[ac]


def _coalesce_host(rows, cols, vals, m: int):
    """Sort (row, col, val) triplets, merge duplicate coordinates, build CSR.

    The shared accumulate pass: every space funnels its expanded triplets
    through this exact routine, which is what makes the output structure
    bitwise-identical across executors.
    """
    if rows.size == 0:
        return (
            np.zeros(m + 1, np.int64),
            np.zeros(0, np.int32),
            np.zeros(0, vals.dtype),
        )
    order = np.lexsort((cols, rows))
    r, c, v = rows[order], cols[order], vals[order]
    head = np.ones(r.size, bool)
    head[1:] = (r[1:] != r[:-1]) | (c[1:] != c[:-1])
    starts = np.flatnonzero(head)
    out_v = np.add.reduceat(v, starts)
    out_r, out_c = r[starts], c[starts]
    indptr = np.zeros(m + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(out_r, minlength=m))
    return indptr, out_c.astype(np.int32), out_v


@spgemm_op.register("reference")
def _spgemm_ref(ex, A: Csr, B: Csr) -> Csr:
    """Oracle: sequential per-row merge, mirroring Ginkgo's reference kernel."""
    m, _ = A.shape
    n = B.shape[1]
    ai = np.asarray(A.indptr)
    ac = np.asarray(A.indices)
    av = np.asarray(A.values)
    bi = np.asarray(B.indptr)
    bc = np.asarray(B.indices)
    bv = np.asarray(B.values)
    dtype = np.result_type(av.dtype, bv.dtype)
    indptr = np.zeros(m + 1, np.int64)
    out_cols: list = []
    out_vals: list = []
    for i in range(m):
        row_c: list = []
        row_v: list = []
        for t in range(int(ai[i]), int(ai[i + 1])):
            k = int(ac[t])
            s0, s1 = int(bi[k]), int(bi[k + 1])
            row_c.append(bc[s0:s1])
            row_v.append(av[t] * bv[s0:s1])
        if row_c:
            cat_c = np.concatenate(row_c)
            cat_v = np.concatenate(row_v)
            uniq, inv = np.unique(cat_c, return_inverse=True)
            acc = np.zeros(uniq.size, dtype)
            np.add.at(acc, inv, cat_v)
            out_cols.append(uniq.astype(np.int32))
            out_vals.append(acc)
            indptr[i + 1] = indptr[i] + uniq.size
        else:
            indptr[i + 1] = indptr[i]
    cols = np.concatenate(out_cols) if out_cols else np.zeros(0, np.int32)
    vals = np.concatenate(out_vals) if out_vals else np.zeros(0, dtype)
    return csr_from_arrays(indptr, cols, vals, (m, n))


@spgemm_op.register("xla")
def _spgemm_xla(ex, A: Csr, B: Csr) -> Csr:
    """Every product ``a_ik·b_kj`` as a device gather-multiply over exactly
    the expanded entries (at most :data:`_EXPAND_CHUNK` at a time),
    coalesced on the host."""
    m, _ = A.shape
    n = B.shape[1]
    rows_a, b_start, b_len = _spgemm_maps(A, B)
    total = int(b_len.sum())
    if total == 0:
        return _empty_csr(m, n, np.result_type(A.dtype, B.dtype))
    # product p pairs entry t[p] of A with entry idx[p] of B, in row order
    t = np.repeat(np.arange(b_len.size, dtype=np.int32), b_len)
    idx = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(b_len) - b_len - b_start, b_len)
    idx32 = idx.astype(np.int32)
    # in chunks of one padded shape, so the device holds one chunk's maps
    step = min(total, _EXPAND_CHUNK)
    prod = np.concatenate([
        np.asarray(_expand(A.values, B.values,
                           np.resize(t[lo:lo + step], step),
                           np.resize(idx32[lo:lo + step], step)))
        for lo in range(0, total, step)])[:total]
    cols = np.asarray(B.indices)[idx]
    rows = np.repeat(rows_a, b_len)
    indptr, out_c, out_v = _coalesce_host(rows, cols, prod, m)
    return csr_from_arrays(indptr, out_c, out_v, (m, n))


#: products a device expansion computes at once
_EXPAND_CHUNK = 1 << 24


@jax.jit
def _expand(a_vals, b_vals, t, idx):
    return a_vals[t] * b_vals[idx]


@sptranspose_op.register("reference")
def _sptranspose_ref(ex, A: Csr) -> Csr:
    """Oracle: host lexsort of the swapped triplet (Csr.transpose semantics)."""
    m, n = A.shape
    ai = np.asarray(A.indptr)
    cols = np.asarray(A.indices)
    rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(ai))
    order = np.lexsort((rows, cols))
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(cols, minlength=n))
    return csr_from_arrays(
        indptr, rows[order].astype(np.int32),
        np.asarray(A.values)[order], (n, m),
    )


@sptranspose_op.register("xla")
def _sptranspose_xla(ex, A: Csr) -> Csr:
    return _sptranspose_device(A)


@jax.jit
def _sptranspose_device(A: Csr) -> Csr:
    """Device transpose: nnz is invariant so every array keeps a static
    shape — the whole permutation (lexsort + bincount) stays on device, in
    one program."""
    m, n = A.shape
    rows = _csr_row_ids(A)
    order = jnp.lexsort((rows, A.indices))
    counts = jnp.bincount(A.indices, length=n)
    t_indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return Csr(
        indptr=t_indptr,
        indices=rows[order].astype(jnp.int32),
        values=A.values[order],
        shape=(n, m),
    )


def spgemm(A: Csr, B: Csr, *, executor=None) -> Csr:
    """``C = A @ B`` for CSR operands — executor-dispatched SpGEMM.

    Output rows are column-sorted and duplicate-free; structural nonzeros are
    kept even when numerically zero, so the result pattern is a pure function
    of the operand patterns.
    """
    if not isinstance(A, Csr) or not isinstance(B, Csr):
        raise TypeError(
            f"spgemm needs CSR operands, got {type(A).__name__} × "
            f"{type(B).__name__}"
        )
    m, k = A.shape
    k2, n = B.shape
    if k != k2:
        raise ValueError(f"spgemm shape mismatch: {A.shape} @ {B.shape}")
    if m == 0 or n == 0 or k == 0 or A.nnz == 0 or B.nnz == 0:
        return _empty_csr(m, n, np.result_type(A.dtype, B.dtype))
    return spgemm_op(A, B, executor=executor)


def sptranspose(A: Csr, *, executor=None) -> Csr:
    """``B = Aᵀ`` for CSR — executor-dispatched sparse transpose."""
    if not isinstance(A, Csr):
        raise TypeError(f"sptranspose needs a CSR operand, got {type(A).__name__}")
    m, n = A.shape
    if m == 0 or n == 0 or A.nnz == 0:
        return _empty_csr(n, m, A.dtype)
    return sptranspose_op(A, executor=executor)


def dot_batch(pairs, *, executor=None):
    """Batched dot products: ``[(x₁,y₁), ...] -> (len(pairs),)`` scalars.

    The communication-avoiding reduction: under the distributed context the
    local partials are stacked and reduced in ONE ``psum`` instead of one
    collective per dot — the enabler for pipelined Krylov methods, whose
    recurrences are restructured precisely so their dots batch here.  Outside
    the context it is just the stacked local dots.
    """
    ctx = _DIST_BLAS.get()
    if ctx is None:
        return jnp.stack(
            [dot_op(x, y, executor=executor) for x, y in pairs]
        )
    axis_name, mask = ctx
    local = jnp.stack(
        [
            dot_op(_masked(x, mask), _masked(y, mask), executor=executor)
            for x, y in pairs
        ]
    )
    return jax.lax.psum(local, axis_name)
