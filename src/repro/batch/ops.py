"""Executor-dispatched batched operations: SpMV per batched format + BLAS-1.

Same three-space contract as the single-system ops (:mod:`repro.sparse.ops`):

* reference — python-loop-over-systems semantics (the sequential oracle;
  Ginkgo's reference kernels iterate the batch in a for loop);
* xla       — one vectorized formulation over the whole batch (``vmap`` /
  broadcast einsum) the compiler fuses into a single launch;
* pallas    — registered from :mod:`repro.kernels.spmv_batch_ell` (batch on
  the outer grid axis; bound when ``repro`` is imported).

All batched vectors are ``(nb, n)``; batched scalars are ``(nb,)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.batch.formats import BatchCsr, BatchEll
from repro.core import registry
from repro.sparse.ops import _csr_row_ids

__all__ = [
    "apply_batch",
    "batch_dot",
    "batch_axpy",
    "batch_axpy_norm",
    "batch_scal",
    "batch_norm2",
]

# =============================================================================
# Batched SpMV — CSR (shared pattern)
# =============================================================================

spmv_batch_csr = registry.operation(
    "spmv_batch_csr", "Y[b] = A[b] @ X[b] for shared-pattern batched CSR"
)


@spmv_batch_csr.register("reference")
def _spmv_batch_csr_ref(ex, A: BatchCsr, X: jax.Array) -> jax.Array:
    # one system at a time — sequential reference semantics
    rows = _csr_row_ids(A.system(0))
    outs = []
    for b in range(A.num_batch):
        y = jnp.zeros((A.shape[0],), dtype=jnp.result_type(A.values, X))
        outs.append(y.at[rows].add(A.values[b] * X[b, A.indices]))
    return jnp.stack(outs)


@spmv_batch_csr.register("xla")
def _spmv_batch_csr_xla(ex, A: BatchCsr, X: jax.Array) -> jax.Array:
    rows = _csr_row_ids(A.system(0))
    contrib = A.values * X[:, A.indices]  # (nb, nnz)
    seg = jax.vmap(
        lambda c: jax.ops.segment_sum(
            c, rows, num_segments=A.shape[0], indices_are_sorted=True
        )
    )
    return seg(contrib)


# =============================================================================
# Batched SpMV — ELL (shared column block)
# =============================================================================

spmv_batch_ell = registry.operation(
    "spmv_batch_ell", "Y[b] = A[b] @ X[b] for shared-pattern batched ELL"
)


@spmv_batch_ell.register("reference")
def _spmv_batch_ell_ref(ex, A: BatchEll, X: jax.Array) -> jax.Array:
    outs = []
    for b in range(A.num_batch):
        gathered = X[b][A.col_idx]  # (m, k)
        outs.append(jnp.sum(A.values[b] * gathered, axis=1))
    return jnp.stack(outs)


@spmv_batch_ell.register("xla")
def _spmv_batch_ell_xla(ex, A: BatchEll, X: jax.Array) -> jax.Array:
    gathered = X[:, A.col_idx]  # (nb, m, k) — shared indices, batched gather
    return jnp.einsum("bmk,bmk->bm", A.values, gathered)


# =============================================================================
# Batched BLAS-1 (row-wise over the batch axis)
# =============================================================================

batch_dot_op = registry.operation("batch_blas_dot")
batch_axpy_op = registry.operation("batch_blas_axpy")
batch_scal_op = registry.operation("batch_blas_scal")
batch_norm2_op = registry.operation("batch_blas_norm2")


@batch_dot_op.register("reference")
def _batch_dot_ref(ex, X, Y):
    return jnp.stack([jnp.vdot(X[b], Y[b]) for b in range(X.shape[0])])


@batch_dot_op.register("xla")
def _batch_dot_xla(ex, X, Y):
    return jnp.einsum("bn,bn->b", X, Y)


@batch_axpy_op.register("reference")
def _batch_axpy_ref(ex, alpha, X, Y):
    return jnp.stack([alpha[b] * X[b] + Y[b] for b in range(X.shape[0])])


@batch_axpy_op.register("xla")
def _batch_axpy_xla(ex, alpha, X, Y):
    return alpha[:, None] * X + Y


@batch_scal_op.register("reference")
def _batch_scal_ref(ex, alpha, X):
    return jnp.stack([alpha[b] * X[b] for b in range(X.shape[0])])


@batch_scal_op.register("xla")
def _batch_scal_xla(ex, alpha, X):
    return alpha[:, None] * X


@batch_norm2_op.register("reference")
def _batch_norm2_ref(ex, X):
    return jnp.stack(
        [jnp.sqrt(jnp.vdot(X[b], X[b]).real) for b in range(X.shape[0])]
    )


@batch_norm2_op.register("xla")
def _batch_norm2_xla(ex, X):
    return jnp.sqrt(jnp.einsum("bn,bn->b", X, X))


# =============================================================================
# apply_batch — gko::batch::BatchLinOp::apply
# =============================================================================

_BATCH_FORMAT_OP = {
    BatchCsr: spmv_batch_csr,
    BatchEll: spmv_batch_ell,
}


def apply_batch(A, X: jax.Array, *, executor=None) -> jax.Array:
    """``Y[b] = A[b] @ X[b]``: format-dispatch then executor-dispatch.

    Composed batched operators (``BatchSum``, ``BatchComposition``, ...)
    delegate to their own ``apply``; the format fast path keeps dispatching
    straight into the kernel registry.
    """
    try:
        op = _BATCH_FORMAT_OP[type(A)]
    except KeyError:
        from repro.batch.formats import BatchMatrixLinOp
        from repro.batch.linop import BatchLinOp

        # a BatchMatrixLinOp not in the table is an unregistered *format* —
        # its _apply would bounce right back here, so fail loudly instead
        if isinstance(A, BatchLinOp) and not isinstance(A, BatchMatrixLinOp):
            return A.apply(X, executor=executor)
        raise TypeError(
            f"no batched spmv registered for format {type(A)}"
        ) from None
    return op(A, X, executor=executor)


def batch_dot(X, Y, *, executor=None):
    return batch_dot_op(X, Y, executor=executor)


def batch_axpy(alpha, X, Y, *, executor=None):
    return batch_axpy_op(alpha, X, Y, executor=executor)


def batch_scal(alpha, X, *, executor=None):
    return batch_scal_op(alpha, X, executor=executor)


def batch_norm2(X, *, executor=None):
    return batch_norm2_op(X, executor=executor)


def batch_axpy_norm(alpha, X, Y, *, executor=None):
    """Fused ``(Z, ‖Z[b]‖²)`` with ``Z = alpha[:, None] * X + Y``.

    Delegates to the SAME ``axpy_norm`` operation the single-vector Krylov
    loops use (its implementations handle both 1-D and ``(nb, n)`` operands),
    so the batched convergence-mask reduction and the single-system stopping
    norm share one fused implementation per kernel space instead of
    recomputing the mask norm with separate dot launches.
    """
    from repro.sparse.ops import axpy_norm_op

    return axpy_norm_op(alpha, X, Y, executor=executor)
