"""Shared solver machinery: results, stopping criteria, scalar preconditioners.

Solvers are written against executor-dispatched BLAS-1/SpMV operations and
``jax.lax`` control flow only, so one solver source serves every executor
(the paper's separation of algorithm from kernels) and distributes under
``pjit`` by sharding the operands (dots become global collectives under GSPMD).

Operators are unified under :mod:`repro.core.linop`: formats, preconditioners,
and solver factories are all LinOps composing through one ``apply``.
:class:`LinearOperator` survives only as a deprecated back-compat shim over
:func:`repro.core.linop.as_linop`.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.linop import Identity, LinOp, as_linop
from repro.sparse.formats import Coo, Csr, Dense, Ell, Sellp

MatrixLike = Union[
    LinOp, Coo, Csr, Ell, Sellp, Dense, Callable[[jax.Array], jax.Array]
]

__all__ = [
    "LinearOperator",
    "SolveResult",
    "Stop",
    "ScalarJacobi",
    "probe_symmetry",
    "ensure_symmetric",
    "jacobi_preconditioner",
    "block_jacobi_preconditioner",
    "identity_preconditioner",
]


class LinearOperator(LinOp):
    """Deprecated back-compat shim — use the operand directly, or
    :func:`repro.core.linop.as_linop`.

    Every sparse format, preconditioner, and solver factory is now itself a
    :class:`~repro.core.linop.LinOp`; wrapping one in ``LinearOperator`` adds
    nothing.  The class delegates to ``as_linop`` so existing call sites keep
    the historical behavior (format -> registry-dispatched SpMV, callable ->
    matrix-free apply).
    """

    def __init__(self, A: MatrixLike, executor=None):
        warnings.warn(
            "repro.solvers.common.LinearOperator is deprecated: formats, "
            "preconditioners and solvers are LinOps — pass them directly "
            "(or use repro.core.linop.as_linop for bare callables)",
            DeprecationWarning,
            stacklevel=2,
        )
        self.A = A
        self.op = as_linop(A)
        self.executor = executor

    @property
    def shape(self):
        return getattr(self.op, "shape", None)

    @property
    def dtype(self):
        return getattr(self.op, "dtype", None)

    def _apply(self, x: jax.Array, executor) -> jax.Array:
        ex = executor if executor is not None else self.executor
        return self.op.apply(x, executor=ex)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SolveResult:
    x: jax.Array
    iterations: jax.Array  # int32
    residual_norm: jax.Array
    converged: jax.Array  # bool
    #: per-iteration residual norms when the solve ran with ``history=``
    #: (a fixed-capacity ring buffer, NaN in unfilled slots — see
    #: :mod:`repro.observability.convergence`); None otherwise.
    history: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class Stop:
    """Combined stopping criterion (gko::stop::Combined).

    Converged when ||r|| <= max(reduction_factor * ||b||, abs_tol), or stopped
    when iterations reach max_iters.
    """

    max_iters: int = 1000
    reduction_factor: float = 1e-6
    abs_tol: float = 0.0

    def threshold(self, bnorm: jax.Array) -> jax.Array:
        if self.reduction_factor == 0.0 and self.abs_tol == 0.0:
            # Without this check an abs_tol-only criterion mistyped as
            # (0.0, 0.0) silently yields threshold 0.0 — a solver that can
            # never converge and always burns max_iters.
            raise ValueError(
                "degenerate stopping criterion: reduction_factor=0.0 with "
                "abs_tol=0.0 can never be satisfied; set abs_tol > 0 for "
                "absolute-tolerance-only stopping or reduction_factor > 0 "
                "for relative stopping"
            )
        return jnp.maximum(self.reduction_factor * bnorm, self.abs_tol)


# -- preconditioners -----------------------------------------------------------

extract_diag_op = registry.operation("extract_diagonal")


@extract_diag_op.register("reference")
def _extract_diag_ref(ex, A):
    if isinstance(A, Dense):
        return jnp.diagonal(A.values)
    if isinstance(A, Csr):
        nnz = A.values.shape[0]
        rows = (
            jnp.searchsorted(A.indptr, jnp.arange(nnz, dtype=jnp.int32), side="right")
            - 1
        )
        n = min(A.shape)
        hit = (rows == A.indices) & (rows < n)
        return jnp.zeros(n, A.values.dtype).at[jnp.where(hit, rows, 0)].add(
            jnp.where(hit, A.values, 0.0)
        )
    if isinstance(A, Coo):
        n = min(A.shape)
        hit = A.row_idx == A.col_idx
        return jnp.zeros(n, A.values.dtype).at[jnp.where(hit, A.row_idx, 0)].add(
            jnp.where(hit, A.values, 0.0)
        )
    if isinstance(A, Ell):
        m, k = A.values.shape
        rows = jnp.broadcast_to(jnp.arange(m)[:, None], (m, k))
        hit = A.col_idx == rows
        return jnp.sum(jnp.where(hit, A.values, 0.0), axis=1)[: min(A.shape)]
    # Fallback (Sellp): densify — reference semantics are allowed to be slow.
    from repro.sparse import ops as sparse_ops

    return jnp.diagonal(sparse_ops.to_dense(A, executor=ex))


@extract_diag_op.register("xla")
def _extract_diag_xla(ex, A):
    return _extract_diag_ref(ex, A)


class ScalarJacobi(LinOp):
    """Scalar Jacobi LinOp: ``M^{-1} v = inv_diag * v``.

    ``inv_diag`` may be held in a reduced storage precision (the adaptive
    knob); the apply upcasts to the vector's dtype, so reduced precision only
    shrinks the stored footprint, never the arithmetic.
    """

    def __init__(self, inv_diag: jax.Array):
        self.inv_diag = inv_diag

    @property
    def shape(self):
        n = self.inv_diag.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.inv_diag.dtype

    @property
    def storage_bytes(self) -> int:
        return int(self.inv_diag.size) * self.inv_diag.dtype.itemsize

    def _apply(self, v: jax.Array, executor) -> jax.Array:
        return self.inv_diag.astype(v.dtype) * v

    def transpose(self) -> "ScalarJacobi":
        # Diagonal operators are symmetric: M^{-T} = M^{-1}.
        return self


# a pytree, so a solve can be jitted with the preconditioner as an argument
jax.tree_util.register_pytree_node(
    ScalarJacobi, lambda s: ((s.inv_diag,), None), lambda _, c: ScalarJacobi(*c)
)


def probe_symmetry(A, *, seed: int = 0, rtol: float = 1e-4) -> Optional[bool]:
    """Cheap seeded two-vector symmetry probe: is ``u^T A v == v^T A u``?

    Returns ``True``/``False`` for concrete square real-dtype format operands,
    ``None`` when the question cannot be answered cheaply (traced values under
    ``jit``/``vmap``, matrix-free operators, non-square or complex operands).
    The probe runs entirely in host numpy so it leaves no trace in any
    executor's dispatch log — launch-count pins never see it.

    A single random pair catches every nonsymmetric matrix outside a measure-
    zero set; the tolerance is relative to ``|u|^T |A| |v|`` so cancellation-
    heavy but symmetric operands do not false-positive.
    """
    values = getattr(A, "values", None)
    shape = getattr(A, "shape", None)
    if values is None or shape is None or shape[0] != shape[1]:
        return None
    if isinstance(values, jax.core.Tracer):
        return None
    if jnp.issubdtype(jnp.asarray(values).dtype, jnp.complexfloating):
        return None
    try:
        from repro.sparse.formats import csr_host_arrays

        indptr, indices, vals = csr_host_arrays(A)
    except Exception:
        return None
    import numpy as np

    n = shape[0]
    rng = np.random.default_rng(seed)
    u = rng.standard_normal(n)
    v = rng.standard_normal(n)
    vals = np.asarray(vals, dtype=np.float64)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    cols = np.asarray(indices, dtype=np.int64)
    uAv = float(np.sum(u[rows] * vals * v[cols]))
    vAu = float(np.sum(v[rows] * vals * u[cols]))
    scale = float(np.sum(np.abs(u[rows]) * np.abs(vals) * np.abs(v[cols])))
    return abs(uAv - vAu) <= rtol * max(scale, 1.0)


def ensure_symmetric(A, *, solver: str, strict: bool = True, seed: int = 0) -> None:
    """Raise a clear error when an SPD-only solver receives a nonsymmetric A.

    ``cg``/``fcg`` silently diverge or converge to garbage on nonsymmetric
    operators; this guard turns that silent failure into a loud one at
    factory/generation time.  ``strict=False`` is the escape hatch for users
    who know their operator is symmetric in exact arithmetic (or accept the
    risk).  Probes that cannot decide (traced values, matrix-free A) pass.
    """
    if not strict:
        return
    sym = probe_symmetry(A, seed=seed)
    if sym is False:
        raise ValueError(
            f"{solver} requires a symmetric (SPD) operator, but a seeded "
            "symmetry probe found u^T A v != v^T A u. CG-family iterations "
            "silently produce garbage on nonsymmetric systems - use gmres, "
            "bicgstab, or cgs instead, or pass strict=False if the operator "
            "is symmetric in exact arithmetic."
        )


def jacobi_preconditioner(
    A: MatrixLike, executor=None, *, adaptive: Union[bool, str] = False
) -> Callable:
    """Scalar Jacobi: M^{-1} v = v / diag(A) (gko::preconditioner::Jacobi, bs=1).

    ``adaptive=True`` stores the inverse diagonal in the cheapest 16-bit
    precision whose range fits (fp16, else bf16); a dtype forces that storage.
    Arithmetic stays in the vector's precision either way.
    """
    d = extract_diag_op(A, executor=executor)
    safe = jnp.where(jnp.abs(d) > 0, d, jnp.ones_like(d))
    inv = jnp.where(jnp.abs(d) > 0, 1.0 / safe, jnp.ones_like(d))
    if adaptive is True:
        maxabs = float(jnp.max(jnp.abs(inv))) if inv.size else 0.0
        inv = inv.astype(jnp.float16 if maxabs < 65504.0 else jnp.bfloat16)
    elif adaptive:
        inv = inv.astype(jnp.dtype(adaptive))
    return ScalarJacobi(inv)


def block_jacobi_preconditioner(
    A: MatrixLike,
    block_size: Optional[int] = None,
    executor=None,
    *,
    blocks=None,
    adaptive: Union[bool, str] = False,
    tau: Optional[float] = None,
) -> Callable:
    """Block-Jacobi (gko::preconditioner::Jacobi with block size > 1):
    M^{-1} = blockdiag(A_11^{-1}, A_22^{-1}, ...) — Ginkgo's flagship
    preconditioner.

    Delegates to :mod:`repro.precond.block_jacobi`: host-side block discovery
    (``blocks`` pins explicit pointers, e.g. from
    :func:`repro.precond.natural_blocks`), format-aware extraction, batched
    Gauss-Jordan inversion, and an executor-dispatched apply.
    ``block_size=None`` takes the executor's cooperative-subgroup width from
    the hardware table (Ginkgo tunes Jacobi storage to the subwarp size);
    ``adaptive`` selects per-block storage precision (see
    :func:`repro.precond.block_jacobi`).  The returned object is callable and
    reports ``storage_bytes`` / ``precision_counts``.
    """
    from repro.precond import block_jacobi as _block_jacobi

    return _block_jacobi(
        A,
        block_size,
        blocks=blocks,
        adaptive=adaptive,
        executor=executor,
        **({} if tau is None else {"tau": tau}),
    )


#: the identity preconditioner — a real LinOp (``storage_bytes == 0``), not a
#: bare function, so benchmark code reads storage/shape uniformly across every
#: ``M=``.  Remains callable (``identity_preconditioner(v) -> v``) for all
#: historical call sites.
identity_preconditioner = Identity()
