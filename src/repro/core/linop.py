"""The LinOp hierarchy — gko::LinOp for this repo.

Ginkgo's algorithm side rests on one abstraction: every matrix format, every
preconditioner, and every solver is a ``gko::LinOp`` composing through a
single ``apply`` interface.  That is what lets a solver precondition another
solver, a shifted system ``A + sigma * I`` be expressed without materializing
it, and a matrix-free user operator flow through any Krylov method unchanged.

This module is that abstraction.  It deliberately imports nothing from the
format / kernel layers, so every layer (``repro.sparse``, ``repro.precond``,
``repro.solvers``, ``repro.batch``) can build on it without cycles:

* :class:`LinOp` — the base: ``shape``, ``dtype``, simple ``apply(b)`` and
  advanced ``apply(alpha, b, beta, x)`` (Ginkgo's ``x = alpha*A*b + beta*x``),
  an ``executor`` slot threaded down through compositions, and ``__call__``
  aliasing the simple apply so a LinOp is a drop-in for the historical
  plain-callable preconditioner convention.
* :class:`Composition` — ``(A o B o ...) v`` applied right to left
  (``gko::Composition``).
* :class:`Sum` — ``(A + B + ...) v`` (``gko::Combination`` with unit
  coefficients; scale terms with :class:`ScaledIdentity` compositions).
* :class:`ScaledIdentity` — ``sigma * I``, the shifted-system building block:
  ``Sum(A, ScaledIdentity(sigma, n))`` is ``A + sigma*I`` without touching
  ``A``'s storage.
* :class:`Transpose` — lazy transpose over operators whose concrete type
  supports it (formats expose host-side ``transpose()``).
* :class:`MatrixFreeOp` — a user-supplied jittable apply with declared shape
  and dtype (``gko::matrix::Identity``-style wrappers, stencils, JVPs, ...).
* :class:`Identity` — the zero-storage identity operator (also the identity
  preconditioner; ``storage_bytes == 0``).

Executor threading: an ``executor=`` passed to ``apply`` overrides everything
below it in the operator tree; otherwise an operator's own ``executor``
attribute applies to its subtree; otherwise dispatch falls to the ambient
executor (:func:`repro.core.executor.current_executor`) at the registry level.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "LinOp",
    "Composition",
    "Sum",
    "ScaledIdentity",
    "Transpose",
    "MatrixFreeOp",
    "Identity",
    "as_linop",
]


class LinOp:
    """Base linear operator (gko::LinOp).

    Subclasses provide ``shape`` (as attribute or property), ``dtype``, and
    ``_apply(b, executor)``.  Everything else — the two ``apply`` arities,
    ``__call__``, the combinator sugar — comes from here.
    """

    #: executor this operator prefers; ``None`` defers to the caller/ambient.
    executor = None

    #: the distributed apply protocol (gko::experimental::distributed):
    #: operators whose storage is row-sharded over a mesh axis set this True
    #: and implement :meth:`local_operator`; the solver layer consults the
    #: flag to run the whole iteration under ``shard_map`` with per-shard
    #: kernels and ``psum`` reductions (see :mod:`repro.distributed.solvers`).
    is_distributed = False

    # -- subclass surface ------------------------------------------------------
    def _apply(self, b: jax.Array, executor) -> jax.Array:
        raise NotImplementedError(
            f"{type(self).__name__} does not implement _apply"
        )

    def local_operator(self, executor=None) -> "LinOp":
        """Per-shard operator for the distributed apply protocol.

        Called INSIDE a ``shard_map`` body on an operator whose array leaves
        carry a leading shard axis of size 1; returns the LinOp acting on
        this shard's padded-local vectors (collectives allowed — halo
        exchange, ``psum``).  Only meaningful when ``is_distributed``.
        """
        raise NotImplementedError(
            f"{type(self).__name__} is not a distributed operator "
            "(is_distributed is False)"
        )

    # -- the gko::LinOp::apply interface ---------------------------------------
    def apply(self, *args, executor=None) -> jax.Array:
        """``apply(b) -> A @ b`` or ``apply(alpha, b, beta, x) -> alpha*A@b + beta*x``.

        The four-argument form is Ginkgo's advanced apply; it is what lets IR
        fuse the residual update ``r = b - A x`` into one operator call:
        ``A.apply(-1.0, x, 1.0, b)``.

        Runs inside ``jax.named_scope("<ClassName>.apply")``, which names the
        operator's own glue (gathers, scatters, casts) on the device trace.
        """
        ex = executor if executor is not None else self.executor
        if len(args) not in (1, 4):
            raise TypeError(
                f"apply takes (b) or (alpha, b, beta, x); got {len(args)} arguments"
            )
        with jax.named_scope(f"{type(self).__name__}.apply"):
            if len(args) == 1:
                return self._apply(args[0], ex)
            alpha, b, beta, x = args
            return alpha * self._apply(b, ex) + beta * x

    def __call__(self, b: jax.Array) -> jax.Array:
        return self.apply(b)

    # -- reporting -------------------------------------------------------------
    @property
    def storage_bytes(self) -> int:
        """Bytes of operator-owned auxiliary storage (0 unless overridden).

        Preconditioners report their generated data here (the adaptive-
        precision metric); matrix formats report their buffers via
        ``memory_bytes``.
        """
        return 0

    # -- combinator sugar ------------------------------------------------------
    def __matmul__(self, other):
        if isinstance(other, LinOp):
            return Composition(self, other)
        return NotImplemented

    def __add__(self, other):
        if isinstance(other, LinOp):
            return Sum(self, other)
        return NotImplemented


def _shape_of(op) -> Optional[Tuple[int, int]]:
    return getattr(op, "shape", None)


def _dtype_of(op):
    return getattr(op, "dtype", None)


def _combined_dtype(ops):
    """Result dtype across operands; None when no operand declares one."""
    dtypes = [d for d in map(_dtype_of, ops) if d is not None]
    return jnp.result_type(*dtypes) if dtypes else None


def _child_apply(op, b, executor):
    """Apply a child operator, threading the resolved executor down."""
    if isinstance(op, LinOp):
        return op.apply(b, executor=executor)
    # tolerated foreign objects (bare callables) — no executor to thread
    return op(b)


class Composition(LinOp):
    """``Composition(A, B, ...) v = A(B(... v))`` — gko::Composition.

    Operands apply right to left, matching matrix-product order; shapes must
    chain (``A.shape[1] == B.shape[0]`` where both are known).
    """

    def __init__(self, *ops, executor=None):
        if not ops:
            raise ValueError("Composition needs at least one operand")
        for left, right in zip(ops, ops[1:]):
            ls, rs = _shape_of(left), _shape_of(right)
            if ls is not None and rs is not None and ls[1] != rs[0]:
                raise ValueError(
                    f"composition shape mismatch: {ls} cannot follow {rs}"
                )
        self.ops = tuple(ops)
        self.executor = executor

    @property
    def shape(self) -> Tuple[int, int]:
        first, last = _shape_of(self.ops[0]), _shape_of(self.ops[-1])
        if first is None or last is None:
            raise AttributeError("composition over shapeless operands")
        return (first[0], last[1])

    @property
    def dtype(self):
        return _combined_dtype(self.ops)

    def _apply(self, b, executor):
        for op in reversed(self.ops):
            b = _child_apply(op, b, executor)
        return b


class Sum(LinOp):
    """``Sum(A, B, ...) v = A v + B v + ...`` — gko::Combination (unit coeffs).

    All operands must share a shape (where known).  Scale a term by composing
    it with :class:`ScaledIdentity`.
    """

    def __init__(self, *ops, executor=None):
        if not ops:
            raise ValueError("Sum needs at least one operand")
        shapes = [s for s in map(_shape_of, ops) if s is not None]
        if shapes and any(s != shapes[0] for s in shapes[1:]):
            raise ValueError(f"sum over mismatched shapes {shapes}")
        self.ops = tuple(ops)
        self.executor = executor

    @property
    def shape(self) -> Tuple[int, int]:
        for op in self.ops:
            s = _shape_of(op)
            if s is not None:
                return s
        raise AttributeError("sum over shapeless operands")

    @property
    def dtype(self):
        return _combined_dtype(self.ops)

    def _apply(self, b, executor):
        acc = _child_apply(self.ops[0], b, executor)
        for op in self.ops[1:]:
            acc = acc + _child_apply(op, b, executor)
        return acc


class ScaledIdentity(LinOp):
    """``sigma * I`` on an ``n``-vector — the shifted-system building block.

    ``Sum(A, ScaledIdentity(sigma, n))`` expresses ``A + sigma*I`` without
    modifying ``A``'s stored values (Ginkgo applies shifts the same way in
    its eigensolver drivers).
    """

    def __init__(self, scale, n: int, dtype=None, executor=None):
        self.scale = scale
        self.n = int(n)
        self._dtype = jnp.dtype(dtype) if dtype is not None else None
        self.executor = executor

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n, self.n)

    @property
    def dtype(self):
        if self._dtype is not None:
            return self._dtype
        return jnp.result_type(self.scale)

    def _apply(self, b, executor):
        return jnp.asarray(self.scale, b.dtype) * b


class Identity(LinOp):
    """The identity operator — also the identity preconditioner.

    A real LinOp with ``storage_bytes == 0`` (it owns no generated data), not
    a bare function: benchmark and solver code can read storage, shape, and
    dtype uniformly across every ``M=``.
    """

    def __init__(self, n: Optional[int] = None, dtype=None):
        self.n = n
        self._dtype = jnp.dtype(dtype) if dtype is not None else None

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        return None if self.n is None else (self.n, self.n)

    @property
    def dtype(self):
        return self._dtype

    @property
    def storage_bytes(self) -> int:
        return 0

    def _apply(self, b, executor):
        return b


class Transpose(LinOp):
    """Lazy transpose of an operator whose concrete type supports it.

    The wrapped operator must expose ``transpose()`` (the sparse formats do,
    host-side); composed operators distribute through their operands
    recursively.  Operators without a transpose (matrix-free, solvers) raise
    ``NotImplementedError`` — exactly Ginkgo's ``Transposable`` contract.

    Executor threading matches the forward operator exactly: with no explicit
    ``executor=``, the wrap inherits the wrapped operator's pinned executor,
    so ``Transpose(Composition(...)).apply`` dispatches through the same
    ``Executor.launch_config`` path as ``Composition(...).apply`` — the
    implicit-layer backward (adjoint solve on ``Transpose(A)``) depends on
    the two passes landing in the same kernel space.
    """

    def __init__(self, op, executor=None):
        self.op = op
        self.executor = (
            executor if executor is not None else getattr(op, "executor", None)
        )
        self._t = _transpose(op)

    @property
    def shape(self) -> Tuple[int, int]:
        m, n = self.op.shape
        return (n, m)

    @property
    def dtype(self):
        return _dtype_of(self.op)

    def _apply(self, b, executor):
        return _child_apply(self._t, b, executor)


def _transpose(op):
    if isinstance(op, Transpose):
        return op.op
    if isinstance(op, (ScaledIdentity, Identity)):
        return op
    if isinstance(op, Composition):
        return Composition(
            *[Transpose(o) for o in reversed(op.ops)], executor=op.executor
        )
    if isinstance(op, Sum):
        return Sum(*[Transpose(o) for o in op.ops], executor=op.executor)
    t = getattr(op, "transpose", None)
    if callable(t):
        return t()
    raise NotImplementedError(
        f"{type(op).__name__} is not transposable (no transpose() support)"
    )


class MatrixFreeOp(LinOp):
    """A user-supplied jittable apply with declared shape/dtype.

    The matrix-free escape hatch: stencils, JVPs, anything ``v -> A v``.
    ``matvec`` must be a pure function of its vector argument (it is traced
    under ``jit`` inside the solvers).
    """

    def __init__(
        self,
        matvec: Callable[[jax.Array], jax.Array],
        shape: Optional[Tuple[int, int]] = None,
        dtype=None,
        executor=None,
    ):
        self.matvec = matvec
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = jnp.dtype(dtype) if dtype is not None else None
        self.executor = executor

    def _apply(self, b, executor):
        return self.matvec(b)


def as_linop(A, *, shape=None, dtype=None, executor=None) -> LinOp:
    """Coerce ``A`` into a LinOp.

    LinOps (formats, preconditioners, solvers, combinators) pass through
    unchanged; bare callables wrap into :class:`MatrixFreeOp`.  This is the
    single coercion point the solver layer uses, so plain-callable operators
    keep working everywhere a LinOp is expected.
    """
    if isinstance(A, LinOp):
        return A
    if callable(A):
        return MatrixFreeOp(A, shape=shape, dtype=dtype, executor=executor)
    raise TypeError(
        f"cannot interpret {type(A).__name__} as a linear operator; expected "
        "a LinOp (format / preconditioner / solver / combinator) or a "
        "callable v -> A @ v"
    )
