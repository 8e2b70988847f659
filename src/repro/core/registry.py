"""Operation registry — the analogue of ``GKO_REGISTER_OPERATION`` + dynamic dispatch.

Ginkgo's core algorithms never name a backend: they submit *operations* to an
executor, and dynamic polymorphism selects the backend kernel at run time.  Here,
an :class:`Operation` is a named dispatch point; implementations are registered
per *kernel space* (``reference`` / ``xla`` / ``pallas``), and the active
:class:`~repro.core.executor.Executor` selects which space's implementation runs
(at trace time — JAX's analogue of run time for kernel selection).

Ginkgo semantics preserved:

* an executor without a registered kernel raises :class:`NotCompiledError`
  (Ginkgo's ``gko::NotCompiled``) in strict mode;
* in permissive mode the executor's fallback chain is walked
  (``pallas -> xla -> reference``), mirroring how applications in practice pair
  a hardware backend with the reference implementation for missing kernels;
* every implementation receives the executor as first argument so it can read
  the hardware parameter table (Ginkgo kernels receive
  ``std::shared_ptr<const Executor>``).

Every dispatch runs inside ``jax.named_scope(<op name>)``, so each device op
it emits carries the op's name in its HLO ``op_name`` metadata, which a
profiler trace reports beside the op.  The scope changes metadata only, never
the compiled program, and under ``jit`` it costs something only while
tracing.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Tuple

import jax

# stdlib-only modules, safe to import before JAX-heavy layers come up
from repro.observability import events as _events
from repro.observability import trace as _trace

__all__ = [
    "NotCompiledError",
    "Operation",
    "operation",
    "register",
    "registered_spaces",
    "all_operations",
    "instantiate_common",
]


class NotCompiledError(NotImplementedError):
    """Raised when an operation has no kernel for the executor's spaces.

    Analogue of ``gko::NotCompiled`` — in Ginkgo this means "this module was not
    compiled for this backend"; here it means "no implementation registered for
    any kernel space this executor may use".
    """


_OPERATIONS: Dict[str, "Operation"] = {}


class Operation:
    """A named, executor-dispatched operation (one ``GKO_REGISTER_OPERATION``)."""

    def __init__(self, name: str, doc: str = ""):
        if name in _OPERATIONS:
            raise ValueError(f"operation {name!r} already defined")
        self.name = name
        self.__doc__ = doc or f"executor-dispatched operation {name!r}"
        self._impls: Dict[str, Callable[..., Any]] = {}
        _OPERATIONS[name] = self

    # -- registration ---------------------------------------------------------
    def register(self, space: str) -> Callable[[Callable], Callable]:
        """Decorator: register ``fn(executor, *args, **kw)`` for ``space``."""

        def deco(fn: Callable) -> Callable:
            if space in self._impls:
                raise ValueError(
                    f"operation {self.name!r} already has a {space!r} kernel"
                )
            self._impls[space] = fn
            return fn

        return deco

    def resolve(self, executor) -> Tuple[str, Callable[..., Any]]:
        """``(kernel_space, implementation)`` that will serve ``executor``."""
        spaces = (executor.kernel_space,) if executor.strict else executor.spaces
        for space in spaces:
            impl = self._impls.get(space)
            if impl is not None:
                return space, impl
        raise NotCompiledError(
            f"operation {self.name!r} has no kernel for executor "
            f"{executor.name!r} (searched spaces {spaces}; "
            f"registered: {sorted(self._impls)})"
        )

    def implementation_for(self, executor) -> Callable[..., Any]:
        return self.resolve(executor)[1]

    def supports(self, executor) -> bool:
        """Does any of the executor's kernel spaces serve this operation?

        The *optional-op* capability probe: algorithm layers (the fused Krylov
        paths) ask before relying on an op that only some backends register,
        and fall back to the portable formulation when the answer is False —
        instead of tripping :class:`NotCompiledError` at dispatch time.
        """
        spaces = (executor.kernel_space,) if executor.strict else executor.spaces
        return any(space in self._impls for space in spaces)

    def space_used(self, executor) -> str:
        """Which kernel space would serve this executor (for tests/telemetry)."""
        spaces = (executor.kernel_space,) if executor.strict else executor.spaces
        for space in spaces:
            if space in self._impls:
                return space
        raise NotCompiledError(self.name)

    # -- dispatch ---------------------------------------------------------------
    def __call__(self, *args, executor=None, **kwargs):
        from repro.core.executor import current_executor

        ex = executor if executor is not None else current_executor()
        space, impl = self.resolve(ex)
        with jax.named_scope(self.name):
            if not _trace.TRACING:
                # hot path: one module-attribute check, no clock read, no
                # allocation beyond the scope's name-stack entry.
                out = impl(ex, *args, **kwargs)
                ex._note_dispatch(self.name)
                return out
            return self._traced_call(ex, space, impl, args, kwargs)

    def _traced_call(self, ex, space, impl, args, kwargs):
        """Instrumented dispatch: structured event + Chrome trace span.

        Wall time here is dispatch/trace-time cost (under ``jit`` each op
        runs once while tracing), so the event records launch *structure*:
        op, space, shapes, resolved LaunchConfig, bytes-moved estimate.
        Device time is read from a profiler trace, by the op's scope.
        """
        tracer = _trace.get_tracer()
        ex._last_launch_config = None  # repopulated if the kernel resolves one
        t0 = time.perf_counter()
        out = impl(ex, *args, **kwargs)
        wall_us = (time.perf_counter() - t0) * 1e6
        ts_us = tracer.rel_us(t0) if tracer is not None else 0.0
        event = _events.make_event(
            op=self.name,
            space=space,
            executor=ex,
            launch=ex._last_launch_config,
            wall_us=wall_us,
            ts_us=ts_us,
            operands=args,
            out=out,
        )
        ex._note_dispatch(self.name, event)
        if tracer is not None:
            tracer.complete(
                self.name, ts_us, wall_us, cat="dispatch", args=event.to_args()
            )
        return out

    def __repr__(self) -> str:
        return f"Operation({self.name!r}, spaces={sorted(self._impls)})"


def operation(name: str, doc: str = "") -> Operation:
    """Create (or fetch) the named operation."""
    if name in _OPERATIONS:
        return _OPERATIONS[name]
    return Operation(name, doc)


def register(name: str, space: str) -> Callable[[Callable], Callable]:
    """Shorthand: ``@register("spmv_ell", "pallas")``."""
    return operation(name).register(space)


def registered_spaces(name: str) -> tuple:
    return tuple(sorted(_OPERATIONS[name]._impls))


def all_operations() -> Dict[str, "Operation"]:
    return dict(_OPERATIONS)


def instantiate_common(
    name: str,
    skeleton: Callable[..., Any],
    space_params: Dict[str, Dict[str, Any]],
) -> Operation:
    """Bind one kernel *skeleton* to several kernel spaces — the ``common/`` folder.

    Ginkgo keeps CUDA/HIP-identical kernels in ``common/`` parameterized by
    architecture-specific constants, and each backend includes the skeleton with
    its own parameter values.  ``instantiate_common`` is the JAX analogue: the
    skeleton is a function ``skeleton(executor, *args, **bound_params)`` and each
    kernel space binds its own parameter dict.

    Example::

        instantiate_common(
            "subgroup_reduce_bench",
            _reduce_skeleton,
            {
                "pallas": dict(block_rows=256),
                "xla": dict(block_rows=1024),
            },
        )
    """
    op = operation(name)
    for space, params in space_params.items():
        bound = functools.partial(skeleton, **params)
        functools.update_wrapper(bound, skeleton)
        op.register(space)(bound)
    return op
