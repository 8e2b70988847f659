"""The system under test, driven through the library's normal entry points.

Each step is the configuration's part, found by name (``bench.Parts``):
the format's ``convert`` (one chip: ``sparse.ell_from_csr_host``; four:
``DistEll.from_matrix`` over row slabs), the preconditioner's ``generate``
(``make_preconditioner``) and the solver's ``solve`` (``krylov.cg``), on
``default_executor()``.  On one chip the solve is jitted with the generated
preconditioner as an argument; on several chips the program generates the
preconditioner shard by shard inside its distributed solve, from the kind
and options the configuration states.

Each step of a request runs inside a harness span (:data:`SPANS`): a
``jax.profiler.TraceAnnotation``, so a traced run can name what the host did
in each gap of the device, and a host-clock reading, which
``precond_generate_s`` reads.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

#: harness spans, in the order a request passes through them
SPANS = ("rhs", "convert", "generate", "h2d", "solve", "d2h")

#: solve-path ops whose kernel space a run prints
SOLVE_PATH_OPS = ("spmv_dot_ell", "spmv_ell", "axpy_norm", "block_jacobi_apply")


class Library:
    """The library, set up for one cell (``bench.Cell``)."""

    def __init__(self, cell, system):
        import jax

        from repro.core import default_executor
        from repro.solvers.common import Stop

        config = cell.config
        self.config = config
        self.parts = cell.parts
        self.system = system
        self.chips = cell.chips
        self.distributed = bool(cell.parts.format.DISTRIBUTED)
        self.fixed = cell.mix["operator"] == "fixed"
        self.dtype = np.dtype(config["dtype"])
        self.opts = {k: v for k, v in config["precond"].items() if k != "kind"}
        self.ex = default_executor()
        self.stop = Stop(
            max_iters=int(config["stop"]["max_iters"]),
            reduction_factor=float(config["stop"]["reduction_factor"]),
        )
        self.device = jax.devices()[0]
        self.A = None
        self.M = None
        self._jit_solve = jax.jit(self._solve_one_chip)

    # -- the library's entry points ------------------------------------------
    def convert(self, values):
        import jax

        return jax.block_until_ready(
            self.parts.format.convert(self.system, values, self.config))

    def generate(self, A):
        import jax

        if self.distributed:
            # the distributed solve generates shard-local factors per call
            return self.config["precond"]["kind"]
        return jax.block_until_ready(self.parts.precond.generate(A, self.opts, self.ex))

    def _solve_one_chip(self, A, M, b):
        return self.parts.solver.solve(A, b, M, self.stop, self.ex)

    def solve(self, A, M, b):
        if not self.distributed:
            return self._jit_solve(A, M, b)
        return self.parts.solver.solve(A, b, M, self.stop, self.ex, precond_opts=self.opts)

    # -- set-up and requests --------------------------------------------------
    def setup(self, values) -> None:
        """Convert and generate the fixed operator (fixed-operator mixes)."""
        if self.fixed:
            self.A = self.convert(values)
            self.M = self.generate(self.A)

    def serve(self, request, clock: dict):
        """One whole request: what the mix changed is converted and generated,
        the system solved, the solution read back.  Adds each span's host
        seconds to ``clock``; returns ``(x, iterations)`` on the host."""
        import jax

        with span("rhs", clock):
            values, b_host = request.values, request.b
        if self.fixed:
            A, M = self.A, self.M
        else:
            with span("convert", clock):
                A = self.convert(values)
            with span("generate", clock):
                M = self.generate(A)
        with span("h2d", clock):
            b = jax.block_until_ready(jax.device_put(b_host))
        with span("solve", clock):
            x, iterations = jax.block_until_ready(self.solve(A, M, b))
        with span("d2h", clock):
            x_host = np.asarray(x)
            iterations = int(iterations)
        return x_host, iterations

    def served_spaces(self) -> dict:
        """Kernel space that served each solve-path op this executor ran."""
        from repro.core import registry

        return {
            op: registry.operation(op).resolve(self.ex)[0]
            for op in sorted(self.ex.dispatch_log)
            if op in SOLVE_PATH_OPS
        }


@contextlib.contextmanager
def span(name: str, clock: dict):
    """A harness span: profiler annotation plus host seconds into ``clock``."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    clock[name] = clock.get(name, 0.0) + time.perf_counter() - t0
