"""jacobi: the program's scalar Jacobi (``make_preconditioner``), and as
its reference the inverse diagonal in float64."""

import numpy as np


def generate(A, opts: dict, executor):
    from repro.precond import make_preconditioner

    return make_preconditioner(A, "jacobi", executor=executor, **opts)


def operand_bytes(n: int, opts: dict, itemsize: int) -> int:
    return n * itemsize


def reference_operand(system, values, opts: dict) -> np.ndarray:
    return 1.0 / values[system.diag_pos].astype(np.float64)


def reference_apply(t, v, opts: dict):
    return t * v
