"""identity: no preconditioner (``M = None`` in the program)."""

import numpy as np


def generate(A, opts: dict, executor):
    if opts:
        raise ValueError(f"the identity takes no options, got {sorted(opts)}")
    return None


def operand_bytes(n: int, opts: dict, itemsize: int) -> int:
    return 0


def reference_operand(system, values, opts: dict) -> np.ndarray:
    return np.zeros(0)


def reference_apply(t, v, opts: dict):
    return v
