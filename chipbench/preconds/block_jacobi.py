"""block_jacobi: the program's block-Jacobi (``make_preconditioner``), and
as its reference the diagonal blocks inverted in float64 by ``numpy``,
applied as a batched matrix-vector product."""

import numpy as np


def generate(A, opts: dict, executor):
    from repro.precond import make_preconditioner

    return make_preconditioner(A, "block_jacobi", executor=executor, **opts)


def operand_bytes(n: int, opts: dict, itemsize: int) -> int:
    """The inverted blocks, each read once; a ragged last block counts whole."""
    bs = int(opts["block_size"])
    return -(-n // bs) * bs * bs * itemsize


def block_inverses(indptr, indices, values, n: int, bs: int) -> np.ndarray:
    """``(n / bs, bs, bs)`` inverses of the diagonal blocks, in float64."""
    if n % bs:
        raise ValueError(f"block size {bs} does not divide {n} rows")
    rows = np.repeat(np.arange(n), np.diff(indptr))
    keep = indices // bs == rows // bs
    blocks = np.zeros((n // bs, bs, bs))
    blocks[rows[keep] // bs, rows[keep] % bs, indices[keep] % bs] = values[keep]
    return np.linalg.inv(blocks)


def reference_operand(system, values, opts: dict) -> np.ndarray:
    return block_inverses(system.indptr, system.indices, values, system.n,
                          int(opts["block_size"]))


def reference_apply(t, v, opts: dict):
    import jax.numpy as jnp

    bs = int(opts["block_size"])
    return jnp.einsum("bij,bj->bi", t, v.reshape(-1, bs)).reshape(-1)
