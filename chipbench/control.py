"""The control: the plain reference put in the program's place, one
precision down.

The configuration's solver as its plain reference (``solvers/<solver>.py``:
``reference``) on the system module's own device operator (shifted
slices), with the configuration's preconditioner built from the host CSR in
float64 (``preconds/<kind>.py``: ``reference_operand``, applied by
``reference_apply``), the configuration's stopping rule, and every array in
the precision below the stated one (:data:`LOWER`).  Its answers go through
the same check as the program's; a limit is sound only where the control
fails it.  Nothing here imports the program, and the benchmark's own runs
never run it.
"""

from __future__ import annotations

import numpy as np

#: the precision a control computes in, for each precision a configuration
#: states; a configuration in any other is refused
LOWER = {"float64": "float32", "float32": "bfloat16"}


def answers(cell, system, pool, order: list) -> list:
    """``[(pool index, x)]``: the control's answers to the requests
    ``pool[k]`` for ``k`` in ``order``, as a run would send them."""
    import jax
    import jax.numpy as jnp

    parts, config = cell.parts, cell.config
    dtype = jnp.dtype(LOWER[config["dtype"]])
    stop = config["stop"]
    opts = {k: v for k, v in config["precond"].items() if k != "kind"}
    operands, solvers = {}, {}
    out = []
    for k in order:
        req = pool[k]
        if k not in operands:
            operands[k] = jnp.asarray(
                parts.precond.reference_operand(system, req.values, opts), dtype)
        if req.shift not in solvers:
            apply_a = parts.system.device_operator(config["system"], req.shift)
            solvers[req.shift] = jax.jit(lambda b, t, a=apply_a: parts.solver.reference(
                a, lambda v: parts.precond.reference_apply(t, v, opts), b,
                float(stop["reduction_factor"]), int(stop["max_iters"])))
        x = solvers[req.shift](jnp.asarray(req.b, dtype), operands[k])
        out.append((k, np.asarray(x.astype(jnp.float32))))
    return out
