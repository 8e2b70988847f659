"""cg: the program's ``krylov.cg`` from ``x0 = 0``, and plain
preconditioned CG as its reference."""


def solve(A, b, M, stop, executor, precond_opts=None):
    """The program's entry: ``(x, iterations)``.  ``M`` is a generated
    preconditioner, or on a distributed operator the kind the program
    generates shard by shard inside the solve (with ``precond_opts``)."""
    from repro.solvers import krylov

    res = krylov.cg(A, b, M=M, precond_opts=precond_opts or None, stop=stop,
                    executor=executor, strict=False)
    return res.x, res.iterations


def reference(apply_a, apply_m, b, reduction_factor: float, max_iters: int):
    """Plain preconditioned CG from ``x0 = 0`` in ``jax.numpy``, in ``b``'s
    dtype, stopped as the program is: ``||r|| <= reduction_factor ||b||``."""
    import jax
    import jax.numpy as jnp

    def cond(state):
        k, rnorm = state[4], state[5]
        return (rnorm > reduction_factor * bnorm) & (k < max_iters)

    def body(state):
        x, r, p, rz, k, _ = state
        q = apply_a(p)
        alpha = rz / jnp.vdot(p, q)
        x = x + alpha * p
        r = r - alpha * q
        z = apply_m(r)
        rz_new = jnp.vdot(r, z)
        p = z + (rz_new / rz) * p
        return x, r, p, rz_new, k + 1, jnp.linalg.norm(r)

    bnorm = jnp.linalg.norm(b)
    z = apply_m(b)
    state = (jnp.zeros_like(b), b, z, jnp.vdot(b, z), 0, bnorm)
    return jax.lax.while_loop(cond, body, state)[0]
