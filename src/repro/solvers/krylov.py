"""Krylov solvers: CG, FCG, BiCGSTAB, GMRES(m) — Ginkgo's solver set.

All solvers:

* are pure-functional and jittable (``lax.while_loop`` / ``lax.fori_loop``);
* perform every vector operation through executor-dispatched BLAS-1 /
  SpMV kernels (:mod:`repro.sparse.ops`) — the algorithm never names a backend;
* distribute under ``pjit`` by sharding A (rows) and the vectors; the dot
  products lower to global all-reduces under GSPMD.

Each function also has a factory-style LinOp twin (``CgSolver``,
``GmresSolver``, ...): ``CgSolver(A, stop=...)`` is a
:class:`~repro.core.linop.LinOp` whose apply *solves*, so a solver can
precondition another solver — ``cg(A2, b, M=CgSolver(A, ...))`` is
inner-outer Krylov, Ginkgo's solver-as-preconditioner pattern.

Precision note: the paper evaluates in IEEE754 double precision; on this CPU
container f64 requires ``jax_enable_x64``.  Solvers are dtype-polymorphic —
benchmarks run f32 by default and f64 under ``with jax.enable_x64(True)``.
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.linop import LinOp, as_linop
from repro.observability import convergence
from repro.solvers.common import (
    MatrixLike,
    SolveResult,
    Stop,
    ensure_symmetric,
    identity_preconditioner,
)
from repro.sparse import ops as blas

__all__ = [
    "cg",
    "fcg",
    "bicgstab",
    "cgs",
    "gmres",
    "CgSolver",
    "FcgSolver",
    "BicgstabSolver",
    "CgsSolver",
    "GmresSolver",
    "PipelinedCgSolver",
]

#: a preconditioner argument: a LinOp / callable ``v -> M^{-1} v`` or a kind
#: name (``"jacobi"`` / ``"block_jacobi"`` / ``"parilu"`` / ``"amg"`` /
#: ``"identity"``)
#: that :func:`repro.precond.make_preconditioner` resolves against ``A`` — the
#: string path is how the ``adaptive`` storage knob threads through the
#: solvers: ``cg(A, b, M="block_jacobi", precond_opts={"adaptive": True})``.
Precond = Union[LinOp, Callable, str]


def _dist_route(solver_fn, A, b, x0, *, stop, M, precond_opts, executor, **options):
    """Delegate to the sharded solve when ``A`` is a distributed operator.

    The distributed layer re-enters ``solver_fn`` with the per-shard local
    operator (not distributed), so the delegation happens exactly once.
    """
    from repro.distributed.solvers import dist_solve

    return dist_solve(
        solver_fn,
        A,
        b,
        x0,
        stop=stop,
        M=M,
        precond_opts=precond_opts,
        executor=executor,
        **options,
    )


def _resolve_precond(A, M, executor, precond_opts):
    if isinstance(M, str):
        from repro.precond import make_preconditioner

        return make_preconditioner(A, M, executor=executor, **(precond_opts or {}))
    if precond_opts:
        raise ValueError("precond_opts is only meaningful when M is a kind name")
    return M if M is not None else identity_preconditioner


def _setup(A, b, x0, M, executor, precond_opts=None):
    Aop = as_linop(A)
    op = lambda v: Aop.apply(v, executor=executor)  # noqa: E731
    x = jnp.zeros_like(b) if x0 is None else x0
    M = _resolve_precond(A, M, executor, precond_opts)
    if isinstance(M, LinOp):
        # thread the solver's executor down the preconditioner subtree too —
        # A and M must dispatch in the same kernel space (bare callables have
        # no executor to thread)
        Mop = M
        M = lambda v: Mop.apply(v, executor=executor)  # noqa: E731
    return op, x, M


def cg(
    A: MatrixLike,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    stop: Stop = Stop(),
    M: Optional[Precond] = None,
    precond_opts: Optional[dict] = None,
    executor=None,
    fused: Optional[bool] = None,
    pipeline: bool = False,
    history=None,
    strict: bool = True,
) -> SolveResult:
    """Preconditioned conjugate gradient (SPD systems).

    ``strict=True`` (the default) runs a cheap seeded symmetry probe on
    concrete format operands and raises instead of silently producing
    garbage on nonsymmetric A; ``strict=False`` is the escape hatch.

    ``history=True`` (or an int capacity) records per-iteration residual
    norms into a jit-safe ring buffer surfaced as ``SolveResult.history``
    (see :mod:`repro.observability.convergence`); the default ``None`` adds
    nothing to the compiled loop.

    ``fused`` selects the apply-with-reduction formulation (SpMV + dot and
    axpy + norm fused into single kernel launches).  The default ``None``
    means "use it when the executor advertises the fused ops for this
    format" — the optional-op capability probe; ``False`` forces the
    portable unfused loop, ``True`` asks for fusion but still degrades
    gracefully when the ops are unavailable.  In the reference/xla kernel
    spaces the fused ops are the literal unfused composition, so both
    settings are bitwise identical there.

    ``pipeline=True`` runs the communication-avoiding (Ghysels–Vanroose)
    variant instead: all three recurrence dot products are batched into one
    reduction per iteration (a single ``psum`` under the distributed
    context).  Pipelining reassociates the recurrences, so iteration counts
    may differ by a step or two from classic CG.
    """
    if getattr(A, "is_distributed", False):
        # shard-local re-entry must not probe: local row blocks of a
        # symmetric global matrix are not themselves symmetric
        return _dist_route(cg, A, b, x0, stop=stop, M=M,
                           precond_opts=precond_opts, executor=executor,
                           fused=fused, pipeline=pipeline, history=history,
                           strict=False)
    ensure_symmetric(A, solver="cg", strict=strict)
    if pipeline:
        return _pipelined_cg(A, b, x0, stop=stop, M=M,
                             precond_opts=precond_opts, executor=executor,
                             history=history)
    want_fused = True if fused is None else bool(fused)
    if want_fused and blas.has_fused_ops(A, executor=executor):
        return _cg_fused(A, b, x0, stop=stop, M=M,
                         precond_opts=precond_opts, executor=executor,
                         history=history)
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)

    r = b - op(x)
    z = M(r)
    p = z
    rz = blas.dot(r, z, executor=ex)
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        x, r, z, p, rz, k, rnorm, hist = state
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, z, p, rz, k, _, hist = state
        Ap = op(p)
        alpha = rz / blas.dot(p, Ap, executor=ex)
        x = blas.axpy(alpha, p, x, executor=ex)
        r = blas.axpy(-alpha, Ap, r, executor=ex)
        z = M(r)
        rz_new = blas.dot(r, z, executor=ex)
        beta = rz_new / rz
        p = blas.axpy(beta, p, z, executor=ex)
        rnorm = blas.norm2(r, executor=ex)
        return (x, r, z, p, rz_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, z, p, rz, jnp.int32(0), rnorm0, hist0)
    x, r, z, p, rz, k, rnorm, hist = jax.lax.while_loop(cond, body, state)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def _cg_fused(A, b, x0, *, stop, M, precond_opts, executor, history=None):
    """CG on the fused-reduction ops: 2 reduction launches per iteration.

    Every iteration issues exactly one ``spmv_dot`` (Ap and p·Ap in a single
    pass over A) and one ``axpy_norm`` (r-update and ‖r‖² in a single pass
    over the vectors) — versus SpMV + 2 dots + norm as four separate
    reduction launches in the portable loop.  With the identity
    preconditioner the ``r·z`` dot *is* the fused ‖r‖², so the loop carries
    no standalone dot at all.
    """
    Aop = as_linop(A)
    op = lambda v: Aop.apply(v, executor=executor)  # noqa: E731
    x = jnp.zeros_like(b) if x0 is None else x0
    Mres = _resolve_precond(A, M, executor, precond_opts)
    # detect identity BEFORE the lambda wrap _setup applies — with identity M
    # the fused ‖r‖² doubles as r·z and the loop carries no standalone dot
    identity_M = Mres is identity_preconditioner
    if isinstance(Mres, LinOp):
        Mop = Mres
        Mfn = lambda v: Mop.apply(v, executor=executor)  # noqa: E731
    else:
        Mfn = Mres
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)

    r = b - op(x)
    z = Mfn(r)
    p = z
    rz = blas.dot(r, z, executor=ex)
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        x, r, z, p, rz, k, rnorm, hist = state
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, z, p, rz, k, _, hist = state
        Ap, pAp = blas.spmv_dot(A, p, executor=ex)
        alpha = rz / pAp
        x = blas.axpy(alpha, p, x, executor=ex)
        r, rr = blas.axpy_norm(-alpha, Ap, r, executor=ex)
        if identity_M:
            # z = r and r·z = ‖r‖² — the fused norm doubles as the CG dot
            z, rz_new = r, rr
        else:
            z = Mfn(r)
            rz_new = blas.dot(r, z, executor=ex)
        beta = rz_new / rz
        p = blas.axpy(beta, p, z, executor=ex)
        rnorm = jnp.sqrt(rr.real)
        return (x, r, z, p, rz_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, z, p, rz, jnp.int32(0), rnorm0, hist0)
    x, r, z, p, rz, k, rnorm, hist = jax.lax.while_loop(cond, body, state)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def _pipelined_cg(A, b, x0, *, stop, M, precond_opts, executor, history=None):
    """Pipelined (Ghysels–Vanroose) preconditioned CG — one reduction/iteration.

    Classic CG needs two dependent dot products per iteration (``p·Ap``
    before the updates, ``r·z`` after), each a separate global reduction.
    The pipelined recurrences carry the auxiliary vectors ``u = M r``,
    ``w = A u``, ``z/q/s/p`` so that all three scalars (γ = r·u, δ = w·u,
    ‖r‖²) are computable from the *same* state — one
    :func:`repro.sparse.ops.dot_batch` call, which under the distributed
    reduction context is a single fused ``psum`` per iteration.

    The reassociated recurrences change rounding, so iteration counts may
    drift by ±1–2 versus classic CG; the converged solution is the same to
    solver tolerance.
    """
    op, x, Mfn = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)
    dtype = b.dtype

    r = b - op(x)
    u = Mfn(r)
    w = op(u)
    d0 = blas.dot_batch([(r, u), (w, u), (r, r)], executor=ex)
    gam, delta, rr = d0[0], d0[1], d0[2]
    zeros = jnp.zeros_like(b)
    one = jnp.ones((), dtype)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=jnp.sqrt(rr.real).dtype)

    def cond(state):
        rr, k = state[10], state[13]
        return (jnp.sqrt(rr.real) > thresh) & (k < stop.max_iters)

    def body(state):
        (x, r, u, w, z, q, s, p, gam, delta, rr,
         gam_old, alpha_old, k, hist) = state
        beta = jnp.where(k == 0, jnp.zeros((), gam.dtype), gam / gam_old)
        # at k == 0 beta = 0, so the denominator reduces to delta
        alpha = gam / (delta - beta * gam / alpha_old)
        mv = Mfn(w)
        nv = op(mv)
        z = blas.axpy(beta, z, nv, executor=ex)
        q = blas.axpy(beta, q, mv, executor=ex)
        s = blas.axpy(beta, s, w, executor=ex)
        p = blas.axpy(beta, p, u, executor=ex)
        x = blas.axpy(alpha, p, x, executor=ex)
        r = blas.axpy(-alpha, s, r, executor=ex)
        u = blas.axpy(-alpha, q, u, executor=ex)
        w = blas.axpy(-alpha, z, w, executor=ex)
        d = blas.dot_batch([(r, u), (w, u), (r, r)], executor=ex)
        hist = convergence.push(hist, k, jnp.sqrt(d[2].real))
        return (x, r, u, w, z, q, s, p, d[0], d[1], d[2],
                gam, alpha, k + 1, hist)

    state = (x, r, u, w, zeros, zeros, zeros, zeros,
             gam, delta, rr, one, one, jnp.int32(0), hist0)
    out = jax.lax.while_loop(cond, body, state)
    x, rr, k = out[0], out[10], out[13]
    rnorm = jnp.sqrt(rr.real)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(out[14]))


def fcg(
    A: MatrixLike,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    stop: Stop = Stop(),
    M: Optional[Precond] = None,
    precond_opts: Optional[dict] = None,
    executor=None,
    history=None,
    strict: bool = True,
) -> SolveResult:
    """Flexible CG (Ginkgo's FCG): Polak–Ribière beta = r'(r - r_prev)/rz_prev,
    robust to non-constant preconditioners.

    Like :func:`cg`, ``strict=True`` probes concrete operands for symmetry
    and raises on nonsymmetric A instead of silently diverging."""
    if getattr(A, "is_distributed", False):
        return _dist_route(fcg, A, b, x0, stop=stop, M=M,
                           precond_opts=precond_opts, executor=executor,
                           history=history, strict=False)
    ensure_symmetric(A, solver="fcg", strict=strict)
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)

    r = b - op(x)
    z = M(r)
    p = z
    rz = blas.dot(r, z, executor=ex)
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        k, rnorm = state[6], state[7]
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, r_prev, z, p, rz, k, _, hist = state
        Ap = op(p)
        alpha = rz / blas.dot(p, Ap, executor=ex)
        x = blas.axpy(alpha, p, x, executor=ex)
        r_new = blas.axpy(-alpha, Ap, r, executor=ex)
        z = M(r_new)
        # flexible beta uses the difference with the previous residual
        rz_new = blas.dot(r_new, z, executor=ex)
        beta = blas.dot(z, r_new - r, executor=ex) / rz
        p = blas.axpy(beta, p, z, executor=ex)
        rnorm = blas.norm2(r_new, executor=ex)
        return (x, r_new, r, z, p, rz_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, r, z, p, rz, jnp.int32(0), rnorm0, hist0)
    out = jax.lax.while_loop(cond, body, state)
    x, r, r_prev, z, p, rz, k, rnorm, hist = out
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def bicgstab(
    A: MatrixLike,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    stop: Stop = Stop(),
    M: Optional[Precond] = None,
    precond_opts: Optional[dict] = None,
    executor=None,
    fused: Optional[bool] = None,
    history=None,
) -> SolveResult:
    """Preconditioned BiCGSTAB (general nonsymmetric systems).

    ``fused`` works as in :func:`cg`: ``None`` probes the executor for the
    fused apply-with-reduction ops and uses them when available.
    """
    if getattr(A, "is_distributed", False):
        return _dist_route(bicgstab, A, b, x0, stop=stop, M=M,
                           precond_opts=precond_opts, executor=executor,
                           fused=fused, history=history)
    want_fused = True if fused is None else bool(fused)
    if want_fused and blas.has_fused_ops(A, executor=executor):
        return _bicgstab_fused(A, b, x0, stop=stop, M=M,
                               precond_opts=precond_opts, executor=executor,
                               history=history)
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)
    eps = jnp.asarray(1e-30, b.dtype)

    r = b - op(x)
    r_hat = r
    rho = blas.dot(r_hat, r, executor=ex)
    p = r
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        x, r, p, rho, k, rnorm, hist = state
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, p, rho, k, _, hist = state
        p_hat = M(p)
        v = op(p_hat)
        alpha = rho / (blas.dot(r_hat, v, executor=ex) + eps)
        s = blas.axpy(-alpha, v, r, executor=ex)
        s_hat = M(s)
        t = op(s_hat)
        omega = blas.dot(t, s, executor=ex) / (blas.dot(t, t, executor=ex) + eps)
        x = x + alpha * p_hat + omega * s_hat
        r_new = blas.axpy(-omega, t, s, executor=ex)
        rho_new = blas.dot(r_hat, r_new, executor=ex)
        beta = (rho_new / (rho + eps)) * (alpha / (omega + eps))
        p = r_new + beta * (p - omega * v)
        rnorm = blas.norm2(r_new, executor=ex)
        return (x, r_new, p, rho_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, p, rho, jnp.int32(0), rnorm0, hist0)
    x, r, p, rho, k, rnorm, hist = jax.lax.while_loop(cond, body, state)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def _bicgstab_fused(A, b, x0, *, stop, M, precond_opts, executor,
                    history=None):
    """BiCGSTAB on the fused ops: both SpMVs carry their follow-up dot
    (``r̂·v`` and ``s·t``) and the final residual update carries ‖r‖²,
    collapsing five reduction launches per iteration into three (the ``t·t``
    and ``r̂·r`` dots remain standalone).  For real dtypes ``s·t`` equals the
    portable loop's ``t·s`` bitwise, preserving fallback parity."""
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)
    eps = jnp.asarray(1e-30, b.dtype)

    r = b - op(x)
    r_hat = r
    rho = blas.dot(r_hat, r, executor=ex)
    p = r
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        x, r, p, rho, k, rnorm, hist = state
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, p, rho, k, _, hist = state
        p_hat = M(p)
        v, rhv = blas.spmv_dot(A, p_hat, w=r_hat, executor=ex)
        alpha = rho / (rhv + eps)
        s = blas.axpy(-alpha, v, r, executor=ex)
        s_hat = M(s)
        t, ts = blas.spmv_dot(A, s_hat, w=s, executor=ex)
        omega = ts / (blas.dot(t, t, executor=ex) + eps)
        x = x + alpha * p_hat + omega * s_hat
        r_new, rr = blas.axpy_norm(-omega, t, s, executor=ex)
        rho_new = blas.dot(r_hat, r_new, executor=ex)
        beta = (rho_new / (rho + eps)) * (alpha / (omega + eps))
        p = r_new + beta * (p - omega * v)
        rnorm = jnp.sqrt(rr.real)
        return (x, r_new, p, rho_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, p, rho, jnp.int32(0), rnorm0, hist0)
    x, r, p, rho, k, rnorm, hist = jax.lax.while_loop(cond, body, state)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def cgs(
    A: MatrixLike,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    stop: Stop = Stop(),
    M: Optional[Precond] = None,
    precond_opts: Optional[dict] = None,
    executor=None,
    history=None,
) -> SolveResult:
    """Conjugate Gradient Squared (Sonneveld) — the paper's solver set's
    transpose-free nonsymmetric method."""
    if getattr(A, "is_distributed", False):
        return _dist_route(cgs, A, b, x0, stop=stop, M=M,
                           precond_opts=precond_opts, executor=executor,
                           history=history)
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)
    eps = jnp.asarray(1e-30, b.dtype)

    r = b - op(x)
    r_hat = r
    rho = blas.dot(r_hat, r, executor=ex)
    u = r
    p = r
    rnorm0 = blas.norm2(r, executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=rnorm0.dtype)

    def cond(state):
        k, rnorm = state[5], state[6]
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, r, u, p, rho, k, _, hist = state
        p_hat = M(p)
        v = op(p_hat)
        alpha = rho / (blas.dot(r_hat, v, executor=ex) + eps)
        q = u - alpha * v
        uq_hat = M(u + q)
        x = x + alpha * uq_hat
        r = r - alpha * op(uq_hat)
        rho_new = blas.dot(r_hat, r, executor=ex)
        beta = rho_new / (rho + eps)
        u = r + beta * q
        p = u + beta * (q + beta * p)
        rnorm = blas.norm2(r, executor=ex)
        return (x, r, u, p, rho_new, k + 1, rnorm,
                convergence.push(hist, k, rnorm))

    state = (x, r, u, p, rho, jnp.int32(0), rnorm0, hist0)
    x, r, u, p, rho, k, rnorm, hist = jax.lax.while_loop(cond, body, state)
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


def gmres(
    A: MatrixLike,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    restart: int = 30,
    stop: Stop = Stop(),
    M: Optional[Precond] = None,
    precond_opts: Optional[dict] = None,
    executor=None,
    history=None,
) -> SolveResult:
    """Restarted GMRES(m) with modified Gram-Schmidt Arnoldi + Givens rotations.

    Right-preconditioned: solves A M^{-1} u = b, x = M^{-1} u, so the true
    residual is available without extra applies.

    ``history=`` records the true residual norm once per restart *cycle*
    (slot ``k // m``), not per inner Arnoldi step — the inner steps only
    track the rotated-rhs estimate.
    """
    if getattr(A, "is_distributed", False):
        return _dist_route(gmres, A, b, x0, stop=stop, M=M,
                           precond_opts=precond_opts, executor=executor,
                           restart=restart, history=history)
    op, x, M = _setup(A, b, x0, M, executor, precond_opts)
    ex = executor
    n = b.shape[0]
    m = restart
    dtype = b.dtype
    bnorm = blas.norm2(b, executor=ex)
    thresh = stop.threshold(bnorm)
    eps = jnp.asarray(1e-30, dtype)

    def arnoldi_cycle(x):
        """One restart cycle. Returns (x_new, rnorm_new)."""
        r = b - op(x)
        beta = blas.norm2(r, executor=ex)
        V = jnp.zeros((m + 1, n), dtype)
        V = V.at[0].set(r / (beta + eps))
        H = jnp.zeros((m + 1, m), dtype)
        # Givens coefficients and the rotated rhs g
        cs = jnp.zeros(m, dtype)
        sn = jnp.zeros(m, dtype)
        g = jnp.zeros(m + 1, dtype).at[0].set(beta)

        def step(j, carry):
            V, H, cs, sn, g, done = carry
            w = op(M(V[j]))
            # modified Gram-Schmidt against all m+1 basis vectors; rows > j are
            # zero so the extra dots are no-ops (keeps shapes static).
            def mgs(i, wh):
                w, h = wh
                hij = jnp.where(i <= j, blas.dot(V[i], w, executor=ex), 0.0)
                w = w - hij * V[i]
                return w, h.at[i].set(hij)

            w, hcol = jax.lax.fori_loop(0, m + 1, mgs, (w, jnp.zeros(m + 1, dtype)))
            hj1 = blas.norm2(w, executor=ex)
            hcol = hcol.at[j + 1].set(hj1)
            V = V.at[j + 1].set(w / (hj1 + eps))

            # apply existing Givens rotations to the new column
            def rot(i, h):
                hi = cs[i] * h[i] + sn[i] * h[i + 1]
                hi1 = -sn[i] * h[i] + cs[i] * h[i + 1]
                h = h.at[i].set(jnp.where(i < j, hi, h[i]))
                return h.at[i + 1].set(jnp.where(i < j, hi1, h[i + 1]))

            hcol = jax.lax.fori_loop(0, m, rot, hcol)

            # new rotation to zero hcol[j+1]
            denom = jnp.sqrt(hcol[j] ** 2 + hcol[j + 1] ** 2) + eps
            c, s = hcol[j] / denom, hcol[j + 1] / denom
            hcol = hcol.at[j].set(c * hcol[j] + s * hcol[j + 1]).at[j + 1].set(0.0)
            cs = cs.at[j].set(c)
            sn = sn.at[j].set(s)
            g_j1 = -s * g[j]
            g = g.at[j + 1].set(g_j1).at[j].set(c * g[j])

            H = H.at[:, j].set(hcol)
            done = done | (jnp.abs(g_j1) <= thresh)
            return V, H, cs, sn, g, done

        # run all m steps (static shape); 'done' only gates the outer loop —
        # redundant inner steps are numerically harmless (rotations freeze g).
        V, H, cs, sn, g, done = jax.lax.fori_loop(
            0, m, step, (V, H, cs, sn, g, jnp.asarray(False))
        )

        # back-substitution on the m×m triangular system H y = g
        def back(i_rev, y):
            i = m - 1 - i_rev
            num = g[i] - jnp.dot(H[i, :], y)
            return y.at[i].set(num / (H[i, i] + eps))

        y = jax.lax.fori_loop(0, m, back, jnp.zeros(m, dtype))
        dx = V[:m].T @ y
        x_new = x + M(dx)
        rnorm = blas.norm2(b - op(x_new), executor=ex)
        return x_new, rnorm

    def cond(state):
        x, k, rnorm, hist = state
        return (rnorm > thresh) & (k < stop.max_iters)

    def body(state):
        x, k, _, hist = state
        x, rnorm = arnoldi_cycle(x)
        return x, k + m, rnorm, convergence.push(hist, k // m, rnorm)

    r0 = blas.norm2(b - op(x), executor=ex)
    hist0 = convergence.init(convergence.capacity(history, stop),
                             dtype=r0.dtype)
    x, k, rnorm, hist = jax.lax.while_loop(
        cond, body, (x, jnp.int32(0), r0, hist0)
    )
    return SolveResult(x, k, rnorm, rnorm <= thresh,
                       convergence.finalize(hist))


# =============================================================================
# Factory-style solver LinOps — gko::solver::Cg::Factory ... ::generate(A)
# =============================================================================


class KrylovSolver(LinOp):
    """A generated solver as a LinOp: ``apply(b)`` *solves* ``A x = b``.

    This is Ginkgo's factory pattern collapsed to one step: a Ginkgo solver
    factory ``generate(A)``-s a solver object that IS a LinOp, so solvers
    compose anywhere an operator is expected — as the ``M`` of an outer
    Krylov method (inner-outer iteration), as the inner solve of iterative
    refinement (:mod:`repro.solvers.ir`), or inside
    :class:`~repro.core.linop.Composition` chains.

    String preconditioners resolve at construction (generation time, like
    Ginkgo's ``generate``), so the host-side setup work never re-runs inside
    a jitted apply.  ``solve(b)`` returns the full :class:`SolveResult`;
    ``apply(b)`` returns only ``x`` (the LinOp face).
    """

    _fn: Callable = None  # bound per subclass
    _requires_spd: bool = False  # CG-family subclasses probe at generation

    def __init__(
        self,
        A: MatrixLike,
        *,
        stop: Stop = Stop(),
        M: Optional[Precond] = None,
        precond_opts: Optional[dict] = None,
        executor=None,
        **options,
    ):
        self.A = as_linop(A)
        self.stop = stop
        if self._requires_spd:
            # generation-time symmetry probe (Ginkgo generates eagerly, so
            # failing here is the earliest loud failure point); the solve-time
            # check is skipped since generation already vetted the operand
            ensure_symmetric(A, solver=type(self).__name__,
                             strict=options.get("strict", True))
            options["strict"] = False
        if getattr(self.A, "is_distributed", False):
            # generation-time resolution for distributed operands goes through
            # the shard-local generators (a global M cannot apply per shard)
            from repro.distributed.precond import dist_preconditioner

            self.M = dist_preconditioner(
                self.A, M, executor=executor, **(precond_opts or {})
            )
        else:
            self.M = _resolve_precond(A, M, executor, precond_opts)
        self.executor = executor
        self.options = options

    @property
    def shape(self):
        return getattr(self.A, "shape", None)

    @property
    def dtype(self):
        return getattr(self.A, "dtype", None)

    def solve(self, b: jax.Array, x0=None, *, executor=None) -> SolveResult:
        ex = executor if executor is not None else self.executor
        return type(self)._fn(
            self.A, b, x0, stop=self.stop, M=self.M, executor=ex, **self.options
        )

    def _apply(self, b: jax.Array, executor) -> jax.Array:
        return self.solve(b, executor=executor).x


class CgSolver(KrylovSolver):
    """Generated CG solver (SPD) as a LinOp."""

    _fn = staticmethod(cg)
    _requires_spd = True


class PipelinedCgSolver(KrylovSolver):
    """Generated communication-avoiding CG solver as a LinOp.

    ``PipelinedCgSolver(A, stop=...)`` is :class:`CgSolver` with
    ``pipeline=True`` baked into the generated options: every iteration
    performs a single batched reduction (one ``psum`` under the distributed
    context) instead of two dependent dots — the latency-bound regime's
    solver of choice at scale."""

    _fn = staticmethod(cg)
    _requires_spd = True

    def __init__(self, A, **kw):
        super().__init__(A, pipeline=True, **kw)


class FcgSolver(KrylovSolver):
    """Generated flexible-CG solver as a LinOp."""

    _fn = staticmethod(fcg)
    _requires_spd = True


class BicgstabSolver(KrylovSolver):
    """Generated BiCGSTAB solver as a LinOp."""

    _fn = staticmethod(bicgstab)


class CgsSolver(KrylovSolver):
    """Generated CGS solver as a LinOp."""

    _fn = staticmethod(cgs)


class GmresSolver(KrylovSolver):
    """Generated GMRES(m) solver as a LinOp (``restart=`` forwards)."""

    _fn = staticmethod(gmres)

    def __init__(self, A, *, restart: int = 30, **kw):
        super().__init__(A, restart=restart, **kw)
