"""Distributed Krylov solves — the solver source runs UNCHANGED per shard.

``dist_solve`` is what the solver entry points (:mod:`repro.solvers.krylov`)
delegate to when handed a distributed operator: it wraps ONE ``shard_map``
over the mesh data axis around the ordinary solver function, giving it

* the matrix's per-shard local operator (local SpMV + halo exchange,
  :meth:`~repro.distributed.matrix.DistLinOp.local_operator`);
* a shard-local preconditioner (:mod:`repro.distributed.precond`);
* the distributed BLAS context
  (:func:`repro.sparse.ops.distributed_blas`), under which every ``dot`` /
  ``norm2`` the solver issues reduces locally through the dispatched kernels
  and then ``psum``-s over the axis, padding masked.

Because the stopping criterion consumes exactly those psum'd norms, ``Stop``
behaves bit-for-bit like the single-device solve (modulo reduction-order
float drift) — Ginkgo's promise that ``solver::Cg`` neither knows nor cares
whether its operator is ``matrix::Csr`` or ``distributed::Matrix``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.matrix import DATA_AXIS, shard_specs
from repro.distributed.precond import dist_preconditioner
from repro.observability import trace
from repro.solvers.common import SolveResult, Stop

__all__ = ["dist_solve"]

#: jitted shard_map closures keyed on everything the closure bakes in
#: (solver, operator/preconditioner structure incl. static partition, stop,
#: executor, options, part count) — without this every distributed solve
#: would rebuild the closure and pay a full retrace + XLA compile.  jit's own
#: cache still handles shape/dtype changes of the array arguments.
_JIT_CACHE = {}


def dist_solve(
    solver_fn,
    A,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    stop: Stop = Stop(),
    M=None,
    precond_opts: Optional[dict] = None,
    executor=None,
    **options,
) -> SolveResult:
    """Run ``solver_fn`` (cg / bicgstab / gmres / ...) sharded over ``A``'s
    partition.  ``b`` / ``x0`` are ordinary global vectors; the result is the
    single-device-shaped :class:`SolveResult` with a global ``x``.
    """
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_shard_mesh
    from repro.sparse import ops as sparse_ops

    part = A.partition
    with trace.span("dist_solve.precond", cat="distributed"):
        Md = dist_preconditioner(A, M, executor=executor, **(precond_opts or {}))
    # static branch: history changes the shard_map output arity, and the
    # option value is part of the _JIT_CACHE key, so each setting compiles
    # its own closure
    want_history = bool(options.get("history"))

    with trace.span("dist_solve.pad", cat="distributed"):
        bp = part.pad(b)
        xp = part.pad(x0) if x0 is not None else jnp.zeros_like(bp)
        mask = jnp.asarray(part.pad_mask)

    a_leaves, a_tree = jax.tree_util.tree_flatten(A)
    m_leaves, m_tree = jax.tree_util.tree_flatten(Md)

    key = (
        solver_fn,
        a_tree,
        m_tree,
        stop,
        executor,
        tuple(sorted(options.items())),
        part.num_parts,
    )
    fn = _JIT_CACHE.get(key)
    if fn is None:
        mesh = make_shard_mesh(part.num_parts, DATA_AXIS)

        def body(a_ls, m_ls, b_l, x0_l, mask_l):
            A_shard = jax.tree_util.tree_unflatten(a_tree, a_ls)
            M_shard = jax.tree_util.tree_unflatten(m_tree, m_ls)
            Aop = A_shard.local_operator(executor=executor)
            Ml = (
                M_shard.local_operator(executor=executor)
                if M_shard is not None
                else None
            )
            with sparse_ops.distributed_blas(DATA_AXIS, mask_l[0]):
                res = solver_fn(
                    Aop,
                    b_l[0],
                    x0_l[0],
                    stop=stop,
                    M=Ml,
                    executor=executor,
                    **options,
                )
            # scalars pick up a length-1 shard axis so every output can use
            # the same sharded out_spec (their psum'd values agree across
            # shards)
            outs = (
                res.x[None],
                res.iterations[None],
                res.residual_norm[None],
                res.converged[None],
            )
            if want_history:
                # the residual norms the solver recorded are the psum'd
                # global norms — every shard holds an identical copy
                outs = outs + (res.history[None],)
            return outs

        vec = P(DATA_AXIS, None)
        out_specs = (vec, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
        if want_history:
            out_specs = out_specs + (P(DATA_AXIS, None),)
        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    shard_specs(a_leaves),
                    shard_specs(m_leaves),
                    vec,
                    vec,
                    vec,
                ),
                out_specs=out_specs,
                # the body's Pallas kernels carry no varying-axes types;
                # every output is sharded, so no replication claim goes
                # unchecked
                check_vma=False,
            )
        )
        _JIT_CACHE[key] = fn
    with trace.span("dist_solve.run", cat="distributed"):
        outs = fn(a_leaves, m_leaves, bp, xp, mask)
    xs, iters, rnorm, conv = outs[:4]
    hist = outs[4][0] if want_history else None
    return SolveResult(part.unpad(xs), iters[0], rnorm[0], conv[0], hist)
