"""Algebraic multigrid — smoothed aggregation on the registered SpGEMM ops.

Smoothed aggregation (Vaněk, Mandel and Brezina, 1996) used as one cycle of
a preconditioner, as Ginkgo uses ``gko::solver::Multigrid``: on PDE-like
matrices, Krylov iteration counts grow with √κ, and multigrid is the
O(√κ) → O(1) jump — a hierarchy of coarse operators built *algebraically*
from the matrix, each level damping the error frequencies its smoother can
see.  Ginkgo's own coarsening, ``gko::multigrid::Pgm``, is pairwise
aggregation with an unsmoothed prolongator; this module aggregates whole
strong neighbourhoods and smooths the prolongator instead, which is what
buys grid-independent convergence.

Setup pipeline (host structure, with every sparse-sparse composition
through the registered ``spgemm`` / ``sptranspose`` ops, so it runs in
whichever kernel space the executor selects):

  1. strength-of-connection — entry (i, j) is *strong* when
     ``|a_ij| ≥ θ·√(a_ii·a_jj)`` (the classical SA filter; anisotropic
     problems drop their weak direction here);
  2. greedy aggregation in row order — 3 passes: seed aggregates around
     rows whose strong neighborhood is untouched, attach leftovers to a
     neighboring aggregate, sweep singletons;
  3. tentative prolongator ``T`` (one unit entry per row: fine point → its
     aggregate), optionally *smoothed* — ``P = (I − ω·D⁻¹A)·T`` via one
     SpGEMM;
  4. Galerkin triple product ``A_c = R·A·P`` with ``R = Pᵀ`` — two SpGEMMs
     and one sparse transpose.

The descent ends at ``coarse_size`` rows, at ``max_levels``, or at a level
whose aggregation would keep more than half of its rows (coarsening that
stalls only makes the next level denser).  The coarsest level is inverted
densely in float64 on the host, or solved by CG; a coarsest level above
:data:`DENSE_COARSE_MAX_ROWS` rows is refused for the dense inverse.

The operand may be CSR or ELL, and the setup reads its host pattern and
values (:func:`repro.sparse.formats.csr_host_arrays`).  The fine level
applies an ELL operand as handed and converts a CSR one to ELL once; coarse
operators and the transfers are ELL too.

The cycle (V or W) runs weighted-Jacobi or block-Jacobi smoothers per level
and the coarse solve; the recursion is unrolled at trace time, so
:meth:`Multigrid._apply` is jit-traceable and works inside
``lax.while_loop``, and :class:`Multigrid` is a pytree, so a jitted solve
takes it as an argument — the requirement for serving as ``M`` in every
Krylov solver through :func:`repro.precond.make_preconditioner`
(``M="amg"``).  Inside ``Multigrid.apply`` the cycle names its parts on the
device trace: ``Multigrid.level<k>`` (level k's smoothing and residual),
``Multigrid.restrict<k>`` / ``Multigrid.prolong<k>`` (the transfers between
level k and k+1) and ``Multigrid.coarse``.

Setup emits ``amg.setup`` / ``amg.level`` / ``amg.coarse_solver`` spans and
the gauges ``amg_level_rows{level}``, ``amg_level_nnz{level}``,
``amg_transfer_nnz{level}`` (stored nonzeros of ``P_k`` and ``R_k``) and
``amg_operator_complexity`` (Σ level nnz / fine nnz) — the standard AMG
cost metric.

The serve layer uses the pattern-only subset at the bottom of this module:
aggregation from the sparsity pattern alone plus an additive two-level
correction whose values are pure gathers/segment-sums of the fine values —
what lets a cached pattern-tier hierarchy be refreshed per values without
re-running setup (see :mod:`repro.serve.cache`).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.linop import LinOp
from repro.observability import metrics, trace
from repro.sparse.formats import (
    Csr,
    Ell,
    csr_from_arrays,
    csr_host_arrays,
    ell_from_csr_host,
)
from repro.sparse.ops import _coalesce_host, apply as sp_apply, spgemm, sptranspose

__all__ = [
    "DENSE_COARSE_MAX_ROWS",
    "AmgLevel",
    "AmgServePattern",
    "Multigrid",
    "aggregate",
    "amg_preconditioner",
    "amg_serve_factors",
    "amg_serve_pattern",
    "batch_amg_apply",
    "strength_mask",
    "tentative_prolongator",
]

#: the largest coarsest level the dense inverse takes (256 MiB in float32)
DENSE_COARSE_MAX_ROWS = 8192


# =============================================================================
# Setup: strength, aggregation, prolongators, Galerkin product
# =============================================================================


def strength_mask(
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    theta: float = 0.08,
) -> np.ndarray:
    """Boolean mask over nnz: ``|a_ij| ≥ θ·√(a_ii·a_jj)``, diagonal excluded.

    The smoothed-aggregation strength-of-connection filter: weak couplings
    (e.g. the ε-direction of anisotropic diffusion) drop out of aggregation
    so aggregates align with the direction the smoother cannot damp.
    """
    n = indptr.shape[0] - 1
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    cols = np.asarray(indices, dtype=np.int64)
    diag = np.ones(n, np.float64)
    dmask = rows == cols
    diag[rows[dmask]] = np.abs(values[dmask].astype(np.float64))
    ref = theta * np.sqrt(diag[rows] * diag[cols])
    return (~dmask) & (np.abs(values.astype(np.float64)) >= ref)


def aggregate(
    indptr: np.ndarray,
    indices: np.ndarray,
    strong: np.ndarray,
    n: int,
) -> Tuple[np.ndarray, int]:
    """Greedy aggregation: ``(agg, n_agg)`` with ``agg[i]`` the aggregate of
    row i.  Three passes (seed / attach / singleton-sweep) — the standard
    SA coarsening, sequential by construction (host setup path).
    """
    ip = np.asarray(indptr).tolist()
    ix = np.asarray(indices).tolist()
    st = np.asarray(strong).tolist()
    agg = [-1] * n
    n_agg = 0
    # pass 1: rows whose strong neighborhood is entirely unaggregated seed a
    # new aggregate containing themselves + that neighborhood
    for i in range(n):
        if agg[i] != -1:
            continue
        nbrs = [ix[t] for t in range(ip[i], ip[i + 1]) if st[t]]
        if any(agg[j] != -1 for j in nbrs):
            continue
        agg[i] = n_agg
        for j in nbrs:
            agg[j] = n_agg
        n_agg += 1
    # pass 2: attach leftovers to any strongly-connected aggregate
    for i in range(n):
        if agg[i] != -1:
            continue
        for t in range(ip[i], ip[i + 1]):
            if st[t] and agg[ix[t]] != -1:
                agg[i] = agg[ix[t]]
                break
    # pass 3: whatever remains (isolated rows) becomes a singleton aggregate
    for i in range(n):
        if agg[i] == -1:
            agg[i] = n_agg
            n_agg += 1
    return np.asarray(agg, np.int64), n_agg


def tentative_prolongator(agg: np.ndarray, n_agg: int) -> Csr:
    """``T``: (n, n_agg) CSR with one unit entry per row (piecewise-constant
    interpolation from aggregates to fine points)."""
    n = agg.shape[0]
    return csr_from_arrays(
        np.arange(n + 1, dtype=np.int64),
        agg.astype(np.int32),
        np.ones(n, np.float32),
        (n, n_agg),
    )


def _csr_diag(indptr, indices, values, n) -> np.ndarray:
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    diag = np.zeros(n, values.dtype)
    m = rows == indices
    diag[rows[m]] = values[m]
    return diag


def _ell_of(A: Csr) -> Ell:
    indptr, indices, values = csr_host_arrays(A)
    return ell_from_csr_host(indptr, indices, values, A.shape)


def _csr_sub_scaled(Tm: Csr, S: Csr, row_scale: np.ndarray) -> Csr:
    """Host sparse combination ``T − diag(row_scale)·S`` (same shape)."""
    ti, tc, tv = csr_host_arrays(Tm)
    si, sc, sv = csr_host_arrays(S)
    m, n = Tm.shape
    t_rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(ti))
    s_rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(si))
    rows = np.concatenate([t_rows, s_rows])
    cols = np.concatenate([tc.astype(np.int64), sc.astype(np.int64)])
    vals = np.concatenate([tv, -row_scale[s_rows] * sv])
    indptr, out_c, out_v = _coalesce_host(rows, cols, vals, m)
    return csr_from_arrays(indptr, out_c, out_v, (m, n))


@dataclasses.dataclass
class AmgLevel:
    """One level of the hierarchy: its operator, grid-transfer pair, and the
    smoother data (inverse diagonal for weighted Jacobi, or a block-Jacobi
    LinOp when the hierarchy was built with ``smoother="block_jacobi"``).

    ``A`` is what the cycle applies, always ELL: on level 0 the operand
    :class:`Multigrid` was handed (converted once if it came as CSR), below
    it the Galerkin product.  The
    transfers ``P`` (coarse → fine) and ``R = Pᵀ`` (fine → coarse) are ELL
    too — PDE hierarchies have near-uniform row counts, and the ELL SpMV
    needs no per-apply row-id reconstruction.
    """

    A: Ell
    P: Ell
    R: Ell
    inv_diag: jax.Array
    smoother: Optional[LinOp] = None


# a pytree, so the hierarchy travels as an argument of a jitted solve
jax.tree_util.register_dataclass(
    AmgLevel,
    data_fields=["A", "P", "R", "inv_diag", "smoother"],
    meta_fields=[],
)


class Multigrid(LinOp):
    """Smoothed-aggregation AMG V/W-cycle as a LinOp (the analogue of
    ``gko::solver::Multigrid`` used as a preconditioner; smoothed
    aggregation where Ginkgo's ``gko::multigrid::Pgm`` aggregates pairwise
    and leaves the prolongator unsmoothed).

    ``apply(r)`` runs one cycle from a zero initial guess — i.e. it is the
    preconditioner application ``M⁻¹ r``.  The cycle recursion is unrolled at
    trace time (the level count is static), so the apply jits and can run
    inside a Krylov solver's ``lax.while_loop``.  With symmetric smoothing
    (the default weighted Jacobi, same pre/post sweep counts) the V-cycle is
    an SPD operator — safe as CG's ``M``.
    """

    def __init__(
        self,
        A,
        *,
        theta: float = 0.08,
        omega: float = 2.0 / 3.0,
        smooth_prolongator: bool = True,
        cycle: str = "v",
        pre_sweeps: int = 1,
        post_sweeps: int = 1,
        max_levels: int = 10,
        coarse_size: int = 64,
        coarse_solver: str = "dense",
        smoother: str = "jacobi",
        smoother_opts: Optional[dict] = None,
        executor=None,
    ):
        if cycle not in ("v", "w"):
            raise ValueError(f"cycle must be 'v' or 'w', got {cycle!r}")
        if coarse_solver not in ("dense", "cg"):
            raise ValueError(
                f"coarse_solver must be 'dense' or 'cg', got {coarse_solver!r}"
            )
        if smoother not in ("jacobi", "block_jacobi"):
            raise ValueError(
                f"smoother must be 'jacobi' or 'block_jacobi', got {smoother!r}"
            )
        self.executor = executor
        self.cycle = cycle
        self.omega = float(omega)
        self.pre_sweeps = int(pre_sweeps)
        self.post_sweeps = int(post_sweeps)
        self._shape = A.shape
        self._dtype = A.values.dtype
        self.levels: List[AmgLevel] = []

        indptr, indices, values = csr_host_arrays(A)
        n = A.shape[0]
        fine_nnz = max(indices.size, 1)
        total_nnz = 0
        with trace.span("amg.setup", cat="amg", n=n, nnz=indices.size,
                        theta=theta, cycle=cycle):
            # the level's operator, as the cycle applies it: an ELL operand as
            # handed, a CSR one converted to ELL once
            op = A if isinstance(A, Ell) else ell_from_csr_host(
                indptr, indices, values, A.shape)
            level = 0
            while n > coarse_size and level < max_levels:
                strong = strength_mask(indptr, indices, values, theta)
                agg, n_agg = aggregate(indptr, indices, strong, n)
                if 2 * n_agg > n:
                    break  # coarsening stalls: this level is the coarsest
                with trace.span("amg.level", cat="amg", level=level,
                                rows=n, nnz=indices.size, coarse_rows=n_agg):
                    A_csr = csr_from_arrays(indptr, indices, values, (n, n))
                    T = tentative_prolongator(agg, n_agg)
                    diag = _csr_diag(indptr, indices, values, n)
                    inv_d = np.where(diag != 0, 1.0 / diag, 0.0).astype(
                        values.dtype
                    )
                    if smooth_prolongator:
                        AT = spgemm(A_csr, T, executor=executor)
                        P = _csr_sub_scaled(T, AT, self.omega * inv_d)
                    else:
                        P = T
                    R = sptranspose(P, executor=executor)
                    A_c = spgemm(R, spgemm(A_csr, P, executor=executor),
                                 executor=executor)
                    sm = None
                    if smoother == "block_jacobi":
                        from repro.precond.block_jacobi import block_jacobi

                        sm = block_jacobi(
                            op, executor=executor, **(smoother_opts or {})
                        )
                    self.levels.append(
                        AmgLevel(A=op, P=_ell_of(P), R=_ell_of(R),
                                 inv_diag=jnp.asarray(inv_d), smoother=sm)
                    )
                metrics.gauge("amg_level_rows", level=level).set(n)
                metrics.gauge("amg_level_nnz", level=level).set(indices.size)
                metrics.gauge("amg_transfer_nnz", level=level).set(P.nnz + R.nnz)
                total_nnz += indices.size
                indptr, indices, values = csr_host_arrays(A_c)
                op = ell_from_csr_host(indptr, indices, values, A_c.shape)
                n = n_agg
                level += 1

            self.coarse_A = op
            metrics.gauge("amg_level_rows", level=level).set(n)
            metrics.gauge("amg_level_nnz", level=level).set(indices.size)
            self.operator_complexity = (total_nnz + indices.size) / fine_nnz
            metrics.gauge("amg_operator_complexity").set(
                self.operator_complexity
            )
            with trace.span("amg.coarse_solver", cat="amg",
                            kind=coarse_solver, rows=n):
                self._coarse_inv = None
                if coarse_solver == "dense":
                    if n > DENSE_COARSE_MAX_ROWS:
                        raise ValueError(
                            f"the coarsest level has {n} rows, more than the "
                            f"dense inverse takes ({DENSE_COARSE_MAX_ROWS}); "
                            "raise coarsening (a lower theta, more levels) or "
                            "use coarse_solver='cg'"
                        )
                    rows = np.repeat(np.arange(n), np.diff(indptr))
                    dense = np.zeros((n, n))
                    dense[rows, indices] = values
                    self._coarse_inv = jnp.asarray(
                        np.linalg.inv(dense).astype(self._dtype)
                    )

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def num_levels(self) -> int:
        # counting the coarse grid, matching gko::solver::Multigrid
        return len(self.levels) + 1

    # -- the cycle -------------------------------------------------------------

    def _smooth(self, L: AmgLevel, x, r, sweeps: int, executor):
        for _ in range(sweeps):
            res = r - sp_apply(L.A, x, executor=executor)
            if L.smoother is not None:
                x = x + L.smoother.apply(res, executor=executor)
            else:
                x = x + self.omega * L.inv_diag * res
        return x

    def _coarse_solve(self, r, executor):
        if self._coarse_inv is not None:
            return jnp.dot(self._coarse_inv, r,
                           precision=jax.lax.Precision.HIGHEST)
        from repro.solvers.common import Stop
        from repro.solvers.krylov import cg

        return cg(self.coarse_A, r,
                  stop=Stop(max_iters=50, reduction_factor=1e-8),
                  executor=executor, strict=False).x

    def _cycle(self, lvl: int, r, executor):
        if lvl == len(self.levels):
            with jax.named_scope("Multigrid.coarse"):
                return self._coarse_solve(r, executor)
        L = self.levels[lvl]
        with jax.named_scope(f"Multigrid.level{lvl}"):
            x = self._smooth(L, jnp.zeros_like(r), r, self.pre_sweeps,
                             executor)
            res = r - sp_apply(L.A, x, executor=executor)
        with jax.named_scope(f"Multigrid.restrict{lvl}"):
            rc = sp_apply(L.R, res, executor=executor)
        xc = self._cycle(lvl + 1, rc, executor)
        if self.cycle == "w" and lvl + 1 < len(self.levels):
            # second recursive visit (γ = 2): correct with the updated
            # coarse residual before interpolating back up (the coarsest
            # visit is exact already — no second solve there)
            with jax.named_scope(f"Multigrid.level{lvl + 1}"):
                rc2 = rc - sp_apply(
                    self.levels[lvl + 1].A, xc, executor=executor
                )
            xc = xc + self._cycle(lvl + 1, rc2, executor)
        with jax.named_scope(f"Multigrid.prolong{lvl}"):
            x = x + sp_apply(L.P, xc, executor=executor)
        with jax.named_scope(f"Multigrid.level{lvl}"):
            return self._smooth(L, x, r, self.post_sweeps, executor)

    def _apply(self, r: jax.Array, executor) -> jax.Array:
        ex = executor if executor is not None else self.executor
        return self._cycle(0, r, ex)


def _multigrid_flatten(mg: Multigrid):
    children = (mg.levels, mg.coarse_A, mg._coarse_inv)
    meta = (mg._shape, mg._dtype, mg.cycle, mg.omega, mg.pre_sweeps,
            mg.post_sweeps, mg.operator_complexity, mg.executor)
    return children, meta


def _multigrid_unflatten(meta, children) -> Multigrid:
    mg = object.__new__(Multigrid)
    mg.levels, mg.coarse_A, mg._coarse_inv = children
    (mg._shape, mg._dtype, mg.cycle, mg.omega, mg.pre_sweeps,
     mg.post_sweeps, mg.operator_complexity, mg.executor) = meta
    return mg


# a pytree (arrays as data, level count and shapes as meta), so one compile
# of a jitted solve serves every request that hands it the same hierarchy
jax.tree_util.register_pytree_node(
    Multigrid, _multigrid_flatten, _multigrid_unflatten
)


def amg_preconditioner(A, *, executor=None, **opts) -> Multigrid:
    """``M="amg"`` factory — one V(1,1)-cycle of smoothed aggregation on a
    CSR or ELL operand."""
    if not isinstance(A, (Csr, Ell)):
        raise TypeError(
            f"amg preconditioner needs a CSR or ELL operand, got "
            f"{type(A).__name__}"
        )
    return Multigrid(A, executor=executor, **opts)


# =============================================================================
# Serve-path AMG: pattern-tier hierarchy + values-tier refresh
# =============================================================================
#
# The serve engine caches per *pattern* (indptr, indices) and refreshes per
# *values*, so the hierarchy must split the same way: aggregation from the
# pattern alone (every off-diagonal is treated as strong), an UNsmoothed
# prolongator (so P is values-free), and Galerkin coarse values that are pure
# segment-sums of the fine values over a pattern-derived map.  The cycle is
# the additive two-level correction  M⁻¹ r = ω·D⁻¹ r + P·A_c⁻¹·Pᵀ r  — SPD,
# batched over the lane's solve slots, and needing only the flat factor row
# ``[inv_diag | A_c⁻¹.flatten()]`` the values tier stores.


@dataclasses.dataclass(frozen=True)
class AmgServePattern:
    """Pattern-tier hierarchy data: values-independent, cacheable."""

    agg: np.ndarray        # (n,)  fine row -> aggregate
    n_agg: int
    coarse_indptr: np.ndarray   # coarse pattern (n_agg + 1,)
    coarse_indices: np.ndarray  # (coarse_nnz,)
    #: fine nnz slot -> coarse nnz slot (Galerkin product collapses to a
    #: segment-sum because P is the unit tentative prolongator)
    seg: np.ndarray
    #: fine nnz slots holding the diagonal, and their row ids
    diag_slots: np.ndarray
    n: int

    @property
    def flat_len(self) -> int:
        return self.n + self.n_agg * self.n_agg


def amg_serve_pattern(
    indptr: np.ndarray, indices: np.ndarray, n: int
) -> AmgServePattern:
    """Build the values-free two-level hierarchy from a sparsity pattern."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    nnz = indices.shape[0]
    strong = np.ones(nnz, bool)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    strong[rows == indices] = False
    agg, n_agg = aggregate(indptr, indices, strong, n)
    # Galerkin pattern: fine entry (i, j) lands at coarse (agg[i], agg[j])
    crows = agg[rows]
    ccols = agg[indices]
    order = np.lexsort((ccols, crows))
    head = np.ones(nnz, bool)
    head[1:] = (crows[order][1:] != crows[order][:-1]) | (
        ccols[order][1:] != ccols[order][:-1]
    )
    group = np.cumsum(head) - 1  # coarse slot per *sorted* fine entry
    seg = np.empty(nnz, np.int64)
    seg[order] = group
    starts = np.flatnonzero(head)
    c_indptr = np.zeros(n_agg + 1, np.int64)
    c_indptr[1:] = np.cumsum(np.bincount(crows[order][starts], minlength=n_agg))
    c_indices = ccols[order][starts].astype(np.int32)
    diag_slots = np.flatnonzero(rows == indices)
    return AmgServePattern(
        agg=agg,
        n_agg=n_agg,
        coarse_indptr=c_indptr,
        coarse_indices=c_indices,
        seg=seg,
        diag_slots=diag_slots,
        n=n,
    )


def amg_serve_factors(pat: AmgServePattern, values: jax.Array) -> jax.Array:
    """Values-tier refresh: flat row ``[inv_diag | A_c⁻¹.flatten()]``.

    Pure gathers and one segment-sum over pattern-derived maps — no
    re-aggregation, which is what hierarchy reuse in the setup cache means.
    """
    values = jnp.asarray(values)
    diag = values[jnp.asarray(pat.diag_slots)]
    inv_diag = jnp.where(diag != 0, 1.0 / diag, 0.0)
    c_vals = jax.ops.segment_sum(
        values, jnp.asarray(pat.seg),
        num_segments=int(pat.coarse_indices.shape[0]),
    )
    crows = np.repeat(
        np.arange(pat.n_agg, dtype=np.int64), np.diff(pat.coarse_indptr)
    )
    dense = jnp.zeros((pat.n_agg, pat.n_agg), values.dtype)
    dense = dense.at[jnp.asarray(crows), jnp.asarray(pat.coarse_indices)].add(
        c_vals
    )
    c_inv = jnp.linalg.inv(dense.astype(jnp.float32)).astype(values.dtype)
    return jnp.concatenate([inv_diag, c_inv.reshape(-1)])


def batch_amg_apply(
    pat: AmgServePattern, flat: jax.Array, R: jax.Array, omega: float = 2.0 / 3.0
) -> jax.Array:
    """Additive two-level correction over a batch: ``(nb, n) -> (nb, n)``.

    ``flat`` is the ``(nb, flat_len)`` stack of per-system factor rows from
    :func:`amg_serve_factors`.  ``M⁻¹ R = ω·D⁻¹ R + P·A_c⁻¹·Pᵀ R`` with the
    unit P — restriction is a scatter-add over aggregates, interpolation a
    gather; every op reduces row-independently, so a slot's apply matches the
    solo two-level correction bitwise.
    """
    n, nc = pat.n, pat.n_agg
    inv_diag = flat[:, :n]
    c_inv = flat[:, n:].reshape(-1, nc, nc)
    agg = jnp.asarray(pat.agg)
    rc = jnp.zeros((R.shape[0], nc), R.dtype).at[:, agg].add(R)
    xc = jnp.einsum("sc,sdc->sd", rc, c_inv)
    return omega * inv_diag * R + xc[:, agg]
