"""Block-Jacobi preconditioner subsystem: correctness, adaptivity, portability.

Covers the acceptance criteria of the adaptive-precision block-Jacobi:

* the true block inverse solves an exactly block-diagonal SPD system in ONE
  CG iteration (the old diagonal-only approximation demonstrably cannot);
* adaptive storage reduces ``storage_bytes`` versus all-fp32 while CG
  iteration counts stay within 10% on the benchmark-style fixture;
* the three kernel spaces (reference / xla / pallas-interpret) agree on the
  apply to mixed-precision tolerance;
* the apply kernel family resolves geometry through the launch-configuration
  subsystem like every other family.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro import sparse, solvers
from repro.core import (
    PallasInterpretExecutor,
    ReferenceExecutor,
    XlaExecutor,
    registry,
    tuning,
    use_executor,
)
from repro.core import params as hw_params
from repro.precond import (
    batch_block_jacobi,
    block_jacobi,
    invert_blocks,
    natural_blocks,
    uniform_block_ptrs,
)

STOP = solvers.Stop(max_iters=500, reduction_factor=1e-6)


def block_spd(n, bs, coupling=0.0, cond_spread=False, seed=8):
    """Block-structured SPD fixture; optionally with off-block coupling and a
    per-block conditioning spread (so adaptive selection mixes precisions)."""
    rng = np.random.default_rng(seed)
    a = np.zeros((n, n), np.float32)
    for bi, s in enumerate(range(0, n, bs)):
        blk = rng.normal(size=(bs, bs)).astype(np.float32)
        blk = blk @ blk.T + 4 * np.eye(bs, dtype=np.float32)
        if cond_spread and bi % 2 == 1:
            # stretch one direction: condition number grows ~scale^2
            scale = np.linspace(1.0, 40.0, bs).astype(np.float32)
            blk = blk * np.sqrt(scale[:, None] * scale[None, :])
        a[s : s + bs, s : s + bs] = blk
    for i in range(n - bs):
        a[i, i + bs] = a[i + bs, i] = coupling
    return a


# -----------------------------------------------------------------------------
# the correctness gap the diagonal-only predecessor had
# -----------------------------------------------------------------------------


def test_block_diagonal_system_one_cg_iteration():
    """On an exactly block-diagonal SPD system, block-Jacobi IS the inverse:
    CG must converge in a single iteration.  The scale-only (diagonal)
    approximation fails this — it needs many iterations — which is exactly
    the gap this subsystem closes."""
    n, bs = 96, 4
    a = block_spd(n, bs)
    rng = np.random.default_rng(1)
    xstar = rng.normal(size=n).astype(np.float32)
    b = (a @ xstar).astype(np.float32)
    A = sparse.csr_from_dense(a)
    with use_executor(XlaExecutor()):
        M = solvers.block_jacobi_preconditioner(A, block_size=bs)
        res = solvers.cg(A, jnp.asarray(b), stop=STOP, M=M)
        assert bool(res.converged)
        assert int(res.iterations) == 1, (
            f"true block inverse must solve a block-diagonal system in one "
            f"iteration, took {int(res.iterations)}"
        )
        np.testing.assert_allclose(np.asarray(res.x), xstar, atol=1e-3)

        # the diagonal-only approximation (scalar Jacobi — what the old
        # implementation effectively was on non-diagonal blocks) cannot
        scalar = solvers.cg(
            A, jnp.asarray(b), stop=STOP, M=solvers.jacobi_preconditioner(A)
        )
        assert int(scalar.iterations) > 1


def test_apply_equals_exact_block_inverse():
    n, bs = 64, 8
    a = block_spd(n, bs)
    v = np.random.default_rng(2).normal(size=n).astype(np.float32)
    want = np.linalg.solve(a, v)
    for fmt in ("csr", "ell", "sellp", "coo", "dense"):
        A = (
            sparse.Dense(jnp.asarray(a))
            if fmt == "dense"
            else getattr(sparse, f"{fmt}_from_dense")(a)
        )
        M = block_jacobi(A, block_size=bs, executor=XlaExecutor())
        got = np.asarray(M(jnp.asarray(v)))
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=fmt)


def test_gauss_jordan_matches_linalg_inv():
    rng = np.random.default_rng(5)
    blocks = rng.normal(size=(20, 6, 6)).astype(np.float32)
    blocks += 4 * np.eye(6, dtype=np.float32)
    got = np.asarray(invert_blocks(jnp.asarray(blocks)))
    want = np.linalg.inv(blocks)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_gauss_jordan_pivots_zero_diagonal():
    """[[0, 1], [1, 0]] is nonsingular but has a zero diagonal — partial
    pivoting must invert it (a ridge-regularized fallback would not)."""
    b = jnp.asarray([[[0.0, 1.0], [1.0, 0.0]]], jnp.float32)
    got = np.asarray(invert_blocks(b))[0]
    np.testing.assert_allclose(got, [[0.0, 1.0], [1.0, 0.0]], atol=1e-6)


def test_singular_block_degrades_to_identity():
    """Rank-deficient blocks must fall back to identity — not a finite but
    wrong 'inverse' computed with a substituted pivot.  [[1,1],[1,1]] is the
    canonical trap: elimination finds a zero pivot mid-way."""
    eye3 = np.eye(3, dtype=np.float32)
    got = np.asarray(invert_blocks(jnp.zeros((1, 3, 3), jnp.float32)))[0]
    np.testing.assert_array_equal(got, eye3)
    rank1 = jnp.asarray([[[1.0, 1.0], [1.0, 1.0]]], jnp.float32)
    got = np.asarray(invert_blocks(rank1))[0]
    np.testing.assert_array_equal(got, np.eye(2, dtype=np.float32))
    # and a healthy block in the same batch is still inverted properly
    both = jnp.asarray(
        [[[1.0, 1.0], [1.0, 1.0]], [[2.0, 0.0], [0.0, 4.0]]], jnp.float32
    )
    got = np.asarray(invert_blocks(both))
    np.testing.assert_array_equal(got[0], np.eye(2, dtype=np.float32))
    np.testing.assert_allclose(got[1], [[0.5, 0.0], [0.0, 0.25]], atol=1e-6)


def test_natural_block_discovery():
    """Supervariable agglomeration recovers the true block partition of a
    block-diagonal sparsity pattern."""
    n, bs = 48, 4
    a = block_spd(n, bs)
    ptrs = natural_blocks(sparse.csr_from_dense(a), max_block_size=8)
    np.testing.assert_array_equal(ptrs, uniform_block_ptrs(n, bs))


def test_non_divisible_n_padded_block():
    a = block_spd(50, 5, coupling=0.1)  # 50 % 4 != 0 with bs=4
    rng = np.random.default_rng(3)
    xstar = rng.normal(size=50).astype(np.float32)
    b = (a @ xstar).astype(np.float32)
    A = sparse.csr_from_dense(a)
    with use_executor(XlaExecutor()):
        M = solvers.block_jacobi_preconditioner(A, block_size=4)
        res = solvers.cg(A, jnp.asarray(b), stop=STOP, M=M)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), xstar, atol=1e-3)


# -----------------------------------------------------------------------------
# adaptive precision — the PR's acceptance criteria
# -----------------------------------------------------------------------------


def _bench_fixture():
    """Benchmark-style fixture: blocked SPD, weak coupling, mixed per-block
    conditioning (half the blocks are well-conditioned, half stretched)."""
    n, bs = 128, 8
    a = block_spd(n, bs, coupling=0.05, cond_spread=True)
    rng = np.random.default_rng(11)
    xstar = rng.normal(size=n).astype(np.float32)
    return a, bs, xstar, (a @ xstar).astype(np.float32)


def test_adaptive_reduces_storage_within_iteration_budget():
    """Acceptance: adaptive block-Jacobi stores strictly fewer bytes than
    all-fp32 while CG takes no more than 10% extra iterations."""
    a, bs, xstar, b = _bench_fixture()
    A = sparse.csr_from_dense(a)
    with use_executor(XlaExecutor()):
        full = solvers.block_jacobi_preconditioner(A, block_size=bs)
        adap = solvers.block_jacobi_preconditioner(A, block_size=bs, adaptive=True)
        assert adap.storage_bytes < full.storage_bytes, (
            f"adaptive {adap.storage_bytes}B must undercut fp32 "
            f"{full.storage_bytes}B ({adap.precision_counts})"
        )
        r_full = solvers.cg(A, jnp.asarray(b), stop=STOP, M=full)
        r_adap = solvers.cg(A, jnp.asarray(b), stop=STOP, M=adap)
    assert bool(r_full.converged) and bool(r_adap.converged)
    k_full, k_adap = int(r_full.iterations), int(r_adap.iterations)
    assert k_adap <= int(np.ceil(1.10 * k_full)), (
        f"adaptive CG took {k_adap} iterations vs fp32's {k_full} "
        f"(>10% regression); classes: {adap.precision_counts}"
    )
    np.testing.assert_allclose(np.asarray(r_adap.x), xstar, atol=2e-3)


def test_adaptive_selects_mixed_classes():
    """The conditioning spread must produce a genuine precision mix — an
    all-or-nothing selection would mean the rule is degenerate."""
    a, bs, _, _ = _bench_fixture()
    A = sparse.csr_from_dense(a)
    M = block_jacobi(A, block_size=bs, adaptive=True, executor=XlaExecutor())
    dtypes = dict(M.precision_counts)
    assert len(dtypes) >= 2, f"expected a precision mix, got {dtypes}"
    assert sum(dtypes.values()) == M.num_blocks


def test_adaptive_spaces_agree_mixed_precision():
    """Acceptance: reference / xla / pallas-interpret agree on the adaptive
    apply to mixed-precision tolerance."""
    a, bs, _, _ = _bench_fixture()
    A = sparse.csr_from_dense(a)
    v = jnp.asarray(np.random.default_rng(7).normal(size=a.shape[0]).astype(np.float32))
    outs = {}
    for cls in (ReferenceExecutor, XlaExecutor, PallasInterpretExecutor):
        ex = cls()
        M = block_jacobi(A, block_size=bs, adaptive=True, executor=ex)
        outs[cls.__name__] = np.asarray(M(v))
        # the dispatch layer must have served the apply op
        assert ex.dispatch_log["block_jacobi_apply"] > 0
    ref = outs.pop("ReferenceExecutor")
    for name, got in outs.items():
        # fp16 storage bounds the element error at ~2^-11 * |y|
        np.testing.assert_allclose(got, ref, atol=5e-3, err_msg=name)


def test_forced_storage_dtype():
    a, bs, _, _ = _bench_fixture()
    A = sparse.csr_from_dense(a)
    M = block_jacobi(A, block_size=bs, adaptive="bfloat16", executor=XlaExecutor())
    assert M.storage_dtypes == ("bfloat16",)
    assert M.storage_bytes == M.num_blocks * bs * bs * 2


def test_bs1_matches_scalar_jacobi():
    rng = np.random.default_rng(9)
    a = block_spd(48, 4, coupling=0.1)
    A = sparse.csr_from_dense(a)
    with use_executor(XlaExecutor()):
        m1 = solvers.jacobi_preconditioner(A)
        m2 = solvers.block_jacobi_preconditioner(A, block_size=1)
        v = jnp.asarray(rng.normal(size=48).astype(np.float32))
        np.testing.assert_allclose(np.asarray(m1(v)), np.asarray(m2(v)), rtol=1e-5)


def test_scalar_jacobi_adaptive_storage():
    a = block_spd(64, 4)
    A = sparse.csr_from_dense(a)
    with use_executor(XlaExecutor()):
        full = solvers.jacobi_preconditioner(A)
        adap = solvers.jacobi_preconditioner(A, adaptive=True)
        v = jnp.asarray(np.random.default_rng(0).normal(size=64).astype(np.float32))
        assert adap.storage_bytes < full.storage_bytes
        got, want = np.asarray(adap(v)), np.asarray(full(v))
        assert got.dtype == np.float32  # arithmetic precision is preserved
        np.testing.assert_allclose(got, want, atol=2e-3)


# -----------------------------------------------------------------------------
# batched variant
# -----------------------------------------------------------------------------


def test_batch_block_jacobi_matches_single_loop():
    from repro import batch as batch_lib

    ns, n, bs = 5, 40, 4
    stack = np.stack([block_spd(n, bs, seed=20 + i) for i in range(ns)])
    A = batch_lib.batch_csr_from_dense(stack)
    V = np.random.default_rng(4).normal(size=(ns, n)).astype(np.float32)
    M = batch_block_jacobi(A, block_size=bs, executor=XlaExecutor())
    got = np.asarray(M(jnp.asarray(V)))
    want = np.stack([np.linalg.solve(stack[i], V[i]) for i in range(ns)])
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_batch_adaptive_reduces_storage_and_converges():
    from repro import batch as batch_lib
    from repro.batch import batch_cg

    ns, n, bs = 6, 48, 4
    stack = np.stack(
        [block_spd(n, bs, coupling=0.05, seed=30 + i) for i in range(ns)]
    )
    A = batch_lib.batch_ell_from_dense(stack)
    rng = np.random.default_rng(5)
    Xstar = rng.normal(size=(ns, n)).astype(np.float32)
    B = jnp.asarray(np.einsum("sij,sj->si", stack, Xstar))
    full = batch_block_jacobi(A, block_size=bs, executor=XlaExecutor())
    adap = batch_block_jacobi(A, block_size=bs, adaptive=True, executor=XlaExecutor())
    assert adap.storage_bytes < full.storage_bytes
    r_full = batch_cg(A, B, stop=STOP, M=full)
    r_adap = batch_cg(
        A, B, stop=STOP, M="block_jacobi",
        precond_opts={"block_size": bs, "adaptive": True},
    )
    assert bool(r_full.converged.all()) and bool(r_adap.converged.all())
    k_full = np.asarray(r_full.iterations)
    k_adap = np.asarray(r_adap.iterations)
    assert (k_adap <= np.ceil(1.10 * k_full) + 1).all(), (k_full, k_adap)
    np.testing.assert_allclose(np.asarray(r_adap.x), Xstar, atol=2e-3)


def test_batch_empty_row_matches_formats_and_single():
    """A system with a structurally empty row: BatchEll's q==0 padding slot is
    indistinguishable from a real col-0 entry, so the empty-row identity
    fallback must act on gathered *values* — BatchCsr, BatchEll, and the
    single-system path all have to agree (only the empty row degrades, not
    its whole block)."""
    from repro import batch as batch_lib

    n, bs = 8, 4
    a = block_spd(n, bs, seed=40)
    a[0, :] = 0.0
    a[:, 0] = 0.0
    stack = a[None]  # one system is enough
    V = np.random.default_rng(1).normal(size=(1, n)).astype(np.float32)
    want = np.asarray(
        block_jacobi(
            sparse.csr_from_dense(a), block_size=bs, executor=XlaExecutor()
        )(jnp.asarray(V[0]))
    )
    for builder in ("batch_csr_from_dense", "batch_ell_from_dense"):
        A = getattr(batch_lib, builder)(stack)
        M = batch_block_jacobi(A, block_size=bs, executor=XlaExecutor())
        got = np.asarray(M(jnp.asarray(V)))[0]
        np.testing.assert_allclose(got, want, atol=1e-5, err_msg=builder)


# -----------------------------------------------------------------------------
# launch-configuration plumbing
# -----------------------------------------------------------------------------


def test_block_jacobi_uses_launch_config():
    shapes = {"nb": 64, "bs": 8, "itemsize": 4}
    ex = PallasInterpretExecutor()
    base = ex.launch_config("block_jacobi", shapes)
    assert set(base.block) == {"block_nb"}
    try:
        # a whole number of (8 sublane x 128 lane) tiles of blocks
        tuning.set_table_entry("block_jacobi", ex.hw.name, {"block_nb": 2048})
        pinned = ex.launch_config("block_jacobi", shapes)
        assert pinned["block_nb"] == 2048
    finally:
        tuning._TABLE.pop(("block_jacobi", ex.hw.name), None)


def test_block_jacobi_vmem_fallback():
    """A starved target still serves the apply through the pallas kernel at
    its smallest tile (no switch to another formulation) and matches the
    oracle."""
    rng = np.random.default_rng(6)
    inv = jnp.asarray(rng.normal(size=(16, 8, 8)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    starved = dataclasses.replace(hw_params.CPU_INTERPRET, vmem_limit_bytes=64)
    op = registry.operation("block_jacobi_apply")
    got = op(inv, vp, executor=PallasInterpretExecutor(starved))
    want = op(inv, vp, executor=ReferenceExecutor())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _extract_blocks_loop(indptr, indices, values, block_ptrs):
    """Row-by-row reference for the vectorized host block extraction."""
    sizes = np.diff(block_ptrs)
    nb, bs = len(sizes), int(sizes.max())
    blocks = np.zeros((nb, bs, bs), values.dtype)
    for b in range(nb):
        lo, hi = int(block_ptrs[b]), int(block_ptrs[b + 1])
        for i in range(lo, hi):
            cols = indices[indptr[i] : indptr[i + 1]]
            vals = values[indptr[i] : indptr[i + 1]]
            keep = (cols >= lo) & (cols < hi)
            blocks[b, i - lo, cols[keep] - lo] = vals[keep]
        for l in range(hi - lo, bs):
            blocks[b, l, l] = 1.0
        for l in range(hi - lo):
            if not blocks[b, l].any():
                blocks[b, l, l] = 1.0
    return blocks


def test_host_block_extraction_matches_row_loop():
    """Ragged block pointers, empty rows, off-block entries: the vectorized
    extraction and the class-ordered gather/scatter maps equal the
    row-by-row construction exactly."""
    from repro.precond.block_jacobi import _extract_blocks_host

    rng = np.random.default_rng(11)
    n = 53
    a = rng.normal(size=(n, n)).astype(np.float32)
    a[rng.random(a.shape) < 0.8] = 0.0
    a[[4, 17, 30]] = 0.0  # structurally empty rows
    A = sparse.csr_from_dense(a)
    ptrs = np.array([0, 3, 8, 9, 16, 24, 31, 40, 47, 53])
    blocks, sizes = _extract_blocks_host(A, ptrs)
    indptr, indices, values = sparse.csr_host_arrays(A)
    np.testing.assert_array_equal(
        blocks, _extract_blocks_loop(indptr, indices, values, ptrs)
    )
    np.testing.assert_array_equal(sizes, np.diff(ptrs))

    M = block_jacobi(A, blocks=ptrs, adaptive=True, executor=XlaExecutor())
    gather, scatter = np.asarray(M.gather_idx), np.asarray(M.scatter_idx)
    bs = M.block_size
    for r in range(n):  # row r sits in slot scatter[r] and is gathered back
        assert gather.reshape(-1)[scatter[r]] == r
    assert (gather == n).sum() == gather.size - n  # the rest are pad slots
    assert scatter.max() < M.num_blocks * bs


# -----------------------------------------------------------------------------
# layouts: row order when the blocks are uniform and share one storage class
# -----------------------------------------------------------------------------


def _varied_blocks(sizes=(3, 5, 2, 6, 4, 5, 1, 6), seed=4):
    """Block-diagonal SPD matrix whose natural blocks vary in size."""
    rng = np.random.default_rng(seed)
    n = sum(sizes)
    a = np.zeros((n, n), np.float32)
    lo = 0
    for s in sizes:
        blk = rng.normal(size=(s, s)).astype(np.float32)
        a[lo : lo + s, lo : lo + s] = blk @ blk.T + 4 * np.eye(s, dtype=np.float32)
        lo += s
    return a


def _layout_counts():
    from repro.observability import metrics

    return {
        layout: metrics.counter("block_jacobi.layout", layout=layout).value
        for layout in ("row", "gathered")
    }


@pytest.mark.parametrize("transpose", [False, True], ids=["apply", "transpose"])
@pytest.mark.parametrize("n", [64, 61], ids=["whole", "ragged"])
@pytest.mark.parametrize("ex_cls", [XlaExecutor, PallasInterpretExecutor],
                         ids=["xla", "pallas"])
def test_row_layout_equals_gathered_bitwise(ex_cls, n, transpose):
    """The row layout carries no maps and applies bitwise as the gathered
    layout does with identity maps over the same inverted blocks."""
    import jax

    from repro.precond.block_jacobi import _block_maps

    bs = 8
    A = sparse.csr_from_dense(block_spd(64, bs, coupling=0.1)[:n, :n])
    M = block_jacobi(A, block_size=bs, executor=ex_cls())
    assert M.layout == "row"
    assert M.gather_idx is None and M.scatter_idx is None
    gather, scatter = _block_maps(uniform_block_ptrs(n, bs), np.arange(M.num_blocks), bs)
    G = dataclasses.replace(
        M, gather_idx=jnp.asarray(gather), scatter_idx=jnp.asarray(scatter)
    )
    assert G.layout == "gathered"
    if transpose:
        M, G = M.transpose(), G.transpose()
        assert M.layout == "row" and M.gather_idx is None
    v = jnp.asarray(np.random.default_rng(5).normal(size=n).astype(np.float32))
    apply = jax.jit(lambda op, u: op(u))
    got, want = np.asarray(apply(M, v)), np.asarray(apply(G, v))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (n,)


@pytest.mark.parametrize(
    "case, layout",
    [
        ("uniform", "row"),
        ("ragged_uniform", "row"),
        ("forced_storage", "row"),
        ("natural_varied", "gathered"),
        ("explicit_varied", "gathered"),
        ("adaptive_mixed", "gathered"),
    ],
)
def test_layout_decided_at_generation(case, layout):
    """Uniform blocks in one storage class take the row layout; varying
    blocks and interleaved storage classes keep the maps.  Each generation
    counts its layout once and names it on the ``block_jacobi.generate``
    span."""
    from repro.observability import trace

    ex = XlaExecutor()
    if case in ("natural_varied", "explicit_varied"):
        a = _varied_blocks()
        A = sparse.csr_from_dense(a)
        ptrs = (natural_blocks(A) if case == "natural_varied"
                else np.array([0, 4, 8, 10, 16, 20, 26, 32]))
        kwargs = dict(blocks=ptrs)
    else:
        a, bs, _, _ = _bench_fixture()
        if case == "ragged_uniform":
            a = a[:123, :123]
        A = sparse.csr_from_dense(a)
        kwargs = dict(block_size=bs, adaptive={
            "uniform": False, "ragged_uniform": False,
            "forced_storage": "bfloat16", "adaptive_mixed": True}[case])
    before = _layout_counts()
    tracer = trace.enable()
    try:
        M = block_jacobi(A, executor=ex, **kwargs)
    finally:
        trace.disable()
    after = _layout_counts()
    assert M.layout == layout
    assert {k: after[k] - before[k] for k in after} == {
        k: int(k == layout) for k in after
    }
    spans = [e for e in tracer.events if e["name"] == "block_jacobi.generate"]
    assert spans[-1]["args"]["layout"] == layout
    steps = {e["name"] for e in tracer.events}
    assert ("block_jacobi.maps" in steps) == (layout == "gathered")
    if layout == "gathered":
        assert M.gather_idx.shape == (M.num_blocks, M.block_size)
        assert M.scatter_idx.shape == (a.shape[0],)
    if case == "adaptive_mixed":
        assert len(M.inv_blocks) > 1
    # either layout applies the inverse of its diagonal blocks
    v = np.random.default_rng(6).normal(size=a.shape[0]).astype(np.float32)
    ptrs = kwargs.get("blocks", uniform_block_ptrs(a.shape[0], M.block_size))
    want = np.concatenate([
        np.linalg.solve(a[lo:hi, lo:hi], v[lo:hi])
        for lo, hi in zip(ptrs[:-1], ptrs[1:])
    ])
    tol = 2e-2 if case in ("forced_storage", "adaptive_mixed") else 1e-4  # 16-bit storage
    np.testing.assert_allclose(np.asarray(M(jnp.asarray(v))), want, rtol=tol, atol=tol)
