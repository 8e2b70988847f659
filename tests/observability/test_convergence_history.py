"""Convergence telemetry: ``history=`` on every solver, plus the acceptance
pin that a traced CG solve reproduces the pinned launch-count structure."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import sparse
from repro.core import make_executor
from repro.observability import convergence, trace
from repro.solvers import krylov
from repro.solvers.common import Stop
from repro.solvers.ir import ir, mixed_precision_ir

#: dispatches in the CG loop body: the fused spmv_dot + axpy_norm, or the
#: seven unfused spmv / dot / norm / axpy ops
BODY_LAUNCHES = {True: 2, False: 7}


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.reset()
    yield
    trace.reset()


def _spd(n=64):
    a = np.zeros((n, n), np.float32)
    for i in range(n):
        a[i, i] = 4.0
        if i > 0:
            a[i, i - 1] = a[i - 1, i] = -1.0
        if i > 2:
            a[i, i - 3] = a[i - 3, i] = -0.5
    return a


def _system(n=64, nonsym=False, seed=0):
    a = _spd(n)
    if nonsym:
        rng = np.random.default_rng(seed)
        a = a + np.triu(rng.normal(size=(n, n)).astype(np.float32), 1) * 0.05
    rng = np.random.default_rng(seed + 1)
    x = rng.normal(size=n).astype(np.float32)
    return a, (a @ x).astype(np.float32)


STOP = Stop(max_iters=200, reduction_factor=1e-6)


def _check_history(res, *, rtol=1e-4):
    hist = convergence.trim(res.history)
    assert hist is not None and len(hist) > 0
    assert np.all(np.isfinite(hist))
    np.testing.assert_allclose(
        hist[-1], float(res.residual_norm), rtol=rtol,
        err_msg="last recorded residual != SolveResult.residual_norm",
    )
    return hist


@pytest.mark.parametrize(
    "solver,opts",
    [
        ("cg", {}),
        ("cg", {"fused": False}),
        ("cg", {"pipeline": True}),
        ("fcg", {}),
        ("bicgstab", {}),
        ("bicgstab", {"fused": False}),
        ("cgs", {}),
    ],
)
def test_history_matches_residual(solver, opts):
    nonsym = solver in ("bicgstab", "cgs")
    a, b = _system(nonsym=nonsym)
    A = sparse.csr_from_dense(a)
    ex = make_executor("xla")
    fn = getattr(krylov, solver)
    res = fn(A, jnp.asarray(b), stop=STOP, executor=ex, history=True, **opts)
    assert res.converged
    hist = _check_history(res)
    assert len(hist) == int(res.iterations)
    # without the option the field stays None (no buffer in the loop state)
    res_off = fn(A, jnp.asarray(b), stop=STOP, executor=ex, **opts)
    assert res_off.history is None
    np.testing.assert_allclose(
        float(res_off.residual_norm), float(res.residual_norm), rtol=1e-4
    )


def test_gmres_history_per_restart_cycle():
    a, b = _system(nonsym=True)
    A = sparse.csr_from_dense(a)
    res = krylov.gmres(
        A, jnp.asarray(b), stop=STOP, executor=make_executor("xla"),
        restart=20, history=True,
    )
    assert res.converged
    hist = _check_history(res)
    # gmres records once per restart cycle, not per inner iteration
    cycles = -(-int(res.iterations) // 20)
    assert len(hist) == cycles


def test_history_capacity_and_ring_buffer():
    stop = Stop(max_iters=100, reduction_factor=1e-6)
    assert convergence.capacity(None, stop) == 0
    assert convergence.capacity(False, stop) == 0
    assert convergence.capacity(True, stop) == 100
    assert convergence.capacity(7, stop) == 7

    hist = convergence.init(4, dtype=jnp.float32)
    assert hist.shape == (4,) and bool(jnp.all(jnp.isnan(hist)))
    for k in range(6):  # wraps: 4,5 overwrite slots 0,1
        hist = convergence.push(hist, k, float(k))
    np.testing.assert_allclose(np.asarray(hist), [4.0, 5.0, 2.0, 3.0])

    empty = convergence.init(0)
    assert convergence.push(empty, 0, 1.0) is empty  # static no-op
    assert convergence.finalize(empty) is None
    assert convergence.trim(None) is None


def test_history_int_cap_rings_on_solver():
    a, b = _system()
    A = sparse.csr_from_dense(a)
    res = krylov.cg(
        A, jnp.asarray(b), stop=STOP, executor=make_executor("xla"), history=4
    )
    assert res.history.shape == (4,)
    # ran longer than the cap: every ring slot was overwritten with a real norm
    assert int(res.iterations) > 4
    assert np.all(np.isfinite(np.asarray(res.history)))


def test_history_under_jit():
    a, b = _system()
    A = sparse.csr_from_dense(a)
    ex = make_executor("xla")

    @jax.jit
    def solve(bb):
        return krylov.cg(A, bb, stop=STOP, executor=ex, history=True)

    res = solve(jnp.asarray(b))
    hist = _check_history(res)
    assert res.history.shape == (STOP.max_iters,)
    assert len(hist) == int(res.iterations)


def test_ir_history():
    a, b = _system()
    A = sparse.csr_from_dense(a)
    ex = make_executor("xla")
    stop = Stop(max_iters=200, reduction_factor=1e-5)
    res = ir(A, jnp.asarray(b), stop=stop, executor=ex, relaxation=0.15,
             history=True)
    assert res.converged
    _check_history(res)
    res_mp = mixed_precision_ir(A, jnp.asarray(b), stop=stop, executor=ex,
                                history=True)
    assert res_mp.converged
    _check_history(res_mp, rtol=1e-3)


def test_batch_history():
    from repro.batch import formats as bf
    from repro.batch.solvers import batch_cg

    nb, n = 4, 32
    rng = np.random.default_rng(0)
    a = _spd(n)
    # vary the diagonal per system so iteration counts differ across the batch
    mats = np.stack([a + np.eye(n, dtype=np.float32) * s
                     for s in (0.0, 0.5, 1.0, 2.0)])
    xs = rng.normal(size=(nb, n)).astype(np.float32)
    bs = np.einsum("bij,bj->bi", mats, xs).astype(np.float32)
    A = bf.batch_csr_from_dense(mats)
    ex = make_executor("xla")
    stop = Stop(max_iters=100, reduction_factor=1e-6)
    res = batch_cg(A, jnp.asarray(bs), stop=stop, executor=ex, history=True)
    assert bool(np.asarray(res.converged).all())
    assert res.history.shape == (100, nb)
    hist = convergence.trim(res.history)
    np.testing.assert_allclose(
        hist[-1], np.asarray(res.residual_norms), rtol=1e-3
    )
    res_off = batch_cg(A, jnp.asarray(bs), stop=stop, executor=ex)
    assert res_off.history is None


# =============================================================================
# acceptance: the traced solve reproduces the PR-6 launch structure
# =============================================================================


def _body_launches(counts, fused):
    if fused:
        return counts.get("spmv_dot_csr", 0) + counts.get("axpy_norm", 0)
    return (
        (counts.get("spmv_csr", 0) - 1)
        + (counts.get("blas_dot", 0) - 1)
        + (counts.get("blas_norm2", 0) - 2)
        + counts.get("blas_axpy", 0)
    )


@pytest.mark.parametrize("fused", [True, False])
def test_traced_cg_matches_bench_pins(tmp_path, fused):
    """A traced CG solve must produce a valid Chrome trace whose dispatch
    span counts reproduce the pinned launch structure (2 fused / 7 unfused
    body launches) — the trace is the pins' live counterpart."""
    want = BODY_LAUNCHES[fused]

    a, b = _system(n=96, seed=3)
    A = sparse.csr_from_dense(a)
    ex = make_executor("xla")
    path = str(tmp_path / "cg_trace.json")
    stop = Stop(max_iters=500, reduction_factor=1e-6)
    with trace.tracing(path):
        ex.dispatch_log.clear()
        res = krylov.cg(A, jnp.asarray(b), stop=stop, executor=ex,
                        fused=fused, history=True)
        counts = dict(ex.dispatch_log)
        events = list(ex.dispatch_events)
    assert res.converged
    assert trace.validate_trace(path) == []

    # the Counter face, the event stream, and the Chrome trace must agree
    assert _body_launches(counts, fused) == want
    ev_counts = {}
    for e in events:
        ev_counts[e.op] = ev_counts.get(e.op, 0) + 1
    assert ev_counts == counts
    with open(path) as f:
        data = json.load(f)
    span_counts = {}
    for ev in data["traceEvents"]:
        if ev.get("cat") == "dispatch":
            span_counts[ev["name"]] = span_counts.get(ev["name"], 0) + 1
    assert _body_launches(span_counts, fused) == want

    # and history telemetry rode along without adding launches
    assert convergence.trim(res.history) is not None
