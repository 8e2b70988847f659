"""Device scopes and profiler spans: the program names its own seams.

Scopes (``jax.named_scope``) land in the HLO ``op_name`` metadata of every op
a dispatch or a ``LinOp.apply`` emits, and change nothing else; spans land on
the profiler's host plane while tracing is enabled, and cost nothing while
it is off.
"""

import contextlib
import glob
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import sparse
from repro.core import make_executor
from repro.precond import make_preconditioner
from repro.solvers import krylov
from repro.solvers.common import Stop
from repro.sparse import gallery
from repro.observability import trace

#: an HLO instruction line: ``%name = shape opcode(...)``, maybe ``ROOT``
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = \S+ ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.reset()
    yield
    trace.reset()


def _poisson_ell(n_side=6):
    indptr, indices, values, shape = gallery.poisson_3d(n_side)
    return sparse.ell_from_csr_host(indptr, indices, values.astype(np.float32), shape)


def _cg_hlo(A, M, ex) -> str:
    b = jnp.ones(A.shape[0], jnp.float32)
    stop = Stop(max_iters=20, reduction_factor=1e-6)
    fn = jax.jit(lambda A, M, b: krylov.cg(A, b, M=M, stop=stop, executor=ex,
                                           strict=False).x)
    return fn.lower(A, M, b).compile().as_text()


def _applied(op, ex) -> np.ndarray:
    """``op`` as a dense matrix: its apply on each unit column."""
    eye = jnp.eye(op.shape[1], dtype=jnp.float32)
    cols = jax.jit(jax.vmap(lambda e: sparse.apply(op, e, executor=ex)))(eye)
    return np.asarray(cols).T


def _instructions(hlo: str) -> list:
    """``[(name, opcode, op_name)]`` of every instruction of an HLO text."""
    out = []
    for line in hlo.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return out


@pytest.fixture(scope="module")
def bj_system():
    ex = make_executor("xla")
    A = _poisson_ell()
    return A, make_preconditioner(A, "block_jacobi", executor=ex, block_size=8), ex


def test_fused_cg_gathers_carry_their_op_and_linop_scopes(bj_system):
    A, M, ex = bj_system
    assert sparse.ops.has_fused_ops(A, executor=ex)

    def gather_scopes(M):
        return [set(op.split("/")) for _, opcode, op in
                _instructions(_cg_hlo(A, M, ex)) if opcode == "gather"]

    parts = gather_scopes(M)
    # the x[col_idx] gather of the loop's fused SpMV + dot
    assert any({"while", "spmv_dot_ell"} <= p for p in parts), parts
    # the initial residual's SpMV, through Ell.apply
    assert any({"Ell.apply", "spmv_ell"} <= p for p in parts), parts
    # uniform 8-blocks in one storage class: the row layout gathers nothing
    assert M.layout == "row"
    assert not any("BlockJacobi.apply" in p for p in parts), parts
    # blocks of varying size: the gather and scatter around the apply kernel
    n = A.shape[0]
    varied = make_preconditioner(A, "block_jacobi", executor=ex,
                                 blocks=[0, *range(5, n, 8), n])
    assert varied.layout == "gathered"
    parts = gather_scopes(varied)
    assert sum("BlockJacobi.apply" in p for p in parts) >= 2, parts


def test_scopes_leave_the_compiled_program_unchanged(bj_system, monkeypatch):
    A, M, ex = bj_system
    scoped = _instructions(_cg_hlo(A, M, ex))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _instructions(_cg_hlo(A, M, ex))
    assert len(scoped) == len(plain)
    assert sorted(o for _, o, _ in scoped) == sorted(o for _, o, _ in plain)
    assert any("BlockJacobi.apply" in op for *_, op in scoped)
    assert not any("BlockJacobi.apply" in op for *_, op in plain)


def test_every_dispatch_runs_in_its_op_scope():
    ex = make_executor("xla")
    x = jnp.arange(8.0, dtype=jnp.float32)
    hlo = jax.jit(lambda v: sparse.ops.dot(v, v, executor=ex)).lower(x).compile().as_text()
    assert any("blas_dot" in op.split("/") for *_, op in _instructions(hlo))


def test_span_lands_on_the_profiler_host_plane_on_the_device_clock(tmp_path):
    """Enabled: the span is a host event of the ``.xplane.pb`` that holds the
    ops it launched, around them on one clock, and a Chrome trace event."""
    from jax.profiler import ProfileData

    f = jax.jit(lambda v: jnp.sin(v) * 2.0)
    x = jnp.ones(256, jnp.float32)
    f(x).block_until_ready()
    tracer = trace.enable()
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("solve.step", cat="test"):
            f(x).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "solve.step":
                    spans.append((e.start_ns, e.end_ns))
                elif ("hlo_module", "jit__lambda") in list(e.stats):
                    ops.append((e.start_ns, e.end_ns))
    assert len(spans) == 1 and ops
    (s0, s1), = spans
    assert all(s0 <= a and b <= s1 for a, b in ops)
    assert [ev["name"] for ev in tracer.events] == ["solve.step"]
    assert trace.validate_trace(tracer.to_json()) == []


def test_disabled_span_is_the_singleton_and_stays_off_the_profile(tmp_path):
    from jax.profiler import ProfileData

    assert trace.span("a") is trace.span("b")
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("never.recorded"):
            pass
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert "never.recorded" not in names


def test_conversion_and_block_jacobi_generation_record_their_steps():
    tracer = trace.enable()
    A = _poisson_ell(4)
    make_preconditioner(A, "block_jacobi", executor=make_executor("xla"), block_size=8)
    events = {ev["name"]: ev for ev in tracer.events if ev["cat"] != "dispatch"}
    # uniform blocks in one storage class: the row layout builds no maps
    steps = ["block_jacobi." + s for s in
             ("extract", "invert", "classify", "upload")]
    assert set(events) == {"sparse.ell_from_csr_host", "block_jacobi.generate", *steps}
    parent = events["block_jacobi.generate"]
    assert parent["args"]["layout"] == "row"
    starts = [events[s]["ts"] for s in steps]
    assert starts == sorted(starts)  # in order, nested in the parent by time
    for s in steps:
        ev = events[s]
        assert parent["ts"] <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"]


def test_the_multigrid_cycle_names_its_levels_transfers_and_coarse_solve():
    """Inside ``Multigrid.apply`` every level's smoothing and residual, each
    restriction and prolongation, and the coarse solve carry scopes of their
    own; setup counts each level's transfer entries."""
    from repro.observability import metrics

    ex = make_executor("xla")
    A = _poisson_ell(12)
    metrics.reset()
    M = make_preconditioner(A, "amg", executor=ex, theta=0.0, coarse_size=16)
    assert M.num_levels >= 3
    ops = [op for *_, op in _instructions(_cg_hlo(A, M, ex)) if op]
    parts = [set(op.split("/")) for op in ops]
    names = ["Multigrid.coarse"]
    for k in range(M.num_levels - 1):
        names += [f"Multigrid.level{k}", f"Multigrid.restrict{k}", f"Multigrid.prolong{k}"]
    for name in names:
        assert any({"while", "Multigrid.apply", name} <= p for p in parts), name
    # the scopes of one level never nest in another's
    assert not any({"Multigrid.level0", "Multigrid.level1"} <= p for p in parts)
    gauges = {(s["name"], s["labels"].get("level")): s["value"] for s in metrics.samples()}
    for k, L in enumerate(M.levels):
        # the entries of P_k and R_k as the cycle applies them, stored or
        # factored: each operator on every unit column
        applied = sum(int(np.count_nonzero(_applied(op, ex))) for op in (L.P, L.R))
        assert gauges["amg_transfer_nnz", str(k)] == applied
    assert ("amg_transfer_nnz", str(M.num_levels - 1)) not in gauges
