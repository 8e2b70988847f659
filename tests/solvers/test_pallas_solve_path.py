"""CG + block-Jacobi on a small 3-D Poisson system through the Pallas kernels.

The solve the chip benchmark runs, at ``poisson_3d(8)`` and in interpret mode:
every solve-path op must be served by the ``pallas`` space (never by
``reference``), the stencil must take the band kernel, and the answer must
match the same solve on the XLA executor and float64 on the host.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import sparse
from repro.core import make_executor, registry
from repro.launch.dist_solve import build_system, csr_matvec_f64
from repro.precond import make_preconditioner
from repro.solvers import krylov
from repro.solvers.common import Stop

N_SIDE = 8
STOP = Stop(max_iters=5000, reduction_factor=1e-6)
PALLAS_OPS = ("spmv_dot_ell", "axpy_norm", "block_jacobi_apply")


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _solve(ex, A, b):
    M = make_preconditioner(A, "block_jacobi", executor=ex)

    def solve(A, M, b):
        res = krylov.cg(A, b, M=M, stop=STOP, executor=ex, strict=False)
        return res.x, res.iterations, res.converged

    program = str(jax.make_jaxpr(solve)(A, M, b))
    x, iters, conv = jax.jit(solve)(A, M, b)
    assert bool(conv)
    return {"x": x, "iterations": int(iters), "program": program, "ex": ex}


@pytest.fixture(scope="module")
def system():
    host, _, b_host = build_system(N_SIDE)
    return host, sparse.ell_from_csr_host(*host), b_host


@pytest.fixture(scope="module")
def pallas_run(system):
    _, A, b = system
    return _solve(make_executor("pallas_interpret"), A, jnp.asarray(b))


@pytest.fixture(scope="module")
def xla_run(system):
    _, A, b = system
    return _solve(make_executor("xla"), A, jnp.asarray(b))


@pytest.mark.parametrize("op", PALLAS_OPS + ("spmv_dot_ell_band",))
def test_solve_path_op_served_by_pallas(system, pallas_run, op):
    ex = pallas_run["ex"]
    if op == "spmv_dot_ell_band":
        # the stencil converts to diagonal-aligned slots, so the fused SpMV
        # takes the band kernel, whose pallas_call carries this name
        assert system[1].offsets is not None
        assert op in pallas_run["program"]
        op = "spmv_dot_ell"
    assert op in ex.dispatch_log
    assert registry.operation(op).resolve(ex)[0] == "pallas"


def test_no_solve_op_served_by_reference(pallas_run):
    ex = pallas_run["ex"]
    spaces = {op: registry.operation(op).resolve(ex)[0] for op in ex.dispatch_log}
    assert "reference" not in spaces.values(), spaces


def test_pallas_solve_matches_xla_iterations(pallas_run, xla_run):
    assert pallas_run["iterations"] == xla_run["iterations"]


def test_pallas_solve_matches_xla_solution(pallas_run, xla_run):
    assert _rel(pallas_run["x"], xla_run["x"]) <= 1e-4


def test_pallas_solve_host_float64_residual(system, pallas_run):
    host, _, b = system
    b64 = np.asarray(b, np.float64)
    r = b64 - csr_matvec_f64(host, pallas_run["x"])
    assert np.linalg.norm(r) / np.linalg.norm(b64) <= 1e-4
