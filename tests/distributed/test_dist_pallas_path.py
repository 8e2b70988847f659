"""Distributed CG + Jacobi over four row slabs through the Pallas kernels.

The four-chip cell's solve at ``poisson_3d(8)``, on four virtual CPU devices
in interpret mode: ``DistEll`` CG must take the iteration count of the same
CG on one device, its result must span the four devices, and each slab's
local SpMV must be served by the ``pallas`` space.
"""

import json

import pytest

SCRIPT = """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import sparse
    from repro.core import make_executor, registry
    from repro.distributed import DistEll, Partition
    from repro.launch.dist_solve import build_system
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    stop = Stop(max_iters=5000, reduction_factor=1e-6)
    host, _, b_host = build_system(8)
    A = sparse.ell_from_csr_host(*host)
    b = jnp.asarray(b_host)
    ex = make_executor("pallas_interpret")
    dist = krylov.cg(DistEll.from_matrix(A, Partition.uniform(A.shape[0], 4)),
                     b, M="jacobi", stop=stop, executor=ex)
    one = krylov.cg(A, b, M="jacobi", stop=stop,
                    executor=make_executor("pallas_interpret"))
    x, x1 = np.asarray(dist.x, np.float64), np.asarray(one.x, np.float64)
    print("RESULT " + json.dumps({
        "converged": bool(dist.converged) and bool(one.converged),
        "iterations": int(dist.iterations),
        "one_device_iterations": int(one.iterations),
        "devices": len(dist.x.sharding.device_set),
        "rel_diff": float(np.linalg.norm(x - x1) / np.linalg.norm(x1)),
        "spmv_ell_space": (registry.operation("spmv_ell").resolve(ex)[0]
                           if "spmv_ell" in ex.dispatch_log else None),
    }))
"""


@pytest.fixture(scope="module")
def result(run_with_devices):
    out = run_with_devices(SCRIPT, n=4)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_dist_cg_takes_one_device_iteration_count(result):
    assert result["converged"]
    assert result["iterations"] == result["one_device_iterations"]


def test_dist_cg_result_spans_four_devices(result):
    assert result["devices"] == 4


def test_dist_cg_local_spmv_served_by_pallas(result):
    assert result["spmv_ell_space"] == "pallas"


def test_dist_cg_matches_one_device_solution(result):
    assert result["rel_diff"] <= 1e-4
