"""The distributed matvec's scopes and the distributed path's spans, on
forced host devices."""

import json


def test_dist_ell_names_its_halo_exchange_and_row_classes(run_with_devices):
    out = run_with_devices("""
        import json, re
        import numpy as np, jax, jax.numpy as jnp
        from repro import sparse
        from repro.distributed import DistEll, Partition
        from repro.sparse import gallery

        assert len(jax.devices()) == 4
        indptr, indices, values, shape = gallery.poisson_3d(6)
        A = sparse.ell_from_csr_host(indptr, indices, values.astype(np.float32), shape)
        Ad = DistEll.from_matrix(A, Partition.uniform(shape[0], 4))
        x = jnp.ones(shape[0], jnp.float32)
        hlo = jax.jit(lambda v: Ad.apply(v)).lower(x).compile().as_text()
        found = {}
        for line in hlo.splitlines():
            m = re.match(r'^\\s*(?:ROOT )?%\\S+ = \\S+ ([\\w\\-]+)\\(', line)
            op = re.search(r'op_name="([^"]*)"', line)
            if m and op:
                found.setdefault(m.group(1), []).append(op.group(1))
        print(json.dumps(found))
    """, n=4)
    found = json.loads(out.strip().splitlines()[-1])
    # the all-gathers of the per-shard body (the global apply gathers its
    # result outside it)
    gathers = [op for opcode, ops in found.items() if opcode.startswith("all-gather")
               for op in ops if "shard_map" in op.split("/")]
    assert gathers and all("DistEll.halo_exchange" in op.split("/") for op in gathers)
    scopes = {part for ops in found.values() for op in ops for part in op.split("/")}
    assert {"DistEll.interior", "DistEll.boundary", "DistEll.halo"} <= scopes


def test_distributed_set_up_and_solve_record_their_spans(run_with_devices):
    out = run_with_devices("""
        import numpy as np, jax.numpy as jnp
        from repro import sparse
        from repro.distributed import DistEll, Partition
        from repro.observability import trace
        from repro.solvers import krylov
        from repro.solvers.common import Stop
        from repro.sparse import gallery

        tracer = trace.enable()
        indptr, indices, values, shape = gallery.poisson_3d(4)
        A = sparse.ell_from_csr_host(indptr, indices, values, shape)
        Ad = DistEll.from_matrix(A, Partition.uniform(shape[0], 2))
        res = krylov.cg(Ad, jnp.ones(shape[0]), M="jacobi",
                        stop=Stop(max_iters=50, reduction_factor=1e-6))
        assert bool(res.converged)
        print(" ".join(ev["name"] for ev in tracer.events if ev["cat"] != "dispatch"))
    """, n=2)
    names = out.strip().splitlines()[-1].split()
    assert names[:2] == ["sparse.ell_from_csr_host", "DistEll.from_matrix"]
    assert names[2:] == ["dist_solve.precond", "dist_solve.pad", "dist_solve.run"]
