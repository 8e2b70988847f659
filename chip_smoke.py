#!/usr/bin/env python3
"""Chip smoke test: preconditioned CG through the library's entry points.

One chip (default): build the 7-point 3-D Poisson system
``gallery.poisson_3d(128)`` (2,097,152 rows, HPCG-class per-node size) as
ELL, and solve it with CG + block-Jacobi in f32 on ``default_executor()`` —
the fused ``spmv_dot_ell`` + ``axpy_norm`` loop.  The answer must pass three
checks: the true relative residual, computed on the host in float64, is at
most 1e-4; the same solve on ``make_executor("xla")`` converges within ±2
iterations; the two solutions agree to 1e-4 relative.  Every op the solve
dispatched must be served by the kernel space expected of it.

``--chips 4``: only the four-chip phase and what it is compared with — CG +
scalar Jacobi on ``DistEll`` over four row slabs of the same system, against
the same CG on one of those chips (iterations ±1, solutions 1e-4 relative).

The script needs a TPU: with none it exits non-zero and prints no result.
The last line of standard output is the JSON result,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--n-side 128]
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: kernel space each solve-path op must be served from (others: not reference)
PALLAS_OPS = ("spmv_ell", "spmv_dot_ell", "axpy_norm", "block_jacobi_apply")


class Checks:
    """Named pass/fail results, printed as they are made."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        mark = "PASS" if ok else "FAIL"
        print(f"  [{mark}] {name}" + (f": {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok

    @property
    def ok(self) -> bool:
        return not self.failed


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _true_residual(host, x, b) -> float:
    import numpy as np

    from repro.launch.dist_solve import csr_matvec_f64

    b64 = np.asarray(b, np.float64)
    return float(np.linalg.norm(b64 - csr_matvec_f64(host, x)) / np.linalg.norm(b64))


def _served_spaces(ex, checks: Checks) -> None:
    """Print the kernel space of every op in ``ex``'s dispatch log and check
    the solve-path ops ran as Pallas kernels and none fell to reference."""
    from repro.core import registry

    for op_name in sorted(ex.dispatch_log):
        space = registry.operation(op_name).resolve(ex)[0]
        print(f"    {op_name:<20} -> {space}")
        if op_name in PALLAS_OPS:
            checks(f"{op_name} served by pallas", space == "pallas", space)
        elif space == "reference":
            checks(f"{op_name} not served by reference", False, space)


def _solver(ex, stop):
    import jax

    from repro.solvers import krylov

    def solve(A, M, b):
        res = krylov.cg(A, b, M=M, stop=stop, executor=ex, strict=False)
        return res.x, res.iterations, res.converged

    return jax.jit(solve)


def _timed_cg(ex, A, kind: str, b, stop, label: str):
    """Generate ``M`` (the generator ``cg(M=kind)`` resolves to), compile and
    run one jitted CG; print setup / compile / solve seconds."""
    import jax

    from repro.precond import make_preconditioner

    t0 = time.perf_counter()
    M = jax.block_until_ready(make_preconditioner(A, kind, executor=ex))
    t1 = time.perf_counter()
    compiled = _solver(ex, stop).lower(A, M, b).compile()
    t2 = time.perf_counter()
    x, iters, conv = jax.block_until_ready(compiled(A, M, b))
    t3 = time.perf_counter()
    print(
        f"  {label}: {int(iters)} iterations, converged={bool(conv)}; "
        f"preconditioner setup {t1 - t0:.3f} s, compile {t2 - t1:.3f} s, "
        f"solve {t3 - t2:.3f} s"
    )
    return x, int(iters), bool(conv)


def one_chip_phase(n_side: int, ex, ref_ex) -> bool:
    """CG + block-Jacobi on ``poisson_3d(n_side)`` with ``ex``, checked against
    float64 on the host and against the same solve on ``ref_ex``."""
    import jax
    import jax.numpy as jnp

    from repro import sparse
    from repro.launch.dist_solve import build_system
    from repro.solvers.common import Stop

    checks = Checks()
    stop = Stop(max_iters=5000, reduction_factor=1e-6)
    t0 = time.perf_counter()
    host, _, b_host = build_system(n_side)
    A = sparse.ell_from_csr_host(*host)
    b = jax.block_until_ready(jnp.asarray(b_host))
    print(
        f"one chip: poisson_3d({n_side}) {A.shape[0]} rows, {host[1].size} nnz, "
        f"ELL k={A.max_nnz}, f32; system setup {time.perf_counter() - t0:.3f} s"
    )
    print(f"  executor {ex.name}")
    x, iters, conv = _timed_cg(ex, A, "block_jacobi", b, stop, "cg + block_jacobi")
    checks("converged", conv, f"{iters} iterations")
    res = _true_residual(host, x, b_host)
    checks("host float64 true relative residual <= 1e-4", res <= 1e-4, f"{res:.3e}")
    _served_spaces(ex, checks)

    print(f"  reference executor {ref_ex.name}")
    x_ref, iters_ref, conv_ref = _timed_cg(
        ref_ex, A, "block_jacobi", b, stop, "cg + block_jacobi"
    )
    checks("reference converged", conv_ref, f"{iters_ref} iterations")
    checks(
        "iterations within 2 of the reference",
        abs(iters - iters_ref) <= 2,
        f"{iters} vs {iters_ref}",
    )
    diff = _rel(x, x_ref)
    checks("solutions agree to 1e-4 relative", diff <= 1e-4, f"{diff:.3e}")
    return checks.ok


def four_chip_phase(n_side: int, ex, parts: int = 4) -> bool:
    """CG + Jacobi on ``DistEll`` over ``parts`` row slabs of
    ``poisson_3d(n_side)``, compared with the same CG on one chip."""
    import jax
    import jax.numpy as jnp

    from repro import sparse
    from repro.distributed import DistEll, Partition
    from repro.launch.dist_solve import build_system
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    checks = Checks()
    if not checks(f"{parts} devices", len(jax.devices()) >= parts, str(jax.devices())):
        return False
    stop = Stop(max_iters=5000, reduction_factor=1e-6)
    t0 = time.perf_counter()
    host, _, b_host = build_system(n_side)
    A = sparse.ell_from_csr_host(*host)
    n = A.shape[0]
    Ad = DistEll.from_matrix(A, Partition.uniform(n, parts))
    b = jax.block_until_ready(jnp.asarray(b_host))
    print(
        f"{parts} chips: poisson_3d({n_side}) {n} rows as DistEll over {parts} "
        f"slabs of {n // parts} rows (halo cols {min(Ad.num_halo_cols)}.."
        f"{max(Ad.num_halo_cols)}); setup {time.perf_counter() - t0:.3f} s"
    )
    print(f"  executor {ex.name}")
    times = []
    for _ in range(2):  # the first call compiles; the second reuses it
        t = time.perf_counter()
        res = krylov.cg(Ad, b, M="jacobi", stop=stop, executor=ex)
        jax.block_until_ready(res.x)
        times.append(time.perf_counter() - t)
    iters = int(res.iterations)
    print(
        f"  distributed cg + jacobi: {iters} iterations; first call (with "
        f"compile) {times[0]:.3f} s, second call {times[1]:.3f} s"
    )
    checks("distributed converged", bool(res.converged), f"{iters} iterations")
    devices = res.x.sharding.device_set
    ids = sorted(d.id for d in devices)
    checks(f"result spans {parts} devices", len(devices) == parts, str(ids))
    res_true = _true_residual(host, res.x, b_host)
    checks(
        "host float64 true relative residual <= 1e-4",
        res_true <= 1e-4,
        f"{res_true:.3e}",
    )
    _served_spaces(ex, checks)

    one_ex = type(ex)(ex.hw)
    x1, iters1, conv1 = _timed_cg(
        one_ex, A, "jacobi", b, stop, "one chip cg + jacobi"
    )
    checks("one-chip converged", conv1, f"{iters1} iterations")
    checks(
        "iterations within 1 of one chip",
        abs(iters - iters1) <= 1,
        f"{iters} vs {iters1}",
    )
    diff = _rel(res.x, x1)
    checks("solutions agree to 1e-4 relative", diff <= 1e-4, f"{diff:.3e}")
    return checks.ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-side", type=int, default=128,
                    help="grid side of the 3-D Poisson system (n_side^3 rows)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase and its comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2

    from repro.core import default_executor, make_executor
    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({warm} entries before this run)")
    try:
        if args.chips == 4:
            ok = four_chip_phase(args.n_side, default_executor())
        else:
            ok = one_chip_phase(args.n_side, default_executor(), make_executor("xla"))
        for d in devices[: args.chips]:
            stats = d.memory_stats() or {}
            print(f"peak_bytes_in_use device {d.id}: {stats.get('peak_bytes_in_use')}")
    except Exception:
        traceback.print_exc()
        return 1
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
