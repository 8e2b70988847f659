"""Autotune sweep: measure candidate tile geometries per op, persist winners.

The launch-configuration resolver (``repro.core.tuning``) falls back to
HardwareParams-derived seeds; this sweep replaces guesses with measurements.
For every op that has a tuning spec it times each candidate geometry on a
representative shape, records the winner in the shape-bucketed autotune cache,
and persists the cache as a per-target table (JSON) that
``tuning.load_table`` / ``REPRO_TUNING_PATH`` can reload.

Run:  PYTHONPATH=src python -m benchmarks.run --autotune
      PYTHONPATH=src python -m benchmarks.autotune --target cpu_interpret \
          --out benchmarks/tuning/cpu_interpret.json

On CPU the pallas kernels run in interpret mode — the absolute times are not
hardware-representative, but the sweep is the same end-to-end machinery a TPU
run uses (candidate generation -> constrain -> VMEM filter -> measure ->
persist), which is what the portability story needs exercised.
"""

from __future__ import annotations

import argparse
import os
from typing import Callable, Dict, Optional

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import make_executor, tuning


def _np_rng():
    return np.random.default_rng(0)


# -- per-op runners -----------------------------------------------------------
# Each builder returns (shapes, run) where run(block) executes the kernel once
# with that explicit geometry (blocking).  Shapes are kept small enough for
# CPU interpret mode; on real hardware pass --full-ish shapes via the table.


def _attention_runner(ex):
    from repro.kernels.flash_attention.kernel import flash_attention

    rng = _np_rng()
    B, H, S, D = 1, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    shapes = {"S": S, "Skv": S, "D": D, "itemsize": 4}

    def run(block):
        return time_fn(
            lambda: flash_attention(
                q, k, v,
                block_q=block["block_q"], block_kv=block["block_kv"],
                interpret=ex.interpret,
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _chunked_attention_runner(ex):
    from repro.nn.attention import attention_xla_chunked

    rng = _np_rng()
    B, H, S, D = 1, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    shapes = {"S": S, "Skv": S, "D": D, "itemsize": 4}

    def run(block):
        return time_fn(
            lambda: attention_xla_chunked(q, k, v, chunk=block["chunk"]),
            warmup=1, repeats=3,
        )

    return shapes, run


def _rmsnorm_runner(ex):
    from repro.kernels.rmsnorm.kernel import rmsnorm

    rng = _np_rng()
    rows, d = 2048, 512
    x = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    shapes = {"rows": rows, "d": d, "itemsize": 4}

    def run(block):
        return time_fn(
            lambda: rmsnorm(
                x, w, block_rows=block["block_rows"], interpret=ex.interpret
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _rwkv6_runner(ex):
    from repro.kernels.rwkv6.kernel import rwkv6_scan_log
    from repro.kernels.rwkv6.xla import rwkv6_chunked_xla

    rng = _np_rng()
    B, S, H, K = 1, 128, 2, 32
    r = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    logw = jnp.asarray(-np.exp(rng.normal(-1.0, 1.0, size=(B, S, H, K))).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, K)).astype(np.float32))
    shapes = {"S": S, "K": K, "V": K, "itemsize": 4}
    pallas = ex.kernel_space == "pallas"

    def run(block):
        if pallas:
            fn = lambda: rwkv6_scan_log(
                r, k, v, logw, u, chunk=block["chunk"], interpret=ex.interpret
            )
        else:
            fn = lambda: rwkv6_chunked_xla(r, k, v, logw, u, chunk=block["chunk"])
        return time_fn(fn, warmup=1, repeats=3)

    return shapes, run


def _ssd_runner(ex):
    from repro.kernels.ssd.kernel import ssd_scan
    from repro.kernels.ssd.xla import ssd_chunked_xla

    rng = _np_rng()
    B, S, H, P, G, N = 1, 128, 2, 32, 1, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(B, S, H)))).astype(np.float32))
    A = jnp.asarray(-np.exp(rng.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    shapes = {"S": S, "N": N, "P": P, "itemsize": 4}
    pallas = ex.kernel_space == "pallas"

    def run(block):
        if pallas:
            fn = lambda: ssd_scan(
                x, dt, A, Bm, C, chunk=block["chunk"], interpret=ex.interpret
            )
        else:
            fn = lambda: ssd_chunked_xla(x, dt, A, Bm, C, chunk=block["chunk"])
        return time_fn(fn, warmup=1, repeats=3)

    return shapes, run


def _spmv_ell_runner(ex):
    from repro.kernels.spmv_ell.kernel import spmv_ell
    from repro.sparse.formats import ell_from_csr_host
    from repro.sparse.gallery import power_law_laplacian

    rng = _np_rng()
    # irregular-degree gallery graph: realistic ELL padding, unlike a
    # uniform-density random matrix
    indptr, indices, values, shape = power_law_laplacian(512, seed=0)
    A = ell_from_csr_host(indptr, indices, values, shape)
    n = shape[0]
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    shapes = {
        "m": A.values.shape[0], "k": A.values.shape[1], "n": n, "itemsize": 4
    }

    def run(block):
        return time_fn(
            lambda: spmv_ell(
                A.col_idx, A.values, x,
                block_m=block["block_m"], block_k=block["block_k"],
                interpret=ex.interpret,
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _spmv_dot_runner(ex):
    from repro.kernels.spmv_dot.kernel import spmv_dot_ell
    from repro.sparse.formats import ell_from_csr_host
    from repro.sparse.gallery import power_law_laplacian

    rng = _np_rng()
    indptr, indices, values, shape = power_law_laplacian(512, seed=0)
    A = ell_from_csr_host(indptr, indices, values, shape)
    n = shape[0]
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    shapes = {
        "m": A.values.shape[0], "k": A.values.shape[1], "n": n, "itemsize": 4
    }

    def run(block):
        return time_fn(
            lambda: spmv_dot_ell(
                A.col_idx, A.values, x, w,
                block_m=block["block_m"], block_k=block["block_k"],
                interpret=ex.interpret,
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _axpy_norm_runner(ex):
    from repro.kernels.axpy_norm.kernel import axpy_norm

    rng = _np_rng()
    n = 1 << 16
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    shapes = {"n": n, "itemsize": 4}

    def run(block):
        return time_fn(
            lambda: axpy_norm(
                0.5, x, y, block_n=block["block_n"], interpret=ex.interpret
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _spmv_sellp_runner(ex):
    from repro.kernels.spmv_sellp.kernel import spmv_sellp
    from repro.sparse.formats import sellp_from_csr_host
    from repro.sparse.gallery import convection_diffusion_2d

    rng = _np_rng()
    # nonsymmetric gallery stencil at the same 512-row scale the sweep used
    indptr, indices, values, shape = convection_diffusion_2d(23, peclet=5.0)
    A = sellp_from_csr_host(indptr, indices, values, shape)
    n = shape[0]
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    shapes = {
        "m": n, "n": n, "slice_size": A.slice_size,
        "stride_factor": A.stride_factor, "itemsize": 4,
    }

    def run(block):
        return time_fn(
            lambda: spmv_sellp(
                A.col_idx, A.values, A.slice_sets, x,
                m=n, slice_size=A.slice_size, block_cols=block["block_cols"],
                max_slice_cols=A.max_slice_cols, interpret=ex.interpret,
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _block_jacobi_runner(ex):
    from repro.kernels.block_jacobi.kernel import block_jacobi_apply

    rng = _np_rng()
    nb, bs = 512, 8
    inv = jnp.asarray(rng.normal(size=(nb, bs, bs)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(nb, bs)).astype(np.float32))
    shapes = {"nb": nb, "bs": bs, "itemsize": 4}

    def run(block):
        return time_fn(
            lambda: block_jacobi_apply(
                inv, vp, block_nb=block["block_nb"], interpret=ex.interpret
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


def _spmv_batch_ell_runner(ex):
    from repro import batch as batch_lib
    from repro.kernels.spmv_batch_ell.kernel import spmv_batch_ell

    rng = _np_rng()
    nb, n = 32, 256
    # one sparsity pattern shared across the batch (the fast path and the
    # representative batched workload); independent patterns would union
    # into a uselessly wide ELL block
    pattern = rng.random((n, n)) < 0.05
    stack = np.where(
        pattern[None], rng.normal(size=(nb, n, n)).astype(np.float32), 0.0
    )
    A = batch_lib.batch_ell_from_dense(stack)
    X = jnp.asarray(rng.normal(size=(nb, n)).astype(np.float32))
    shapes = {
        "nb": nb, "m": A.values.shape[1], "k": A.values.shape[2],
        "n": n, "itemsize": 4,
    }

    def run(block):
        return time_fn(
            lambda: spmv_batch_ell(
                A.col_idx, A.values, X,
                block_m=block["block_m"], block_k=block["block_k"],
                interpret=ex.interpret,
            ),
            warmup=1, repeats=3,
        )

    return shapes, run


#: op -> (runner builder, kernel spaces the sweep applies to)
RUNNERS: Dict[str, tuple] = {
    "nn_attention": (_attention_runner, ("pallas",)),
    "nn_attention_chunked": (_chunked_attention_runner, ("xla", "reference")),
    "nn_rmsnorm": (_rmsnorm_runner, ("pallas",)),
    "nn_rwkv6_scan": (_rwkv6_runner, ("pallas", "xla")),
    "nn_ssd_scan": (_ssd_runner, ("pallas", "xla")),
    "spmv_ell": (_spmv_ell_runner, ("pallas",)),
    "spmv_dot": (_spmv_dot_runner, ("pallas",)),
    "axpy_norm": (_axpy_norm_runner, ("pallas",)),
    "spmv_sellp": (_spmv_sellp_runner, ("pallas",)),
    "spmv_batch_ell": (_spmv_batch_ell_runner, ("pallas",)),
    "block_jacobi": (_block_jacobi_runner, ("pallas",)),
}


def run(
    target: str = "cpu_interpret",
    out: Optional[str] = None,
    ops: Optional[list] = None,
) -> str:
    """Sweep all applicable ops for ``target``; persist and return the table path."""
    ex = make_executor(target)
    hw = ex.hw
    budget = hw.vmem_limit_bytes // tuning.VMEM_HEADROOM
    if out is None:
        out = os.path.join(os.path.dirname(__file__), "tuning", f"{hw.name}.json")
    # preload the existing table so a subset sweep (--ops) refreshes only its
    # ops and re-persists the rest unchanged
    if os.path.exists(out):
        tuning.load_table(out)
    for op, (builder, spaces) in RUNNERS.items():
        if ops and op not in ops:
            continue
        if ex.kernel_space not in spaces:
            print(f"# skipped {op}: applies to {spaces}, target "
                  f"{target!r} runs the {ex.kernel_space!r} space "
                  f"(sweep it with a matching --target)")
            continue
        spec = tuning.get_spec(op)
        if spec.candidates is None:
            continue
        shapes, bench = builder(ex)
        seen, best = set(), None
        for cand in spec.candidates(hw, shapes):
            if spec.constrain is not None:
                cand = spec.constrain(hw, shapes, cand)
            key = tuple(sorted(cand.items()))
            if key in seen:
                continue
            seen.add(key)
            if spec.vmem_bytes(shapes, cand) > budget:
                continue
            secs = bench(cand)
            emit(f"autotune.{op}.{_slug(cand)}", secs * 1e6, f"target={target}")
            if best is None or secs < best[0]:
                best = (secs, cand)
        if best is not None:
            tuning.record_autotuned(op, hw.name, shapes, best[1])
            emit(f"autotune.{op}.winner.{_slug(best[1])}", best[0] * 1e6,
                 f"target={target}")
    # save everything in the cache (the preloaded file + this sweep's
    # winners): filtering to hw.name here would drop other targets' entries
    # when --out points at a shared multi-target table
    n = tuning.save_table(out)
    print(f"# persisted {n} tuned entries -> {out}")
    return out


def _slug(block: Dict[str, int]) -> str:
    return "_".join(f"{k.split('_')[-1]}{v}" for k, v in sorted(block.items()))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--target", default="cpu_interpret",
                    help="hardware target name (see repro.core.params.TARGETS)")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--ops", nargs="*", default=None, help="subset of ops")
    args = ap.parse_args()
    run(target=args.target, out=args.out, ops=args.ops)


if __name__ == "__main__":
    main()
