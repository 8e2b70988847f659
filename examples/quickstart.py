"""Quickstart: the executor model end-to-end in five minutes.

Demonstrates the paper's core idea on three payloads:
  1. sparse solve (Ginkgo's own domain): one CG source, three executors;
  2. the LinOp hierarchy: shifted systems, matrix-free operators,
     solver-as-preconditioner, and mixed-precision iterative refinement —
     all through one ``apply`` interface;
  3. an LM forward (the framework built on the same design): one model,
     three executors, identical logits.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro import solvers, sparse
from repro.core import (
    MatrixFreeOp,
    PallasInterpretExecutor,
    ReferenceExecutor,
    ScaledIdentity,
    Sum,
    XlaExecutor,
    use_executor,
)
from repro.configs import get_smoke_config
from repro.models import lm


def sparse_demo():
    print("=== 1. Krylov solve: one algorithm, three executors ===")
    n = 128
    a = np.zeros((n, n), np.float32)
    for i in range(n):
        a[i, i] = 4.0
        if i:
            a[i, i - 1] = a[i - 1, i] = -1.0
    xstar = np.linspace(-1, 1, n).astype(np.float32)
    b = jnp.asarray(a @ xstar)

    # SELL-P: the paper's GPU throughput format, TPU-adapted (8-row slices)
    A = sparse.sellp_from_dense(a)
    for ex in (ReferenceExecutor(), XlaExecutor(), PallasInterpretExecutor()):
        with use_executor(ex):
            res = solvers.cg(A, b, stop=solvers.Stop(max_iters=300, reduction_factor=1e-6))
        err = float(jnp.abs(res.x - xstar).max())
        print(f"  {ex.name:40s} iters={int(res.iterations):3d} "
              f"resnorm={float(res.residual_norm):.2e} err={err:.2e}")


def linop_demo():
    print("=== 2. LinOp hierarchy: compose, refine, precondition ===")
    n = 128
    a = np.zeros((n, n), np.float32)
    for i in range(n):
        a[i, i] = 4.0
        if i:
            a[i, i - 1] = a[i - 1, i] = -1.0
    A = sparse.csr_from_dense(a)
    xstar = np.linspace(-1, 1, n).astype(np.float32)

    with use_executor(XlaExecutor()):
        # shifted system A + 0.5 I without touching A's storage
        sigma = 0.5
        shifted = Sum(A, ScaledIdentity(sigma, n))
        b = jnp.asarray((a + sigma * np.eye(n, dtype=np.float32)) @ xstar)
        res = solvers.cg(shifted, b, stop=solvers.Stop(max_iters=300,
                                                       reduction_factor=1e-6))
        print(f"  shifted  A+{sigma}I   iters={int(res.iterations):3d} "
              f"err={float(jnp.abs(res.x - xstar).max()):.2e}")

        # the same stencil matrix-free: no stored matrix at all
        def stencil(v):
            return 4.0 * v - jnp.pad(v[1:], (0, 1)) - jnp.pad(v[:-1], (1, 0))

        b2 = jnp.asarray(a @ xstar)
        res = solvers.cg(MatrixFreeOp(stencil, shape=(n, n), dtype=jnp.float32),
                         b2, stop=solvers.Stop(max_iters=300,
                                               reduction_factor=1e-6))
        print(f"  matrix-free       iters={int(res.iterations):3d} "
              f"err={float(jnp.abs(res.x - xstar).max()):.2e}")

        # a generated solver IS a LinOp: GMRES preconditions CG (a
        # tolerance-stopped inner solve is a variable preconditioner — on
        # ill-conditioned systems use fcg as the outer method instead)
        inner = solvers.GmresSolver(
            A, restart=8, stop=solvers.Stop(max_iters=8, reduction_factor=1e-2))
        res = solvers.cg(A, b2, M=inner,
                         stop=solvers.Stop(max_iters=100, reduction_factor=1e-6))
        print(f"  cg + gmres inner  iters={int(res.iterations):3d} "
              f"err={float(jnp.abs(res.x - xstar).max()):.2e}")

        # mixed-precision IR: f32 inner CG under an f64 outer residual
        with jax.enable_x64(True):
            A64 = sparse.csr_from_dense(a.astype(np.float64))
            b64 = jnp.asarray(a.astype(np.float64) @ np.linspace(-1, 1, n))
            res = solvers.mixed_precision_ir(
                A64, b64, stop=solvers.Stop(max_iters=50,
                                            reduction_factor=1e-12))
            print(f"  mixed-prec IR     sweeps={int(res.iterations):2d} "
                  f"resnorm={float(res.residual_norm):.2e} "
                  f"(f32 inner, f64 outer)")


def lm_demo():
    print("=== 3. LM forward: same model code, three executors ===")
    cfg = get_smoke_config("granite_8b")
    params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)), jnp.int32
    )
    outs = {}
    for ex in (ReferenceExecutor(), XlaExecutor(), PallasInterpretExecutor()):
        with use_executor(ex):
            logits, _ = lm.forward(params, cfg, tokens=tokens)
        outs[ex.name] = np.asarray(logits)
        print(f"  {ex.name:40s} logits[0,0,:3] = {np.asarray(logits)[0,0,:3]}")
    names = list(outs)
    spread = max(
        np.abs(outs[a] - outs[names[0]]).max() for a in names[1:]
    )
    print(f"  max cross-executor deviation: {spread:.2e}")


if __name__ == "__main__":
    sparse_demo()
    linop_demo()
    lm_demo()
