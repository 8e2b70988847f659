"""Interpret-mode parity for all six kernel families across the three kernel
spaces, with block geometry resolved through the launch-config subsystem.

This is the acceptance gate for the tuning refactor: no ops.py binding
hard-codes tile sizes anymore, so dispatching the same operation through
reference / xla / pallas executors exercises the resolver end-to-end and must
produce matching numerics.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import sparse
from repro.core import (
    PallasInterpretExecutor,
    ReferenceExecutor,
    XlaExecutor,
    registry,
)
import repro.kernels  # noqa: F401 — populate the kernel spaces

EXECUTORS = (ReferenceExecutor, XlaExecutor, PallasInterpretExecutor)


def _spaces_outputs(op_name, *args):
    op = registry.operation(op_name)
    outs = {}
    for cls in EXECUTORS:
        ex = cls()
        outs[op.space_used(ex)] = op(*args, executor=ex)
    return outs


def _assert_all_match(outs, atol):
    ref = outs.pop("reference")
    for space, got in outs.items():
        ref_leaves = ref if isinstance(ref, tuple) else (ref,)
        got_leaves = got if isinstance(got, tuple) else (got,)
        for r, g in zip(ref_leaves, got_leaves):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(r, np.float32),
                atol=atol, err_msg=f"space {space} diverged",
            )


def test_attention_parity(rng):
    q = jnp.asarray(rng.normal(size=(1, 4, 48, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 48, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 48, 32)).astype(np.float32))
    outs = _spaces_outputs("nn_attention", q, k, v)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=2e-3)


def test_rmsnorm_parity(rng):
    x = jnp.asarray(rng.normal(size=(33, 129, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    outs = _spaces_outputs("nn_rmsnorm", x, w)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=1e-4)


def test_rwkv6_parity(rng):
    B, S, H, K = 1, 70, 2, 16
    r = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    logw = jnp.asarray(-np.exp(rng.normal(-1.0, 0.5, size=(B, S, H, K))).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, K)).astype(np.float32))
    outs = _spaces_outputs("nn_rwkv6_scan", r, k, v, logw, u)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=5e-3)


def test_ssd_parity(rng):
    B, S, H, P, G, N = 1, 96, 2, 16, 1, 8
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(B, S, H)))).astype(np.float32))
    A = jnp.asarray(-np.exp(rng.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    outs = _spaces_outputs("nn_ssd_scan", x, dt, A, Bm, C)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=5e-3)


@pytest.mark.parametrize("fmt", ["ell", "sellp"])
def test_spmv_parity(rng, fmt):
    n = 150
    a = rng.normal(size=(n, n)).astype(np.float32)
    a[rng.random(a.shape) < 0.85] = 0.0
    A = sparse.ell_from_dense(a) if fmt == "ell" else sparse.sellp_from_dense(a)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    outs = _spaces_outputs(f"spmv_{fmt}", A, x)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=1e-3)


def test_spmv_batch_ell_parity(rng):
    """Batched ELL SpMV: three-space parity with geometry resolved through
    the launch-config subsystem (batch axis on the outer grid axis)."""
    from repro import batch

    nb, n = 9, 120
    stack = rng.normal(size=(nb, n, n)).astype(np.float32)
    stack[rng.random(stack.shape) < 0.85] = 0.0
    A = batch.batch_ell_from_dense(stack)
    X = jnp.asarray(rng.normal(size=(nb, n)).astype(np.float32))
    outs = _spaces_outputs("spmv_batch_ell", A, X)
    assert set(outs) == {"reference", "xla", "pallas"}
    _assert_all_match(outs, atol=1e-3)


def test_spmv_batch_ell_uses_launch_config(rng):
    """The pallas binding resolves tile geometry via Executor.launch_config —
    a pinned table override must change nothing numerically but be the
    geometry the resolver hands back."""
    from repro.core import tuning

    shapes = {"nb": 8, "m": 64, "k": 16, "n": 64, "itemsize": 4}
    ex = PallasInterpretExecutor()
    base = ex.launch_config("spmv_batch_ell", shapes)
    assert base.source.startswith("table")
    assert set(base.block) == {"block_m", "block_k"}
    try:
        tuning.set_table_entry(
            "spmv_batch_ell", ex.hw.name, {"block_m": 32, "block_k": 8}
        )
        pinned = ex.launch_config("spmv_batch_ell", shapes)
        assert (pinned["block_m"], pinned["block_k"]) == (32, 8)
    finally:
        tuning._TABLE.pop(("spmv_batch_ell", ex.hw.name), None)


def test_spmv_batch_ell_vmem_fallback(rng):
    """A starved target still answers through the pallas space (xla kernel
    inside the binding) and matches the oracle."""
    import dataclasses

    from repro import batch
    from repro.core import params as hw_params

    nb, n = 4, 96
    stack = rng.normal(size=(nb, n, n)).astype(np.float32)
    stack[rng.random(stack.shape) < 0.9] = 0.0
    A = batch.batch_ell_from_dense(stack)
    X = jnp.asarray(rng.normal(size=(nb, n)).astype(np.float32))
    starved = dataclasses.replace(hw_params.CPU_INTERPRET, vmem_limit_bytes=1024)
    ex = PallasInterpretExecutor(starved)
    got = registry.operation("spmv_batch_ell")(A, X, executor=ex)
    want = registry.operation("spmv_batch_ell")(A, X, executor=ReferenceExecutor())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_spmv_vmem_fallback_serves_pallas_space(rng):
    """A starved target still answers through the pallas kernel at its
    smallest tile (x is never VMEM-resident, so no size switches kernels)
    and matches the oracle."""
    import dataclasses

    from repro.core import params as hw_params

    n = 200
    a = rng.normal(size=(n, n)).astype(np.float32)
    a[rng.random(a.shape) < 0.9] = 0.0
    A = sparse.ell_from_dense(a)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    starved = dataclasses.replace(hw_params.CPU_INTERPRET, vmem_limit_bytes=1024)
    ex = PallasInterpretExecutor(starved)
    got = registry.operation("spmv_ell")(A, x, executor=ex)
    want = registry.operation("spmv_ell")(A, x, executor=ReferenceExecutor())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
