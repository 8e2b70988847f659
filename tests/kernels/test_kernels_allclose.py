"""Per-kernel interpret-mode validation against pure-jnp oracles.

Every Pallas kernel sweeps shapes/dtypes (hypothesis + parametrize) and must
match its ref.py oracle.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp_compat import given, settings, st

from repro import sparse
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6.kernel import rwkv6_scan_log
from repro.kernels.rwkv6.ref import rwkv6_ref
from repro.kernels.rwkv6.xla import rwkv6_chunked_xla
from repro.kernels.spmv_ell.kernel import spmv_ell
from repro.kernels.spmv_ell.ref import spmv_ell_ref
from repro.kernels.spmv_sellp.kernel import spmv_sellp
from repro.kernels.spmv_sellp.ref import spmv_sellp_ref
from repro.kernels.ssd.kernel import ssd_scan
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.ssd.xla import ssd_chunked_xla


# -- rmsnorm ---------------------------------------------------------------------

@given(
    rows=st.integers(1, 200),
    d=st.sampled_from([32, 64, 128, 384]),
    block=st.sampled_from([8, 32, 128]),
    dtype=st.sampled_from([np.float32, "bfloat16"]),
)
@settings(max_examples=15)
def test_rmsnorm_sweep(rows, d, block, dtype):
    rng = np.random.default_rng(rows * d)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    x = jnp.asarray(rng.normal(size=(rows, d)), dt)
    w = jnp.asarray(rng.normal(size=(d,)), dt)
    got = rmsnorm(x, w, interpret=True, block_rows=block)
    want = rmsnorm_ref(x, w)
    atol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_rmsnorm_nd_input(rng):
    x = jnp.asarray(rng.normal(size=(2, 7, 3, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    got = rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(got, rmsnorm_ref(x, w), atol=1e-5)


# -- spmv ------------------------------------------------------------------------

@pytest.mark.parametrize("bm,bk", [(1024, 8), (2048, 16), (37, 5)])
def test_spmv_ell_blocks(rng, bm, bk):
    # 2500 rows: several row blocks of 1024 plus a padded tail
    a = rng.normal(size=(2500, 97)).astype(np.float32)
    a[rng.random(a.shape) < 0.85] = 0
    A = sparse.ell_from_dense(a)
    x = jnp.asarray(rng.normal(size=(97,)).astype(np.float32))
    got = spmv_ell(A.col_idx, A.values, x, block_m=bm, block_k=bk,
                   interpret=True)
    want = spmv_ell_ref(A.col_idx, A.values, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(want), a @ np.asarray(x), rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize(
    "bm,bk,coop", [(64, 8, True), (128, 16, False), (37, 5, True)]
)
def test_spmv_batch_ell_blocks(rng, bm, bk, coop):
    from repro import batch
    from repro.kernels.spmv_batch_ell.kernel import spmv_batch_ell as kern
    from repro.kernels.spmv_batch_ell.ref import spmv_batch_ell_ref

    nb = 6
    stack = rng.normal(size=(nb, 150, 97)).astype(np.float32)
    stack[rng.random(stack.shape) < 0.85] = 0
    A = batch.batch_ell_from_dense(stack)
    X = jnp.asarray(rng.normal(size=(nb, 97)).astype(np.float32))
    got = kern(A.col_idx, A.values, X, block_m=bm, block_k=bk,
               use_coop=coop, interpret=True)
    want = spmv_batch_ell_ref(A.col_idx, A.values, X)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(want), np.einsum("bmn,bn->bm", stack, np.asarray(X)),
        rtol=1e-3, atol=1e-4,
    )


@given(m=st.integers(1, 120), n=st.integers(1, 90), seed=st.integers(0, 99))
@settings(max_examples=10)
def test_spmv_sellp_sweep(m, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, n)).astype(np.float32)
    a[rng.random(a.shape) < 0.8] = 0
    A = sparse.sellp_from_dense(a, slice_size=8, stride_factor=8)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    got = spmv_sellp(A.col_idx, A.values, A.slice_sets, x, m=m,
                     slice_size=A.slice_size, block_cols=A.stride_factor,
                     max_slice_cols=A.max_slice_cols, interpret=True)
    want = spmv_sellp_ref(A.col_idx, A.values, A.slice_sets, x, m, A.slice_size)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- flash attention ----------------------------------------------------------------

@pytest.mark.parametrize(
    "B,Hq,Hkv,S,Skv,D,bq,bkv,causal",
    [
        (2, 4, 2, 64, 64, 32, 16, 16, True),
        (1, 3, 1, 100, 100, 16, 32, 16, True),
        (1, 2, 2, 48, 96, 32, 16, 16, True),  # Skv > S: chunked-prefill align
        (1, 2, 1, 64, 64, 32, 64, 64, False),
        (1, 2, 1, 50, 70, 32, 16, 32, False),  # padded kv, non-causal
    ],
)
def test_flash_attention_shapes(rng, B, Hq, Hkv, S, Skv, D, bq, bkv, causal):
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv,
                          interpret=True)
    want = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
    want = mha_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


# -- ssd -------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 32, 96, 64])
def test_ssd_chunks(rng, chunk):
    B, S, H, P, G, N = 2, 96, 4, 32, 2, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(B, S, H)))).astype(np.float32))
    A = jnp.asarray(-np.exp(rng.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(B, S, G, N)).astype(np.float32))
    want_y, want_h = ssd_ref(x, dt, A, Bm, C)
    got_y, got_h = ssd_scan(x, dt, A, Bm, C, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=2e-3)
    rel = np.abs(np.asarray(got_h - want_h)).max() / max(
        np.abs(np.asarray(want_h)).max(), 1.0
    )
    assert rel < 2e-3
    # the portable chunked-XLA path must agree too
    xy, xh = ssd_chunked_xla(x, dt, A, Bm, C, chunk=chunk)
    np.testing.assert_allclose(xy, want_y, atol=2e-3)


# -- rwkv6 -----------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 32, 80])
def test_rwkv6_chunks(rng, chunk):
    B, S, H, K, V = 2, 80, 3, 32, 32
    r = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, V)).astype(np.float32))
    xw = rng.normal(-1.0, 1.0, size=(B, S, H, K)).astype(np.float32)
    logw = jnp.asarray(-np.exp(xw))
    w = jnp.asarray(np.exp(-np.exp(xw.astype(np.float64))).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, K)).astype(np.float32))
    want_y, want_s = rwkv6_ref(r, k, v, w, u)
    got_y, got_s = rwkv6_scan_log(r, k, v, logw, u, chunk=chunk, interpret=True)
    scale = max(np.abs(np.asarray(want_y)).max(), 1.0)
    assert np.abs(np.asarray(got_y - want_y)).max() / scale < 2e-3
    xy, xs = rwkv6_chunked_xla(r, k, v, logw, u, chunk=chunk)
    assert np.abs(np.asarray(xy - want_y)).max() / scale < 2e-3


def test_rwkv6_extreme_decay_stability(rng):
    """w -> 0 (strong decay): the log-space ratio form must stay finite."""
    B, S, H, K = 1, 64, 2, 16
    r = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, K)).astype(np.float32))
    xw = rng.normal(2.5, 1.0, size=(B, S, H, K)).astype(np.float32)
    logw = jnp.asarray(-np.exp(xw))
    u = jnp.asarray(rng.normal(size=(H, K)).astype(np.float32))
    y, s = rwkv6_scan_log(r, k, v, logw, u, chunk=16, interpret=True)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(s)).all()
    w = jnp.asarray(np.exp(-np.exp(xw.astype(np.float64))).astype(np.float32))
    want_y, _ = rwkv6_ref(r, k, v, w, u)
    scale = max(np.abs(np.asarray(want_y)).max(), 1.0)
    assert np.abs(np.asarray(y - want_y)).max() / scale < 2e-3


def test_flash_binding_vmem_autofit(rng):
    """The launch-config resolver shrinks blocks until the set fits VMEM."""
    import dataclasses

    from repro.core import PallasInterpretExecutor, params as hw_params, tuning
    from repro.core.registry import operation

    tiny_vmem = dataclasses.replace(
        hw_params.CPU_INTERPRET, vmem_limit_bytes=1 * 1024 * 1024
    )
    ex_small = PallasInterpretExecutor(tiny_vmem)
    ex_big = PallasInterpretExecutor()
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, 64, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 1, 64, 64)).astype(np.float32))
    op = operation("nn_attention")
    out_small = op(q, k, v, executor=ex_small)
    out_big = op(q, k, v, executor=ex_big)
    np.testing.assert_allclose(
        np.asarray(out_small), np.asarray(out_big), atol=2e-5
    )
    shapes = {"S": 64, "Skv": 64, "D": 64, "itemsize": 4}
    cfg_small = tuning.resolve("nn_attention", shapes, tiny_vmem)
    cfg_big = tuning.resolve("nn_attention", shapes, ex_big.hw)
    assert cfg_small.fits_vmem
    assert cfg_small.source.endswith("+shrunk")
    assert cfg_small.vmem_bytes <= tiny_vmem.vmem_limit_bytes // tuning.VMEM_HEADROOM
    assert cfg_small["block_q"] < cfg_big["block_q"]
