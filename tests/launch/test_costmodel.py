"""Cost model: exact scan trip counts (the thing XLA's analysis gets wrong)."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch import costmodel
from repro.launch.costmodel import function_cost


def test_scan_trip_counts_exact():
    d = 128
    w = jnp.ones((d, d), jnp.float32)
    x = jnp.ones((8, d), jnp.float32)

    def scanned(x, w):
        def body(c, _):
            return c @ w, None

        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    c = function_cost(scanned, x, w)
    want = 10 * 2 * 8 * d * d
    np.testing.assert_allclose(c["flops"], want, rtol=0.01)


def test_xla_cost_analysis_undercounts_loops():
    """Documents WHY the jaxpr walker exists: XLA counts the body once."""
    d = 128
    w = jnp.ones((d, d), jnp.float32)
    x = jnp.ones((8, d), jnp.float32)

    def scanned(x, w):
        def body(c, _):
            return c @ w, None

        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    compiled = jax.jit(scanned).lower(x, w).compile()
    hlo_flops = compiled.cost_analysis().get("flops", 0.0)
    one_body = 2 * 8 * d * d
    assert hlo_flops < 2 * one_body  # ~1x body, not 10x


def test_dot_flops_batched():
    a = jnp.ones((4, 16, 32), jnp.float32)
    b = jnp.ones((4, 32, 8), jnp.float32)
    c = function_cost(lambda a, b: jnp.einsum("bik,bkj->bij", a, b), a, b)
    np.testing.assert_allclose(c["flops"], 2 * 4 * 16 * 32 * 8, rtol=0.01)


def test_remat_grad_counts_recompute():
    d = 64
    w = jnp.ones((d, d), jnp.float32)
    x = jnp.ones((4, d), jnp.float32)

    def loss(w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        c, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=6)
        return jnp.sum(c)

    c_fwd = function_cost(lambda w: loss(w), w)
    c_grad = function_cost(jax.grad(loss), w)
    # grad with remat ~ fwd + recompute-fwd + 2x bwd matmuls >= 3x fwd dots
    assert c_grad["flops"] > 2.5 * c_fwd["flops"]


def test_fused_bytes_leq_unfused():
    x = jnp.ones((128, 128), jnp.float32)

    def f(x):
        return jnp.sum(jnp.tanh(x * 2.0 + 1.0))

    c = function_cost(f, x)
    assert c["fused_bytes"] <= c["bytes"]
    assert c["fused_bytes"] > 0


def test_collective_census_parser():
    from repro.launch.dryrun import collective_census, _shape_bytes

    hlo = """
      %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups=...
      %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%sum
      %rs = (f32[16,16]{1,0}, f32[16,16]{1,0}) reduce-scatter(%a, %b)
      %cp = bf16[4,4]{1,0} collective-permute-start(%z)
      %dot = f32[8,8]{1,0} dot(%p, %q)
    """
    census = collective_census(hlo)
    assert census["all-gather"]["bytes"] == 8 * 128 * 2
    assert census["all-reduce"]["bytes"] == 1024 * 4
    assert census["reduce-scatter"]["bytes"] == 2 * 16 * 16 * 4
    assert census["collective-permute"]["bytes"] == 4 * 4 * 2
    assert "dot" not in census
