"""Basic layers: linear, norms, rope, MLPs — all executor-dispatched where hot.

The norm goes through the registered ``nn_rmsnorm`` operation (reference / xla
/ pallas); matmuls are jnp einsums (XLA's MXU lowering is already optimal for
dense GEMM — a Pallas matmul would only re-derive it, so per DESIGN.md the
kernel space covers attention/scan/spmv hot-spots instead).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.nn.common import ParamBuilder, ones_init, zeros_init

_rmsnorm_op = registry.operation("nn_rmsnorm")


# -- linear ---------------------------------------------------------------------

def linear_init(
    rng,
    d_in: int,
    d_out: int,
    axes: Tuple[Optional[str], Optional[str]],
    *,
    dtype=jnp.float32,
    std: Optional[float] = None,
    bias: bool = False,
):
    pb = ParamBuilder(rng, dtype)
    pb.param("w", (d_in, d_out), axes, std=std if std is not None else d_in ** -0.5)
    if bias:
        pb.param("b", (d_out,), (axes[1],), init=zeros_init)
    return pb.build()


def linear(p, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# -- norms ------------------------------------------------------------------------

def rmsnorm_init(rng, d: int, *, dtype=jnp.float32):
    pb = ParamBuilder(rng, dtype)
    pb.param("scale", (d,), ("embed",), init=ones_init)
    return pb.build()


def rmsnorm(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return _rmsnorm_op(x, p["scale"], eps)


def layernorm_init(rng, d: int, *, dtype=jnp.float32):
    pb = ParamBuilder(rng, dtype)
    pb.param("scale", (d,), ("embed",), init=ones_init)
    pb.param("bias", (d,), ("embed",), init=zeros_init)
    return pb.build()


def layernorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        x.dtype
    )


def groupnorm(x: jax.Array, num_groups: int, eps: float = 1e-5) -> jax.Array:
    """Parameter-free group norm over the last axis (RWKV6 head norm)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_groups, d // num_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.reshape(*lead, d).astype(x.dtype)


# -- rotary embeddings -------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """(head_dim/2,) inverse frequencies (f32)."""
    if head_dim % 2:
        raise ValueError(f"rope head_dim must be even, got {head_dim}")
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(
    x: jax.Array,  # (B, S, H, D) or (B, S, D) for shared rope dims
    positions: jax.Array,  # (B, S) int32 absolute positions
    theta: float = 10000.0,
) -> jax.Array:
    """Llama-style interleaved-half rotary embedding."""
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B, S, D/2)
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


# -- MLPs -------------------------------------------------------------------------

def swiglu_init(rng, d: int, d_ff: int, *, dtype=jnp.float32):
    pb = ParamBuilder(rng, dtype)
    pb.param("gate", (d, d_ff), ("embed", "mlp"), std=d ** -0.5)
    pb.param("up", (d, d_ff), ("embed", "mlp"), std=d ** -0.5)
    pb.param("down", (d_ff, d), ("mlp", "embed"), std=d_ff ** -0.5)
    return pb.build()


def swiglu(p, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def gelu_mlp_init(rng, d: int, d_ff: int, *, dtype=jnp.float32, bias: bool = True):
    pb = ParamBuilder(rng, dtype)
    pb.param("up", (d, d_ff), ("embed", "mlp"), std=d ** -0.5)
    pb.param("down", (d_ff, d), ("mlp", "embed"), std=d_ff ** -0.5)
    if bias:
        pb.param("up_b", (d_ff,), ("mlp",), init=zeros_init)
        pb.param("down_b", (d,), ("embed",), init=zeros_init)
    return pb.build()


def gelu_mlp(p, x: jax.Array) -> jax.Array:
    h = x @ p["up"]
    if "up_b" in p:
        h = h + p["up_b"]
    h = jax.nn.gelu(h)
    y = h @ p["down"]
    if "down_b" in p:
        y = y + p["down_b"]
    return y


# -- embedding ----------------------------------------------------------------------

def embedding_init(rng, vocab: int, d: int, *, dtype=jnp.float32, std=0.02):
    pb = ParamBuilder(rng, dtype)
    pb.param("table", (vocab, d), ("vocab", "embed"), std=std)
    return pb.build()


def embed(p, tokens: jax.Array) -> jax.Array:
    return p["table"][tokens]


def unembed(p, h: jax.Array) -> jax.Array:
    """logits = h @ table^T (used for tied embeddings and LM heads)."""
    return h @ p["table"].T
