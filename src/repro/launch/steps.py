"""Step factories: train_step / prefill_step / decode_step + their shardings.

These are the functions the launcher jits and the dry-run lowers.  Everything
configuration-dependent is closed over (static); everything data-dependent is
an argument (traced).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.models import lm
from repro.optim.adamw import AdamWState


def make_train_step(cfg, optimizer):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True
        )(params, cfg, batch)
        params, opt_state, stats = optimizer.update(params, grads, opt_state)
        return params, opt_state, {**metrics, **stats}

    return train_step


def make_grad_accum_train_step(cfg, optimizer, num_microbatches: int):
    """Microbatched gradient accumulation via lax.scan (compute/comm overlap:
    XLA schedules microbatch i+1's compute against microbatch i's gradient
    reduction)."""

    def train_step(params, opt_state, batch):
        def split(x):
            b = x.shape[0]
            return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

        micro = {k: split(v) for k, v in batch.items()}

        def acc_fn(carry, mb):
            g_acc, loss_acc = carry
            (loss, _), grads = jax.value_and_grad(lm.loss_fn, has_aux=True)(
                params, cfg, mb
            )
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
            return (g_acc, loss_acc + loss), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, loss_sum), _ = jax.lax.scan(acc_fn, (zeros, 0.0), micro)
        grads = jax.tree_util.tree_map(lambda g: g / num_microbatches, grads)
        params, opt_state, stats = optimizer.update(params, grads, opt_state)
        stats = dict(stats)
        stats["loss"] = loss_sum / num_microbatches
        return params, opt_state, stats

    return train_step


def make_compressed_dp_train_step(cfg, optimizer, data_axis: str = "data"):
    """Explicit-DP train step with int8 error-feedback gradient compression.

    The cross-replica gradient reduction — the collective that crosses the
    slowest links (DCN between pods) at 1000-node scale — runs on an int8
    payload via :func:`repro.optim.compressed_psum`; quantization error is
    carried per replica in an error-feedback state (leading device axis,
    sharded over the data axis).

    Params/optimizer state are replicated (pure DP; compose with TP by
    nesting inside the model's sharded ops as usual).

    Returns ``train_step(params, opt_state, err_state, batch)`` and
    ``init_err_state(params, num_replicas)``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim.compression import compressed_psum

    def init_err_state(params, num_replicas: int):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((num_replicas,) + p.shape, jnp.float32), params
        )

    def train_step(params, opt_state, err_state, batch):
        def body(params, opt_state, err_stacked, batch_l):
            err_l = jax.tree_util.tree_map(lambda e: e[0], err_stacked)
            (loss, metrics), grads = jax.value_and_grad(
                lm.loss_fn, has_aux=True
            )(params, cfg, batch_l)
            grads, err_l = compressed_psum(grads, err_l, data_axis)
            loss = jax.lax.pmean(loss, data_axis)
            params, opt_state, stats = optimizer.update(params, grads, opt_state)
            err_stacked = jax.tree_util.tree_map(lambda e: e[None], err_l)
            stats = dict(stats)
            stats["loss"] = loss
            return params, opt_state, err_stacked, stats

        replicated = jax.tree_util.tree_map(lambda _: P(), params)
        opt_rep = jax.tree_util.tree_map(lambda _: P(), opt_state)
        err_specs = jax.tree_util.tree_map(lambda _: P(data_axis), err_state)
        batch_specs = {k: P(data_axis) for k in batch}
        stats_specs = {k: P() for k in
                       ("loss", "lr", "grad_norm", "param_norm")}
        return jax.shard_map(
            body,
            in_specs=(replicated, opt_rep, err_specs, batch_specs),
            out_specs=(replicated, opt_rep, err_specs, stats_specs),
            check_vma=False,  # optimizer math is replica-identical by
            # construction (same compressed grads everywhere)
        )(params, opt_state, err_state, batch)

    return train_step, init_err_state


def make_prefill_step(cfg):
    def prefill_step(params, batch, cache):
        logits, cache = lm.prefill(
            params,
            cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            cache=cache,
        )
        # serving returns the last position's logits (next-token distribution)
        return logits[:, -1, :], cache

    return prefill_step


def make_decode_step(cfg):
    def decode_step(params, batch, length, cache):
        logits, cache = lm.decode_step(
            params,
            cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            length=length,
            cache=cache,
        )
        return logits[:, -1, :], cache

    return decode_step


# =============================================================================
# shapes + shardings for a (cfg, shape, mesh) cell
# =============================================================================

def model_shapes_and_axes(cfg):
    """Abstract param shapes + logical axes without materializing anything."""
    box = {}

    def f():
        params, axes = lm.init_model(jax.random.PRNGKey(0), cfg)
        box["axes"] = axes
        return params

    shapes = jax.eval_shape(f)
    return shapes, box["axes"]


def opt_state_shapes(optimizer, param_shapes):
    return jax.eval_shape(optimizer.init, param_shapes)


def batch_struct(cfg, global_batch: int, seq_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
    toks = jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
    out = {"labels": toks}
    if cfg.frontend == "stub_embeddings":
        out["embeds"] = jax.ShapeDtypeStruct(
            (global_batch, seq_len, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    else:
        out["tokens"] = toks
    return out


def cache_struct(cfg, batch: int, s_max: int):
    return jax.eval_shape(lambda: lm.init_cache(cfg, batch, s_max))


def train_shardings(mesh, cfg, optimizer, *, zero: str = "zero1"):
    """(param_sh, opt_sh) trees for the cell."""
    shapes, axes = model_shapes_and_axes(cfg)
    p_sh = shd.param_shardings(mesh, shapes, axes, zero="fsdp" if zero == "fsdp" else "none")
    opt_shapes = opt_state_shapes(optimizer, shapes)
    m_zero = "zero1" if zero in ("zero1", "fsdp") else "none"
    mu_sh = shd.moment_shardings(mesh, opt_shapes.mu, axes, zero=m_zero)
    nu_sh = shd.moment_shardings(mesh, opt_shapes.nu, axes, zero=m_zero)
    opt_sh = AdamWState(step=shd.replicated(mesh), mu=mu_sh, nu=nu_sh)
    return shapes, axes, p_sh, opt_shapes, opt_sh
