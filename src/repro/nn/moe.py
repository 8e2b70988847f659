"""Mixture-of-Experts layer: top-k router + grouped-GEMM expert dispatch.

Two dispatch formulations, same math:

* ``sort`` (default): tokens are replicated k ways, sorted by expert id, and
  the expert SwiGLU runs as three ``jax.lax.ragged_dot`` grouped GEMMs — the
  MaxText-style sparse path.  Compiles on CPU and under GSPMD; on TPU the
  ragged dot lowers to the native grouped-matmul kernels.
* ``dense``: every expert processes every token, combined with the routing
  weights (einsum over the expert axis).  O(E/k) more FLOPs — used only as the
  smoke-test oracle for the sort path.

Expert parallelism at scale (DESIGN.md §5): expert weight arrays carry the
("expert", ...) logical axis which the sharding rules map to the "model" mesh
axis; under pjit, GSPMD turns the gather/scatter around the ragged dots into
all-to-alls across the expert shards.

Router: softmax -> top-k -> renormalize (qwen2/olmoe convention), with the
standard load-balance auxiliary loss (Switch-style fraction*prob) and router
z-loss returned as metrics.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.nn.common import ParamBuilder


def padded_experts(cfg) -> int:
    """Expert count padded for even expert-parallel sharding (qwen2: 60->64).

    Padded experts receive -inf router logits and zero ragged-dot groups —
    dead weight sharded away, never compute.
    """
    return cfg.n_experts_padded or cfg.n_experts


def moe_init(rng, cfg, *, dtype=jnp.float32):
    d, E, dff = cfg.d_model, padded_experts(cfg), cfg.d_expert
    pb = ParamBuilder(rng, dtype)
    pb.param("router", (d, cfg.n_experts), ("embed", None), std=d ** -0.5,
             dtype=jnp.float32)
    pb.param("gate", (E, d, dff), ("expert", "embed", "expert_mlp"), std=d ** -0.5)
    pb.param("up", (E, d, dff), ("expert", "embed", "expert_mlp"), std=d ** -0.5)
    pb.param("down", (E, dff, d), ("expert", "expert_mlp", "embed"), std=dff ** -0.5)
    if cfg.shared_expert_ff:
        sff = cfg.shared_expert_ff
        pb.param("sh_gate", (d, sff), ("embed", "mlp"), std=d ** -0.5)
        pb.param("sh_up", (d, sff), ("embed", "mlp"), std=d ** -0.5)
        pb.param("sh_down", (sff, d), ("mlp", "embed"), std=sff ** -0.5)
        # qwen2-moe gates the shared expert with a sigmoid scalar per token
        pb.param("sh_gate_proj", (d, 1), ("embed", None), std=d ** -0.5)
    return pb.build()


def _router(p, x2, cfg):
    """x2: (T, d) -> (weights (T, k), ids (T, k), aux_metrics)."""
    T = x2.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    logits = x2.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)  # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # Switch-style load-balance loss: E * sum_e fraction_e * mean_prob_e
    counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(0, 1))  # (E,)
    fraction = counts / jnp.maximum(T * k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(fraction * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights, ids, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}


def _experts_sort(p, x2, weights, ids, cfg):
    """Sort-based dispatch + ragged grouped GEMM."""
    T, d = x2.shape
    E, k = p["gate"].shape[0], cfg.top_k  # padded expert count

    flat_ids = ids.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_ids)  # stable
    inv = jnp.argsort(order)
    token_of = order // k  # source token per sorted slot
    xs = x2[token_of]  # (T*k, d) gathered tokens in expert order

    group_sizes = jnp.sum(
        jax.nn.one_hot(flat_ids, E, dtype=jnp.int32), axis=0
    )  # (E,)

    gate = jax.lax.ragged_dot(xs, p["gate"], group_sizes)
    up = jax.lax.ragged_dot(xs, p["up"], group_sizes)
    h = jax.nn.silu(gate) * up
    out_s = jax.lax.ragged_dot(h, p["down"], group_sizes)  # (T*k, d)

    out = out_s[inv].reshape(T, k, d)
    return jnp.sum(out * weights[..., None].astype(out.dtype), axis=1)


def _experts_dense(p, x2, weights, ids, cfg):
    """Oracle: every expert on every token, masked combine."""
    E, k = p["gate"].shape[0], cfg.top_k
    gate = jnp.einsum("td,edf->tef", x2, p["gate"])
    up = jnp.einsum("td,edf->tef", x2, p["up"])
    h = jax.nn.silu(gate) * up
    out_e = jnp.einsum("tef,efd->ted", h, p["down"])  # (T, E, d)
    combine = jnp.zeros((x2.shape[0], E), jnp.float32)
    one_hot = jax.nn.one_hot(ids, E, dtype=jnp.float32)  # (T, k, E)
    combine = jnp.sum(one_hot * weights[..., None], axis=1)  # (T, E)
    return jnp.einsum("te,ted->td", combine.astype(out_e.dtype), out_e)


# =============================================================================
# expert-parallel dispatch (shard_map): the at-scale path
#
# Layout: activations are data-sharded and model-replicated (the TP layout the
# rest of the block already uses), experts are sharded over the "model" axis.
# Because every model column holds the tokens already, dispatch needs NO
# all-to-all: each column selects the tokens routed to ITS experts into a
# fixed-capacity buffer (GShard-style capacity with drop), runs three ragged
# grouped GEMMs, scatters back, and one psum over the model axis combines the
# columns — the same reduction a TP dense MLP pays.  Capacity keeps every
# shape static; overflow tokens fall back to the shared expert / residual.
# =============================================================================


def _capacity(cfg, T: int, n_cols: int) -> int:
    c = int(cfg.moe_capacity_factor * T * cfg.top_k / max(n_cols, 1))
    return max((c + 7) // 8 * 8, 8)


def _experts_ep_body(x2, router_w, gate_l, up_l, down_l, cfg, model_axis):
    """Per-device body. x2: (T, d) local tokens; *_l: this column's experts."""
    T, d = x2.shape
    E_pad_local = gate_l.shape[0]
    m = jax.lax.axis_index(model_axis)
    n_cols = jax.lax.axis_size(model_axis)
    k = cfg.top_k

    # router (replicated weights; computed redundantly per column — cheap)
    logits = x2.astype(jnp.float32) @ router_w  # (T, E_real)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    counts = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    fraction = counts / jnp.maximum(T * k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    lb_loss = cfg.n_experts * jnp.sum(fraction * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    flat_ids = ids.reshape(-1)  # (T*k,)
    flat_w = weights.reshape(-1)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k

    lo = m * E_pad_local
    mine = (flat_ids >= lo) & (flat_ids < lo + E_pad_local)
    pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
    C = _capacity(cfg, T, n_cols)
    keep = mine & (pos < C)
    slot = jnp.where(keep, pos, C)  # C = overflow slot

    # scatter tokens + local expert ids into the fixed buffer
    buf = jnp.zeros((C + 1, d), x2.dtype).at[slot].add(
        jnp.where(keep[:, None], x2[tok], 0)
    )
    eid = jnp.zeros((C + 1,), jnp.int32).at[slot].max(
        jnp.where(keep, flat_ids - lo, 0)
    )

    # order by local expert id; empty slots carry zeros into expert 0 (no-op)
    order = jnp.argsort(eid[:C])
    xs = buf[:C][order]
    sorted_eid = eid[:C][order]
    group_sizes = jnp.sum(
        jax.nn.one_hot(sorted_eid, E_pad_local, dtype=jnp.int32), axis=0
    )

    gate = jax.lax.ragged_dot(xs, gate_l, group_sizes)
    up = jax.lax.ragged_dot(xs, up_l, group_sizes)
    h = jax.nn.silu(gate) * up
    out_s = jax.lax.ragged_dot(h, down_l, group_sizes)  # (C, d)

    inv = jnp.argsort(order)
    out_buf = jnp.concatenate([out_s[inv], jnp.zeros((1, d), out_s.dtype)], axis=0)

    contrib = out_buf[slot] * jnp.where(keep, flat_w, 0.0)[:, None].astype(out_s.dtype)
    y2 = jnp.sum(contrib.reshape(T, k, d), axis=1)  # partial: this column only
    drop_frac = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(mine), 1)
    return y2, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
                "moe_drop_frac": drop_frac}


def _experts_ep_a2a_body(x2, router_w, gate_l, up_l, down_l, cfg, model_axis):
    """all_to_all dispatch body. x2: (T_l, d) — this device's seq shard.

    Tokens stay sequence-sharded over the model axis; each device sends the
    tokens routed to remote experts through one all_to_all (fixed per-pair
    capacity), computes its local experts' ragged GEMMs on the received set,
    and a second all_to_all returns results to the owning device — no
    model-axis activation all-gather and no output psum.
    """
    T, d = x2.shape
    E_local = gate_l.shape[0]
    n_cols = jax.lax.axis_size(model_axis)
    k = cfg.top_k

    logits = x2.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    counts = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    lb_loss = cfg.n_experts * jnp.sum(
        counts / jnp.maximum(T * k, 1) * jnp.mean(probs, axis=0)
    )
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    flat_ids = ids.reshape(-1)  # (T*k,)
    flat_w = weights.reshape(-1)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    dest = flat_ids // E_local  # owning column per assignment
    local_eid = flat_ids % E_local

    # per-destination positions (running count of assignments to each column)
    dest_onehot = jax.nn.one_hot(dest, n_cols, dtype=jnp.int32)  # (T*k, ncols)
    pos = jnp.cumsum(dest_onehot, axis=0) - dest_onehot  # exclusive
    pos = jnp.sum(pos * dest_onehot, axis=1)  # (T*k,)

    # pair capacity: expected T*k/n_cols with slack (pair-level balance is
    # noisier than device-level, hence the 2x)
    C = max(int(2.0 * cfg.moe_capacity_factor * T * k / max(n_cols, 1) + 7) // 8 * 8, 8)
    keep = pos < C
    slot = jnp.where(keep, dest * C + pos, n_cols * C)  # overflow slot

    send_x = jnp.zeros((n_cols * C + 1, d), x2.dtype).at[slot].add(
        jnp.where(keep[:, None], x2[tok], 0)
    )[:-1]
    send_eid = jnp.zeros((n_cols * C + 1,), jnp.int32).at[slot].max(
        jnp.where(keep, local_eid, 0)
    )[:-1]
    send_valid = jnp.zeros((n_cols * C + 1,), jnp.bool_).at[slot].max(keep)[:-1]

    # exchange: (ncols, C, ...) -> first axis becomes source column
    recv_x = jax.lax.all_to_all(
        send_x.reshape(n_cols, C, d), model_axis, 0, 0, tiled=False
    ).reshape(n_cols * C, d)
    recv_eid = jax.lax.all_to_all(
        send_eid.reshape(n_cols, C), model_axis, 0, 0, tiled=False
    ).reshape(n_cols * C)
    recv_valid = jax.lax.all_to_all(
        send_valid.reshape(n_cols, C), model_axis, 0, 0, tiled=False
    ).reshape(n_cols * C)

    recv_eid = jnp.where(recv_valid, recv_eid, 0)  # invalid slots -> expert 0
    order = jnp.argsort(recv_eid)
    xs = recv_x[order]
    group_sizes = jnp.sum(
        jax.nn.one_hot(recv_eid[order], E_local, dtype=jnp.int32), axis=0
    )
    gate = jax.lax.ragged_dot(xs, gate_l, group_sizes)
    up = jax.lax.ragged_dot(xs, up_l, group_sizes)
    out_s = jax.lax.ragged_dot(jax.nn.silu(gate) * up, down_l, group_sizes)
    inv = jnp.argsort(order)
    out_buf = out_s[inv] * recv_valid[:, None].astype(out_s.dtype)

    # return exchange
    back = jax.lax.all_to_all(
        out_buf.reshape(n_cols, C, d), model_axis, 0, 0, tiled=False
    ).reshape(n_cols * C, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], axis=0)

    contrib = back[slot] * jnp.where(keep, flat_w, 0.0)[:, None].astype(back.dtype)
    y2 = jnp.zeros((T, d), x2.dtype).at[tok].add(contrib.astype(x2.dtype))
    drop_frac = 1.0 - jnp.sum(keep) / jnp.maximum(T * k, 1)
    return y2, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
                "moe_drop_frac": drop_frac}


def _experts_ep(p, x, cfg):
    """shard_map expert-parallel MoE. x: (B, S, d) -> (y, metrics)."""
    batch_axes, model_axis = cfg.moe_spec
    P = jax.sharding.PartitionSpec
    has_shared = "sh_gate" in p
    a2a = cfg.moe_dispatch == "a2a"

    def body(x_l, router_w, gate_l, up_l, down_l, *shared):
        B_l, S_l, d = x_l.shape
        x2 = x_l.reshape(B_l * S_l, d)
        if a2a:
            y2, metrics = _experts_ep_a2a_body(
                x2, router_w, gate_l, up_l, down_l, cfg, model_axis
            )
        else:
            y2, metrics = _experts_ep_body(
                x2, router_w, gate_l, up_l, down_l, cfg, model_axis
            )
        if has_shared:
            sh_gate_l, sh_up_l, sh_down_l, sh_gate_proj = shared
            shp = (jax.nn.silu(x2 @ sh_gate_l) * (x2 @ sh_up_l)) @ sh_down_l
            gate_sc = jax.nn.sigmoid(x2.astype(jnp.float32) @ sh_gate_proj)
            y2 = y2 + shp.astype(y2.dtype) * gate_sc.astype(y2.dtype)
        if not a2a:
            y2 = jax.lax.psum(y2, model_axis)  # combine expert columns
        metrics = {k: jax.lax.pmean(jax.lax.pmean(v, model_axis), batch_axes)
                   for k, v in metrics.items()}
        return y2.reshape(B_l, S_l, d), metrics

    # a2a: tokens stay sequence-sharded over the model axis (the SP layout);
    # gather: tokens model-replicated, experts read their local copy
    x_spec = P(batch_axes, model_axis, None) if a2a else P(batch_axes, None, None)
    in_specs = [
        x_spec,
        P(None, None),  # router replicated
        P(model_axis, None, None),  # experts sharded
        P(model_axis, None, None),
        P(model_axis, None, None),
    ]
    args = [x, p["router"], p["gate"], p["up"], p["down"]]
    if has_shared:
        if a2a:
            # shared experts run on local tokens with full weights (69 MB at
            # qwen2 scale — cheaper than reintroducing the output psum)
            in_specs += [P(None, None), P(None, None), P(None, None), P(None, None)]
        else:
            in_specs += [
                P(None, model_axis),  # shared-expert hidden sharded over model
                P(None, model_axis),
                P(model_axis, None),
                P(None, None),
            ]
        args += [p["sh_gate"], p["sh_up"], p["sh_down"], p["sh_gate_proj"]]

    out_specs = (x_spec, {
        "moe_lb_loss": P(), "moe_z_loss": P(), "moe_drop_frac": P()})
    return jax.shard_map(
        body, in_specs=tuple(in_specs), out_specs=out_specs
    )(*args)


def moe_forward(p, x: jax.Array, cfg, *, impl: str = None):
    """x: (B, S, d) -> (y, metrics).  impl: "sort" | "dense" | "ep" (default:
    "ep" when cfg.moe_spec is set, else "sort")."""
    if impl is None:
        impl = "ep" if cfg.moe_spec else "sort"
    if impl == "ep":
        return _experts_ep(p, x, cfg)

    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    weights, ids, metrics = _router(p, x2, cfg)
    if impl == "sort":
        y2 = _experts_sort(p, x2, weights, ids, cfg)
    elif impl == "dense":
        y2 = _experts_dense(p, x2, weights, ids, cfg)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")

    if "sh_gate" in p:
        sh = (jax.nn.silu(x2 @ p["sh_gate"]) * (x2 @ p["sh_up"])) @ p["sh_down"]
        sh_gate = jax.nn.sigmoid(x2.astype(jnp.float32) @ p["sh_gate_proj"])
        y2 = y2 + sh.astype(y2.dtype) * sh_gate.astype(y2.dtype)

    return y2.reshape(B, S, d), metrics
