"""Logical-axis -> mesh-axis sharding rules (DP / TP / EP / ZeRO).

Models annotate parameters with *logical* axes ("embed", "mlp", "heads",
"vocab", "expert", ...); this module turns those into ``NamedSharding``s for a
concrete mesh.  The rules:

* tensor-parallel ("model" mesh axis): mlp hidden, attention heads, kv heads,
  vocab, experts — first annotated dim that divides evenly gets the axis;
* data-parallel: dims annotated "batch" shard over ("pod", "data");
* ZeRO-1: optimizer moments additionally shard a large replicated dim over
  "data" (params stay replicated across data; the update induces the ZeRO-1
  reduce-scatter/all-gather pair);
* FSDP mode (``zero="fsdp"``): parameters themselves shard "embed" over
  "data" — a §Perf lever for memory-bound cells.

Every assignment is divisibility-checked; non-divisible dims fall back to
replication (e.g. minicpm3's vocab 73448 on a 16-wide model axis).

Axes trees are arbitrary pytrees whose leaves are tuples of logical-axis names
(or None); the walkers below pair them with shape trees structurally.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axes eligible for the tensor-parallel mesh axis, in priority order;
# "kv_seq" is the sequence-parallel fallback for KV caches whose head count
# does not divide the model axis (e.g. granite kv=8 on a 16-wide axis)
MODEL_AXES = ("expert", "mlp", "heads", "kv_heads", "kv_seq", "vocab")
# logical axes eligible for ZeRO sharding of moments / FSDP of params
ZERO_AXES = ("embed", "expert_mlp", "mlp", "heads", "vocab")


# -- padded-shard reduction hygiene --------------------------------------------
#
# Distributed vectors are padded to a uniform per-shard length (``Lmax``) so
# every shard has the same shape under ``shard_map``; the padding slots MUST
# be excluded from any cross-shard reduction (``psum`` dot / norm), or a
# ragged partition double-counts whatever happens to sit in them — the
# classic padded-shard bug.  The distributed BLAS layer routes every
# reduction operand through :func:`zero_shard_padding` so a reduction is
# correct even when padding slots hold garbage (e.g. after an operator that
# writes the full padded shard).


def shard_pad_mask(part_sizes: Sequence[int], max_size: int) -> np.ndarray:
    """(P, max_size) bool mask — True on real slots, False on padding."""
    sizes = np.asarray(part_sizes, np.int64)
    if max_size < (int(sizes.max()) if sizes.size else 0):
        raise ValueError(
            f"max_size {max_size} smaller than largest part {sizes.max()}"
        )
    return np.arange(max_size, dtype=np.int64)[None, :] < sizes[:, None]


def zero_shard_padding(x: jax.Array, mask) -> jax.Array:
    """Zero the padding slots of a (possibly poisoned) padded shard.

    ``mask`` is this shard's slice of :func:`shard_pad_mask` (bool,
    broadcastable against ``x`` on the trailing shard axis); ``None`` means
    "no padding" and returns ``x`` unchanged.
    """
    if mask is None:
        return x
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


def _is_axes_leaf(x) -> bool:
    return x is None or (
        isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)
    )


def _mesh_axis_size(mesh: Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel mesh axes ("pod","data") or ("data",)."""
    return tuple(n for n in ("pod", "data") if n in mesh.shape)


def spec_for_leaf(
    shape: Sequence[int],
    axes: Optional[Tuple[Optional[str], ...]],
    mesh: Mesh,
    *,
    zero: str = "none",  # "none" | "zero1" | "fsdp"
) -> P:
    if axes is None:
        return P()
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} rank != shape {shape}")
    assign: list = [None] * len(shape)

    daxes = data_axes(mesh)
    dsize = _mesh_axis_size(mesh, daxes) if daxes else 1
    model_size = mesh.shape.get("model", 1)
    model_used = False
    data_used = False

    # 0) batch dims -> data axes
    for i, ax in enumerate(axes):
        if ax == "batch" and dsize > 1 and shape[i] % dsize == 0:
            assign[i] = daxes if len(daxes) > 1 else daxes[0]
            data_used = True
            break

    # 1) tensor parallel: highest-priority eligible divisible dim
    if model_size > 1:
        for logical in MODEL_AXES:
            if model_used:
                break
            for i, ax in enumerate(axes):
                if ax == logical and assign[i] is None and shape[i] % model_size == 0:
                    assign[i] = "model"
                    model_used = True
                    break

    # 2) ZeRO/FSDP: shard one more big dim over the data axes
    if zero in ("zero1", "fsdp") and dsize > 1 and not data_used:
        for logical in ZERO_AXES:
            placed = False
            for i, ax in enumerate(axes):
                if ax == logical and assign[i] is None and shape[i] % dsize == 0:
                    assign[i] = daxes if len(daxes) > 1 else daxes[0]
                    placed = True
                    break
            if placed:
                break
    return P(*assign)


def _walk(mesh: Mesh, shapes, axes_tree, *, zero: str):
    flat_axes, treedef = jax.tree_util.tree_flatten(axes_tree, is_leaf=_is_axes_leaf)
    flat_shapes = treedef.flatten_up_to(shapes)
    out = [
        NamedSharding(mesh, spec_for_leaf(s.shape, a, mesh, zero=zero))
        for s, a in zip(flat_shapes, flat_axes)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def param_shardings(mesh: Mesh, shapes, axes_tree, *, zero: str = "none"):
    """shapes: pytree of ShapeDtypeStruct (eval_shape); axes_tree: logical axes."""
    return _walk(mesh, shapes, axes_tree, zero=zero)


def moment_shardings(mesh: Mesh, shapes, axes_tree, *, zero: str = "zero1"):
    """Optimizer-moment shardings (ZeRO-1 by default)."""
    return _walk(mesh, shapes, axes_tree, zero=zero)


def cache_shardings(mesh: Mesh, shapes, axes_tree):
    return _walk(mesh, shapes, axes_tree, zero="none")


def batch_spec(mesh: Mesh, batch_size: int, extra_dims: int = 1) -> P:
    """Shard the leading batch dim over as many data axes as divide it."""
    daxes = data_axes(mesh)
    full = _mesh_axis_size(mesh, daxes) if daxes else 1
    if daxes and full > 1 and batch_size % full == 0:
        lead = daxes if len(daxes) > 1 else daxes[0]
        return P(lead, *([None] * extra_dims))
    if "data" in mesh.shape and mesh.shape["data"] > 1 and batch_size % mesh.shape["data"] == 0:
        return P("data", *([None] * extra_dims))
    return P(*([None] * (extra_dims + 1)))


def batch_shardings(mesh: Mesh, batch: Dict) -> Dict:
    """NamedShardings for a data batch dict ({tokens|embeds, labels})."""
    return {
        k: NamedSharding(mesh, batch_spec(mesh, v.shape[0], v.ndim - 1))
        for k, v in batch.items()
    }


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def tree_replicated(mesh: Mesh, tree):
    return jax.tree_util.tree_map(lambda _: replicated(mesh), tree)
