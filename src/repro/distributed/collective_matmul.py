"""Ring collective matmul — explicit compute/communication overlap for TP.

The TP MLP's second matmul produces partial sums that must be reduced across
the "model" axis.  A monolithic ``psum`` serializes compute then communication;
the ring formulation (Wang et al., "Overlap communication with dependent
computation via decomposition") splits the reduction into ``axis_size`` chunked
steps where each step's ``ppermute`` overlaps the next step's partial matmul —
XLA's async collective-permute machinery schedules them concurrently.

``ring_reduce_scatter_matmul``: computes ``y = sum_r x_r @ w_r`` reduce-
scattered over the axis (each shard ends with its output-row chunk), one
matmul + one ppermute per step.

``ring_all_gather_matmul``: computes ``y_local = x_full @ w_local`` where x is
row-sharded, gathering x chunks around the ring while accumulating partial
products — the all-gather never materializes the full x.

Both are shard_map bodies: use under ``jax.shard_map`` with the "model" axis
manual.  Correctness is asserted against the dense equivalent in
tests/distributed (8-device subprocess).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def ring_reduce_scatter_matmul(
    x: jax.Array,  # (m, k_local) — this shard's contraction slice
    w: jax.Array,  # (k_local, n) — this shard's weight slice
    axis_name: str,
) -> jax.Array:
    """Returns (m, n / axis_size): the reduce-scattered product chunk.

    Equivalent to ``psum(x @ w)[:, rank*chunk:(rank+1)*chunk]`` with the
    reduction decomposed into a ring so each ppermute overlaps the next
    partial matmul.
    """
    size = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    n = w.shape[1]
    if n % size:
        raise ValueError(f"output dim {n} not divisible by axis size {size}")
    chunk = n // size
    perm = [(i, (i + 1) % size) for i in range(size)]

    def chunk_of(i):
        # the accumulator destined for shard r sits at shard q = r + 1 + i
        # (mod size) at step i, so shard q contributes chunk r = q - 1 - i;
        # it arrives at its owner exactly on the last step
        idx = (rank - 1 - i) % size
        return jax.lax.dynamic_slice_in_dim(w, idx * chunk, chunk, axis=1)

    acc = x @ chunk_of(0)  # partial product for neighbour's chunk
    for i in range(1, size):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + x @ chunk_of(i)
    return acc  # (m, chunk) — this shard's rows of the reduced product


def ring_all_gather_matmul(
    x: jax.Array,  # (m_local, k) — row shard of x
    w: jax.Array,  # (k, n_local) — column shard of w
    axis_name: str,
) -> jax.Array:
    """Returns (m_local * size, n_local) = all_gather(x) @ w, gathered via ring.

    Each step matmuls the chunk currently held and forwards it — the full x is
    never resident; communication hides behind the running matmul.
    """
    size = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    m_local = x.shape[0]
    out = jnp.zeros((m_local * size, w.shape[1]), x.dtype)
    perm = [(i, (i + 1) % size) for i in range(size)]

    chunk_x = x
    for i in range(size):
        src = (rank - i) % size  # whose rows we currently hold
        out = jax.lax.dynamic_update_slice_in_dim(
            out, (chunk_x @ w).astype(out.dtype), src * m_local, axis=0
        )
        if i + 1 < size:
            chunk_x = jax.lax.ppermute(chunk_x, axis_name, perm)
    return out
