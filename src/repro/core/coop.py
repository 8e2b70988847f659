"""Cooperative groups — Ginkgo §4, adapted from warp shuffles to TPU lane tiles.

The paper implements subwarp-granularity ``shfl_xor`` / ``ballot`` / ``any`` /
``all`` on top of full-warp primitives with computed masks::

    Size       = given subwarp size
    Rank       = tid % Size
    LaneOffset = floor(tid % warpsize / Size) * Size
    Mask       = ~0 >> (warpsize - Size) << LaneOffset

    subwarp.shfl_xor(data, bm) = warp.shfl_xor(data, bm, Size)
    subwarp.ballot(pred)       = (warp.ballot(pred) & Mask) >> LaneOffset
    subwarp.any(pred)          = (warp.ballot(pred) & Mask) != 0
    subwarp.all(pred)          = (warp.ballot(pred) & Mask) == Mask

TPU adaptation (see DESIGN.md §2): there are no warp shuffles on a TPU.  The VPU
operates on (8, 128) vector registers, and cross-lane exchange is expressed as
shape manipulation that the Mosaic compiler keeps in registers.  What *does*
transfer is the interface and the granularity parameterization: a "warp" is a
contiguous segment of ``warp_size`` lanes of the last axis, a subgroup is a
``size``-lane segment inside it, and the paper's Rank/LaneOffset/Mask arithmetic
is reproduced bit-for-bit for the ballot-style predicate ops (including the
uint32/uint64 ``lane_mask_type`` distinction and the ``popcnt`` overloads).

Implementation notes for Pallas compatibility:

* every index computation uses ``lax.broadcasted_iota`` (>= 2D on the real
  Mosaic backend; kernels may not capture array constants, so no host-side
  ``np.arange`` tables);
* all ops are pure jnp/lax, usable inside Pallas kernel bodies (interpret or
  compiled) and in plain XLA code — one source, many backends, which is the
  point of the paper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "lane_mask_type",
    "lane_mask_bits",
    "popcnt",
    "subgroup",
    "SubgroupView",
]


def lane_mask_type(warp_size: int):
    """Paper: architecture-agnostic (unsigned) integer type for a lane mask.

    32-bit warps (CUDA) -> uint32; 64-bit wavefronts (AMD) -> uint64.
    """
    if warp_size <= 32:
        return jnp.uint32
    if warp_size <= 64:
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "64-lane warp ballots need uint64 lane masks; enable x64 "
                "(e.g. `with jax.enable_x64(True):`) — the paper's "
                "AMD wavefront-64 case maps to this configuration"
            )
        return jnp.uint64
    raise ValueError(f"warp_size {warp_size} exceeds 64-bit lane masks")


def lane_mask_bits(warp_size: int) -> int:
    return 32 if warp_size <= 32 else 64


def popcnt(x: jax.Array) -> jax.Array:
    """Paper: single ``popcnt`` with overloads for 32- and 64-bit integers."""
    if x.dtype not in (jnp.uint32, jnp.uint64, jnp.int32, jnp.int64):
        raise TypeError(f"popcnt expects a 32/64-bit integer array, got {x.dtype}")
    return jax.lax.population_count(x)


def _segment(x: jax.Array, size: int) -> jax.Array:
    """Reshape the last axis (..., L) -> (..., L//size, size)."""
    L = x.shape[-1]
    if L % size:
        raise ValueError(f"last axis {L} not divisible by subgroup size {size}")
    return x.reshape(*x.shape[:-1], L // size, size)


def _unsegment(x: jax.Array) -> jax.Array:
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def _lane_iota(shape) -> jax.Array:
    """int32 iota along the last axis, broadcast to ``shape`` (Mosaic-safe)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _take_last(x: jax.Array, idx: jax.Array) -> jax.Array:
    """take_along_axis over the last axis (idx broadcast to x's shape)."""
    return jnp.take_along_axis(x, idx, axis=-1)


class SubgroupView:
    """A subgroup-of-the-lane-axis view of an array — ``gko::group::tiled_partition``.

    ``x`` has its last axis interpreted as lanes; the view partitions those lanes
    into contiguous subgroups of ``size`` (paper: "we always use subwarps with
    contiguous threads").  All ops return arrays of x's full shape, with the
    subgroup-collective result broadcast to every member lane — matching the
    shuffle-based semantics where every thread ends up holding the value.
    """

    def __init__(self, x: jax.Array, size: int, warp_size: int = 32):
        if size & (size - 1):
            raise ValueError(f"subgroup size must be a power of two, got {size}")
        # Shuffle/reduce subgroups may exceed the warp (they are just lane
        # segments); the ballot ops below additionally require size <= warp
        # (checked there) since the paper's mask arithmetic lives inside warps.
        if warp_size % size and size % warp_size:
            raise ValueError(
                f"subgroup size {size} incompatible with warp_size {warp_size}"
            )
        self.data = x
        self.size = size
        self.warp_size = warp_size

    # -- identity (paper: thread_rank / size) ----------------------------------
    def thread_rank(self) -> jax.Array:
        """Rank = tid % Size, broadcast over x's shape."""
        return _lane_iota(self.data.shape) % self.size

    # -- shuffles ---------------------------------------------------------------
    def shfl_xor(self, bitmask: int) -> jax.Array:
        """subwarp.shfl_xor(data, bm): lane r receives data from lane r ^ bm."""
        if not 0 <= bitmask < self.size:
            raise ValueError(f"bitmask {bitmask} out of range for size {self.size}")
        seg = _segment(self.data, self.size)
        idx = _lane_iota(seg.shape) ^ bitmask
        return _unsegment(_take_last(seg, idx))

    def shfl(self, src_lane: int) -> jax.Array:
        """subwarp.shfl(data, lane): every lane receives lane ``src_lane``'s value."""
        seg = _segment(self.data, self.size)
        idx = jnp.full_like(_lane_iota(seg.shape), src_lane)
        return _unsegment(_take_last(seg, idx))

    def shfl_down(self, delta: int) -> jax.Array:
        """Lane r receives from lane r+delta; out-of-range lanes keep their own
        value (CUDA semantics)."""
        seg = _segment(self.data, self.size)
        lane = _lane_iota(seg.shape)
        idx = jnp.where(lane + delta >= self.size, lane, lane + delta)
        return _unsegment(_take_last(seg, idx))

    # -- reductions (built from shfl_xor exactly like the paper's Listing 2) ----
    def reduce(self, op=jnp.add) -> jax.Array:
        """Butterfly all-reduce within the subgroup; every lane gets the result.

        Implemented as the log2(size) shfl_xor butterfly from the paper's
        DPC++ Listing 2 — the same data movement a shuffle reduction performs,
        expressed as lane permutations the vector unit can fuse.
        """
        out = self.data
        bitmask = 1
        while bitmask < self.size:
            seg = _segment(out, self.size)
            idx = _lane_iota(seg.shape) ^ bitmask
            out = _unsegment(op(seg, _take_last(seg, idx)))
            bitmask <<= 1
        return out

    def sum(self) -> jax.Array:
        return self.reduce(jnp.add)

    def max(self) -> jax.Array:
        return self.reduce(jnp.maximum)

    def min(self) -> jax.Array:
        return self.reduce(jnp.minimum)

    def inclusive_scan(self, op=jnp.add) -> jax.Array:
        """Hillis-Steele inclusive scan within each subgroup (shfl_up based)."""
        seg = _segment(self.data, self.size)
        out = seg
        lane = _lane_iota(seg.shape)
        delta = 1
        while delta < self.size:
            src = jnp.maximum(lane - delta, 0)
            shifted = _take_last(out, src)
            out = jnp.where(lane >= delta, op(out, shifted), out)
            delta <<= 1
        return _unsegment(out)

    # -- ballots (paper's mask arithmetic, bit-for-bit) --------------------------
    def _warp_segment(self, x: jax.Array) -> jax.Array:
        """Reshape lanes into (..., warps, warp_size)."""
        L = x.shape[-1]
        if L % self.warp_size:
            raise ValueError(
                f"last axis {L} not divisible by warp_size {self.warp_size}"
            )
        return x.reshape(*x.shape[:-1], L // self.warp_size, self.warp_size)

    def _full_warp_ballot(self, pred: jax.Array) -> jax.Array:
        """warp.ballot: pack warp_size predicate bits into one integer per warp,
        broadcast back to every lane of the warp."""
        mt = lane_mask_type(self.warp_size)
        w = self._warp_segment(pred).astype(mt)
        weights = jnp.left_shift(
            jnp.ones((), mt), _lane_iota(w.shape).astype(mt)
        )
        packed = jnp.sum(w * weights, axis=-1, keepdims=True, dtype=mt)
        return _unsegment(jnp.broadcast_to(packed, w.shape))

    def _mask_and_offset(self, shape):
        """Paper: LaneOffset = floor(tid % warpsize / Size) * Size;
        Mask = ~0 >> (warpsize - Size) << LaneOffset."""
        if self.size > self.warp_size:
            raise ValueError(
                f"ballot ops need subgroup size ({self.size}) <= warp_size "
                f"({self.warp_size}) — the paper's masks live inside one warp"
            )
        mt = lane_mask_type(self.warp_size)
        bits = lane_mask_bits(self.warp_size)
        tid = _lane_iota(shape) % self.warp_size
        lane_offset = ((tid // self.size) * self.size).astype(mt)
        full = jnp.full((), (1 << bits) - 1 if bits < 64 else 0xFFFFFFFFFFFFFFFF, mt)
        mask = (full >> jnp.asarray(self.warp_size - self.size, mt)) << lane_offset
        return mask, lane_offset

    def ballot(self, pred: jax.Array) -> jax.Array:
        """subwarp.ballot(pred) = (warp.ballot(pred) & Mask) >> LaneOffset."""
        mask, lane_offset = self._mask_and_offset(pred.shape)
        warp = self._full_warp_ballot(pred)
        return (warp & mask) >> lane_offset

    def any(self, pred: jax.Array) -> jax.Array:
        """subwarp.any(pred) = (warp.ballot(pred) & Mask) != 0."""
        mask, _ = self._mask_and_offset(pred.shape)
        warp = self._full_warp_ballot(pred)
        return (warp & mask) != 0

    def all(self, pred: jax.Array) -> jax.Array:
        """subwarp.all(pred) = (warp.ballot(pred) & Mask) == Mask."""
        mask, _ = self._mask_and_offset(pred.shape)
        warp = self._full_warp_ballot(pred)
        return (warp & mask) == mask

    def count(self, pred: jax.Array) -> jax.Array:
        """popcnt(subwarp.ballot(pred)) — the paper's ballot+popcount idiom."""
        return popcnt(self.ballot(pred))


def subgroup(x: jax.Array, size: int, warp_size: int = 32) -> SubgroupView:
    """``gko::group::tiled_partition<size>(warp)`` analogue."""
    return SubgroupView(x, size, warp_size)
