"""Cooperative groups: paper §4 mask arithmetic, shuffle/ballot semantics."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp_compat import given, st

from repro.core import coop


SIZES = (2, 4, 8, 16, 32)


@pytest.mark.parametrize("size", SIZES + (64, 128))
def test_reduce_matches_segment_sum(rng, size):
    a = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
    got = coop.subgroup(a, size).sum()
    seg = np.asarray(a).reshape(4, 128 // size, size)
    want = np.broadcast_to(seg.sum(-1, keepdims=True), seg.shape).reshape(4, 128)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op,npop", [(jnp.maximum, np.max), (jnp.minimum, np.min)])
def test_reduce_minmax(rng, op, npop):
    a = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
    got = coop.subgroup(a, 8).reduce(op)
    seg = np.asarray(a).reshape(2, 8, 8)
    want = np.broadcast_to(npop(seg, -1, keepdims=True), seg.shape).reshape(2, 64)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("size", SIZES)
def test_inclusive_scan(rng, size):
    a = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
    got = coop.subgroup(a, size).inclusive_scan()
    want = np.cumsum(np.asarray(a).reshape(3, 64 // size, size), -1).reshape(3, 64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@given(
    bitmask=st.integers(0, 7),
    size=st.sampled_from([8, 16, 32]),
)
def test_shfl_xor_property(bitmask, size):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(2, 128)).astype(np.float32))
    got = coop.subgroup(a, size).shfl_xor(bitmask)
    seg = np.asarray(a).reshape(2, 128 // size, size)
    want = seg[..., np.arange(size) ^ bitmask].reshape(2, 128)
    np.testing.assert_allclose(got, want)
    # involution: applying twice restores the input
    again = coop.subgroup(got, size).shfl_xor(bitmask)
    np.testing.assert_allclose(again, a)


def test_shfl_and_shfl_down(rng):
    a = jnp.asarray(rng.normal(size=(2, 32)).astype(np.float32))
    sg = coop.subgroup(a, 8)
    got = sg.shfl(3)
    seg = np.asarray(a).reshape(2, 4, 8)
    want = np.broadcast_to(seg[..., 3:4], seg.shape).reshape(2, 32)
    np.testing.assert_allclose(got, want)
    got = sg.shfl_down(2)
    lane = np.arange(8)
    idx = np.where(lane + 2 >= 8, lane, lane + 2)
    np.testing.assert_allclose(got, seg[..., idx].reshape(2, 32))


@given(size=st.sampled_from([2, 4, 8, 16, 32]), seed=st.integers(0, 100))
def test_ballot_paper_semantics(size, seed):
    """(warp.ballot & Mask) >> LaneOffset — bit i set iff member i's pred."""
    rng = np.random.default_rng(seed)
    pred = rng.integers(0, 2, size=(128,)).astype(bool)
    sg = coop.subgroup(jnp.zeros((128,)), size, warp_size=32)
    b = np.asarray(sg.ballot(jnp.asarray(pred)))
    pr = pred.reshape(128 // size, size)
    for gidx in range(128 // size):
        expect = sum(int(pr[gidx, i]) << i for i in range(size))
        assert (b.reshape(128 // size, size)[gidx] == expect).all()
    got_any = np.asarray(sg.any(jnp.asarray(pred))).reshape(-1, size)[:, 0]
    got_all = np.asarray(sg.all(jnp.asarray(pred))).reshape(-1, size)[:, 0]
    got_cnt = np.asarray(sg.count(jnp.asarray(pred))).reshape(-1, size)[:, 0]
    np.testing.assert_array_equal(got_any, pr.any(1))
    np.testing.assert_array_equal(got_all, pr.all(1))
    np.testing.assert_array_equal(got_cnt, pr.sum(1))


def test_ballot_wavefront64_needs_x64():
    sg = coop.subgroup(jnp.zeros((128,)), 64, warp_size=64)
    if not jax.config.jax_enable_x64:
        with pytest.raises(ValueError, match="uint64"):
            sg.ballot(jnp.ones((128,), bool))


def test_ballot_wavefront64_under_x64():
    with jax.enable_x64(True):
        pred = jnp.asarray(np.tile(np.arange(64) % 3 == 0, 2))
        sg = coop.subgroup(jnp.zeros((128,)), 8, warp_size=64)
        cnt = np.asarray(sg.count(pred)).reshape(16, 8)[:, 0]
        want = np.tile((np.arange(64) % 3 == 0).reshape(8, 8).sum(1), 2)
        np.testing.assert_array_equal(cnt, want)


def test_popcnt_overloads():
    x32 = jnp.asarray([0, 1, 3, 255], jnp.uint32)
    np.testing.assert_array_equal(coop.popcnt(x32), [0, 1, 2, 8])
    with pytest.raises(TypeError):
        coop.popcnt(jnp.zeros(3, jnp.float32))


def test_thread_rank():
    sg = coop.subgroup(jnp.zeros((2, 32)), 8)
    ranks = np.asarray(sg.thread_rank())
    assert (ranks == np.tile(np.arange(8), 4)).all()


def test_subgroup_size_validation():
    with pytest.raises(ValueError):
        coop.subgroup(jnp.zeros((32,)), 3)  # not a power of two
    with pytest.raises(ValueError):
        coop.subgroup(jnp.zeros((31,)), 8).sum()  # not divisible
