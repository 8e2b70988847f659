"""The benchmark's own operator, matvec, pool and control against the
program's generator and against each other."""

import numpy as np
import pytest

from chipbench import check, workload
from chipbench.preconds import block_jacobi
from chipbench.systems import poisson7


@pytest.mark.parametrize("n_side", [1, 2, 3, 5, 8])
def test_generator_equals_the_gallery(n_side):
    from repro.sparse import gallery

    indptr, indices, values, shape = gallery.poisson_3d(n_side)
    s = poisson7.host_csr(n_side)
    assert shape == (s.n, s.n)
    np.testing.assert_array_equal(s.indptr, indptr)
    np.testing.assert_array_equal(s.indices, indices)
    np.testing.assert_array_equal(s.values, values)
    assert s.values.dtype == values.dtype == np.float32
    np.testing.assert_array_equal(s.indices[s.diag_pos], np.arange(s.n))


def test_shifted_values_move_the_diagonal_only():
    s = poisson7.host_csr(4)
    v = s.shifted_values(0.1)
    assert np.all(v[s.diag_pos] == np.float32(6.1))
    off = np.ones(s.nnz, bool)
    off[s.diag_pos] = False
    np.testing.assert_array_equal(v[off], s.values[off])
    assert s.shifted_values(0.0) is s.values


def test_matvec_equals_the_programs():
    from repro.launch.dist_solve import csr_matvec_f64

    s = poisson7.host_csr(6)
    x = np.random.default_rng(0).standard_normal(s.n)
    host = (s.indptr, s.indices, s.values, (s.n, s.n))
    np.testing.assert_array_equal(
        check.csr_matvec_f64(s.indptr, s.indices, s.values, x), csr_matvec_f64(host, x))


@pytest.mark.parametrize("shift", [0.0, 0.1])
def test_device_operator_equals_the_csr(shift):
    s = poisson7.host_csr(6)
    x = np.random.default_rng(1).standard_normal(s.n)
    y = poisson7.device_operator({"n_side": 6}, shift)(np.asarray(x, np.float32))
    ref = check.csr_matvec_f64(s.indptr, s.indices, s.shifted_values(shift), x)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", range(poisson7.IMAGES))
def test_images_commute_with_the_operator(k):
    s = poisson7.host_csr(4)
    x = np.random.default_rng(2).standard_normal(s.n)

    def A(v):
        return check.csr_matvec_f64(s.indptr, s.indices, s.values, v)

    np.testing.assert_allclose(A(s.image(x, k)), s.image(A(x), k), atol=1e-12)


def test_pool_is_drawn_from_the_seed():
    s = poisson7.host_csr(8)
    mix = {"operator": "per_request", "diag_shift_dt": [10, 30, 100], "pool": 3,
           "requests_per_round": 3}
    a = workload.make_pool(s, mix, 2 ** 40 + 7)
    b = workload.make_pool(s, mix, 2 ** 40 + 7)
    assert [r.shift for r in a] == pytest.approx([0.1, 1 / 30, 0.01])
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.b, rb.b)
        assert ra.b.dtype == np.float32
    others = [workload.make_pool(s, mix, seed) for seed in range(8)]
    assert len({o[0].b.tobytes() for o in others}) > 1
    # the same work for every seed: the same operators, and right-hand sides
    # of the same norm
    for o in others:
        for ra, rb in zip(a, o):
            np.testing.assert_array_equal(ra.values, rb.values)
            assert np.linalg.norm(rb.b) == pytest.approx(np.linalg.norm(ra.b), rel=1e-6)


def test_readings_draw_x_star_from_the_seed():
    """The readings behind the limits see a new right-hand side per seed,
    not an image of a fixed one."""
    s = poisson7.host_csr(8)
    mix = {"operator": "fixed", "diag_shift_dt": [], "pool": 1, "requests_per_round": 1}
    pools = [workload.make_pool(s, mix, seed, xstar="seed") for seed in (5, 6, 2 ** 40)]
    norms = {float(np.linalg.norm(p[0].b)) for p in pools}
    assert len(norms) == 3
    again = workload.make_pool(s, mix, 2 ** 40, xstar="seed")
    np.testing.assert_array_equal(again[0].b, pools[2][0].b)
    with pytest.raises(ValueError):
        workload.make_pool(s, mix, 5, xstar="fixed")


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_the_pool_is_in_the_stated_dtype(dtype):
    s = poisson7.host_csr(4)
    mix = {"operator": "fixed", "diag_shift_dt": [], "pool": 1, "requests_per_round": 1}
    (req,) = workload.make_pool(s, mix, -3, dtype)
    assert req.b.dtype == req.values.dtype == np.dtype(dtype)


def test_block_inverses_invert_the_diagonal_blocks():
    s = poisson7.host_csr(4)
    inv = block_jacobi.block_inverses(s.indptr, s.indices, s.values, s.n, 8)
    dense = np.zeros((s.n, s.n))
    rows = np.repeat(np.arange(s.n), np.diff(s.indptr))
    dense[rows, s.indices] = s.values
    for b in range(s.n // 8):
        block = dense[8 * b:8 * b + 8, 8 * b:8 * b + 8]
        np.testing.assert_allclose(inv[b] @ block, np.eye(8), atol=1e-12)


def test_judge_takes_the_worst_answer_and_fails_a_nan():
    limits = {"resid": 1e-4, "resid_inf": 1e-3}
    good = {"resid": 1e-6, "resid_inf": 2e-6}
    correct, failed, worst = check.judge([good, {"resid": 2e-6, "resid_inf": 1e-6}], limits)
    assert correct and failed == 0
    assert worst == {"resid": {"value": 2e-6, "limit": 1e-4},
                     "resid_inf": {"value": 2e-6, "limit": 1e-3}}
    correct, failed, worst = check.judge([good, {"resid": float("nan"), "resid_inf": 0.0}],
                                         limits)
    assert not correct and failed == 1 and worst["resid"]["value"] != worst["resid"]["value"]
    assert not check.judge([], limits)[0]
