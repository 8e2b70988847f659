"""Byte counts behind the roofline shares, against hand counts, and the
table of peaks."""

import pytest

from chipbench import roofline
from chipbench.systems import poisson7

# 4^3 grid: 64 rows; each of the 6 faces drops 16 neighbour entries
N, NNZ = 64, 64 * 7 - 6 * 16


def test_the_grid_has_the_hand_counted_entries():
    s = poisson7.host_csr(4)
    assert (s.n, s.nnz) == (N, NNZ) == (64, 352)


def test_spmv_bytes():
    # 352 values + 64 of x + 64 of y, 4 bytes each; no index bytes
    assert roofline.spmv_min_bytes(N, NNZ) == 352 * 4 + 64 * 4 + 64 * 4 == 1920


@pytest.mark.parametrize("precond, operand", [
    ({"kind": "block_jacobi", "block_size": 8}, 8 * 8 * 8 * 4),  # 8 blocks of 8x8
    ({"kind": "jacobi"}, 64 * 4),
    ({"kind": "identity"}, 0),
])
def test_cg_iteration_bytes(precond, operand):
    # values once, the preconditioner's operand once, x, r, p read and written
    assert roofline.precond_operand_bytes(N, precond) == operand
    assert roofline.cg_iter_min_bytes(N, NNZ, precond) == 352 * 4 + operand + 6 * 64 * 4


def test_a_ragged_last_block_counts_whole():
    assert roofline.precond_operand_bytes(10, {"kind": "block_jacobi", "block_size": 8}) \
        == 2 * 64 * 4


def test_full_size_counts():
    # 128^3: 58.3 MB of values, 67.1 MB of inverted blocks, 6 passes of 8.4 MB
    n, nnz = 128 ** 3, 14_581_760
    assert roofline.spmv_min_bytes(n, nnz) == 75_104_256
    assert roofline.cg_iter_min_bytes(
        n, nnz, {"kind": "block_jacobi", "block_size": 8}) == 175_767_552


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert roofline.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_an_unknown_device_is_an_error(kind):
    with pytest.raises(KeyError):
        roofline.peak(kind, "hbm_bytes_per_s")
