"""Compile the solve path for a described TPU v5e at 2,097,152 rows.

Nothing runs: each test lowers through the library's normal dispatch (a
:class:`PallasTpuExecutor`, the registry, the tuning tables) and compiles for
one chip, or all four, of a ``v5e:2x2`` topology that is described, not
attached — what the chip's compiler would refuse (block shapes off the
(8, 128) tiling, scalar stores to VMEM, more VMEM than the kernel asked for)
fails here.  Sizes are
the 128³ Poisson system the chip benchmark solves: 7 ELL slots per row,
8x8 Jacobi blocks.

The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker that runs this file loads
the TPU compiler.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import PallasTpuExecutor, params as hw_params, registry

M = 128 ** 3  # rows of poisson_3d(128)
K = 7  # 7-point stencil
BS = 8  # block-Jacobi block size
OFFSETS = (-128 * 128, -128, -1, 0, 1, 128, 128 * 128)  # its diagonals


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def four_chips(topo):
    """A 1-D mesh over the four described chips, which the distributed solve
    takes in place of the host's devices."""
    import numpy as np
    from jax.sharding import Mesh

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield Mesh(np.asarray(topo.devices[:4]), ("data",))
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def ex():
    return PallasTpuExecutor(hw_params.TPU_V5E)


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _ell(one_chip, offsets=None):
    from repro.sparse import Ell

    return Ell(
        col_idx=_sds(one_chip, (M, K), jnp.int32),
        values=_sds(one_chip, (M, K)),
        shape=(M, M),
        offsets=offsets,
    )


def _row_gathers(text, elements=M):
    """The compiled program's gathers whose result has ``elements`` elements."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= [a-z0-9]+\[([0-9,]*)\]\S* gather\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) == elements:
            found.append(line.strip())
    return found


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"
    return text


def test_spmv_ell_compiles(one_chip, ex):
    op = registry.operation("spmv_ell")
    assert op.space_used(ex) == "pallas"
    _compile(lambda A, x: op(A, x, executor=ex), _ell(one_chip), _sds(one_chip, (M,)))


def test_spmv_dot_ell_compiles(one_chip, ex):
    op = registry.operation("spmv_dot_ell")
    assert op.space_used(ex) == "pallas"
    vec = _sds(one_chip, (M,))
    _compile(lambda A, x, w: op(A, x, w, executor=ex), _ell(one_chip), vec, vec)


def test_spmv_ell_band_compiles(one_chip, ex):
    """Diagonal-aligned slots: shifted slices of ``x``, no gather."""
    op = registry.operation("spmv_ell")
    text = _compile(
        lambda A, x: op(A, x, executor=ex),
        _ell(one_chip, OFFSETS),
        _sds(one_chip, (M,)),
    )
    assert "spmv_ell_band" in text


def test_spmv_dot_ell_band_compiles(one_chip, ex):
    op = registry.operation("spmv_dot_ell")
    vec = _sds(one_chip, (M,))
    text = _compile(
        lambda A, x, w: op(A, x, w, executor=ex),
        _ell(one_chip, OFFSETS), vec, vec,
    )
    assert "spmv_dot_ell_band" in text


def test_axpy_norm_compiles(one_chip, ex):
    op = registry.operation("axpy_norm")
    assert op.space_used(ex) == "pallas"
    vec = _sds(one_chip, (M,))
    _compile(
        lambda a, x, y: op(a, x, y, executor=ex), _sds(one_chip, ()), vec, vec
    )


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_block_jacobi_apply_compiles(one_chip, ex, storage):
    op = registry.operation("block_jacobi_apply")
    assert op.space_used(ex) == "pallas"
    nb = M // BS
    _compile(
        lambda inv, vp: op(inv, vp, executor=ex),
        _sds(one_chip, (nb, BS, BS), jnp.dtype(storage)),
        _sds(one_chip, (nb, BS)),
    )


def test_fused_cg_block_jacobi_compiles(one_chip, ex):
    """The whole fused-CG ``while_loop`` with block-Jacobi, as one program."""
    from repro.precond import BlockJacobi
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    nb = M // BS

    def solve(A, inv, gather_idx, scatter_idx, b):
        M_ = BlockJacobi(
            inv_blocks=(inv,), gather_idx=gather_idx, scatter_idx=scatter_idx,
            n=M, block_size=BS, num_blocks=nb,
        )
        res = krylov.cg(A, b, M=M_, executor=ex, strict=False,
                        stop=Stop(max_iters=1000, reduction_factor=1e-6))
        return res.x, res.iterations

    text = _compile(
        solve,
        _ell(one_chip),
        _sds(one_chip, (nb, BS, BS)),
        _sds(one_chip, (nb, BS), jnp.int32),
        _sds(one_chip, (M,), jnp.int32),
        _sds(one_chip, (M,)),
    )
    assert "while" in text
    # the fused loop: spmv_dot_ell, axpy_norm and block_jacobi_apply kernels
    assert text.count("tpu_custom_call") >= 3
    # the gathered layout moves the vector through its maps, both ways
    assert len(_row_gathers(text)) >= 2


def test_fused_cg_block_jacobi_row_compiles(one_chip, ex):
    """The fused-CG loop with a row-layout block-Jacobi (uniform blocks, one
    storage class, no maps): the apply reshapes the vector and the program
    holds no gather of an ``M``-row vector."""
    from repro.precond import BlockJacobi
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    nb = M // BS

    def solve(A, inv, b):
        M_ = BlockJacobi(
            inv_blocks=(inv,), gather_idx=None, scatter_idx=None,
            n=M, block_size=BS, num_blocks=nb,
        )
        assert M_.layout == "row"
        res = krylov.cg(A, b, M=M_, executor=ex, strict=False,
                        stop=Stop(max_iters=1000, reduction_factor=1e-6))
        return res.x, res.iterations

    text = _compile(
        solve,
        _ell(one_chip, OFFSETS),
        _sds(one_chip, (nb, BS, BS)),
        _sds(one_chip, (M,)),
    )
    assert "while" in text
    assert text.count("tpu_custom_call") >= 3
    assert _row_gathers(text) == []


def test_fused_cg_amg_compiles(one_chip, ex):
    """CG with one smoothed-aggregation V-cycle as ``M``, the hierarchy a
    jit argument: the band SpMV on the fine level and inside level 0's
    factored transfers and level 1's factored operator, the rectangular
    packed ``spmv_ell`` of the stored transfers and the packed coarse
    operators inside the loop.  The hierarchy is built here on the CPU at 32³
    and handed over as shapes."""
    import numpy as np

    from repro import sparse
    from repro.kernels import lanes
    from repro.precond import make_preconditioner
    from repro.solvers import krylov
    from repro.solvers.common import Stop
    from repro.sparse.gallery import poisson_3d

    indptr, indices, values, shape = poisson_3d(32)
    A = sparse.ell_from_csr_host(indptr, indices, values.astype(np.float32), shape)
    M_ = make_preconditioner(A, "amg", executor=ex, theta=0.0)
    assert M_.num_levels >= 3
    stored = [L.R for L in M_.levels if isinstance(L.R, sparse.Ell)]
    assert any(R.offsets is None and R.shape[0] < R.shape[1] for R in stored)
    # the stored A_1 would gather its 34 slots, padded to 64 by block_k 32,
    # over its row blocks: (64, rows, 128)
    n_agg = M_.levels[1].A.shape[0]
    block_m = ex.launch_config("spmv_ell", {"m": n_agg, "k": 34, "n": n_agg,
                                            "itemsize": 4})["block_m"]
    rows, _ = lanes.row_tiling(n_agg, block_m, jnp.float32, jnp.float32)

    def shapes(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    def solve(A, M, b):
        res = krylov.cg(A, b, M=M, executor=ex, strict=False,
                        stop=Stop(max_iters=1000, reduction_factor=1e-6))
        return res.x, res.iterations

    text = _compile(solve, shapes(A), shapes(M_), _sds(one_chip, (shape[0],)))
    assert "while" in text
    # the fine level's band kernels and the transfers' packed kernel
    assert "spmv_dot_ell_band" in text and "spmv_ell_band" in text
    assert text.count("tpu_custom_call") >= 2 * M_.num_levels
    assert _row_gathers(text, 64 * rows * lanes.LANES) == []


def test_dist_cg_jacobi_four_slabs_compiles(four_chips, ex, monkeypatch):
    """CG + scalar Jacobi on ``DistEll`` over four row slabs of 524,288 rows,
    one per chip: the halo ``all_gather``, the ``psum`` reductions and each
    slab's Pallas SpMV in one program.  Interior slots hold the full 7-point
    row, boundary rows 6 local slots and 1 halo slot; a slab's halo is one
    128 x 128 plane per neighbour."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import DistEll, Partition
    from repro.launch import mesh as mesh_lib
    from repro.solvers import krylov
    from repro.solvers.common import Stop

    monkeypatch.setattr(
        mesh_lib, "make_shard_mesh", lambda n, axis="data": four_chips
    )
    parts, plane = 4, 128 * 128
    L = M // parts

    def slabs(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(
            (parts,) + shape, dtype, sharding=NamedSharding(four_chips, P("data"))
        )

    A = DistEll(
        int_col_idx=slabs(L, K, dtype=jnp.int32), int_values=slabs(L, K),
        bnd_col_idx=slabs(L, K - 1, dtype=jnp.int32), bnd_values=slabs(L, K - 1),
        halo_col_idx=slabs(L, 1, dtype=jnp.int32), halo_values=slabs(L, 1),
        halo_map=slabs(2 * plane, dtype=jnp.int32),
        shape=(M, M), nnz=K * M - 6 * plane,
        partition=Partition.uniform(M, parts),
        _halo_counts=(plane, 2 * plane, 2 * plane, plane),
    )
    leaves, tree = jax.tree_util.tree_flatten(A)

    def solve(leaves, b):
        res = krylov.cg(jax.tree_util.tree_unflatten(tree, leaves), b,
                        M="jacobi", executor=ex,
                        stop=Stop(max_iters=5000, reduction_factor=1e-6))
        return res.x, res.iterations

    b = jax.ShapeDtypeStruct((M,), jnp.float32,
                             sharding=NamedSharding(four_chips, P("data")))
    text = _compile(solve, leaves, b)
    assert "while" in text
    assert "all-gather" in text and "all-reduce" in text
