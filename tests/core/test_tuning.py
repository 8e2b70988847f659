"""Launch-configuration subsystem: tables, VMEM budget, alignment."""

import dataclasses

import pytest

from repro.core import params as hw_params
from repro.core import tuning
from repro.core.executor import PallasInterpretExecutor, XlaExecutor

# pull in every kernel family's spec registration
import repro.kernels  # noqa: F401


OPS_AND_SHAPES = {
    "nn_attention": {"S": 2048, "Skv": 2048, "D": 128, "itemsize": 2},
    "nn_attention_chunked": {"S": 2048, "Skv": 2048, "D": 128, "itemsize": 2},
    "nn_rmsnorm": {"rows": 8192, "d": 4096, "itemsize": 2},
    "nn_rwkv6_scan": {"S": 2048, "K": 64, "V": 64, "itemsize": 4},
    "nn_ssd_scan": {"S": 2048, "N": 128, "P": 64, "itemsize": 4},
    "spmv_ell": {"m": 4096, "k": 128, "n": 4096, "itemsize": 4},
    "spmv_sellp": {
        "m": 4096, "n": 4096, "slice_size": 8, "stride_factor": 8, "itemsize": 4
    },
}


@pytest.mark.parametrize("op", sorted(OPS_AND_SHAPES))
@pytest.mark.parametrize("target", sorted(hw_params.TARGETS))
def test_resolved_config_fits_vmem_and_alignment(op, target):
    """Every kernel family's resolved config respects the target's budget and
    MXU/lane alignment — for ALL hardware targets (the portability claim)."""
    hw = hw_params.get_target(target)
    cfg = tuning.resolve(op, OPS_AND_SHAPES[op], hw)
    assert cfg.op == op and cfg.target == target
    assert cfg.fits_vmem, f"{op}@{target} over budget: {cfg}"
    assert cfg.vmem_bytes <= hw.vmem_limit_bytes // tuning.VMEM_HEADROOM
    spec = tuning.get_spec(op)
    assert set(cfg.block) == set(spec.params)
    for param, value in cfg.block.items():
        assert value >= spec.floor(param), (param, cfg)
    # alignment rules per family
    if op == "nn_attention":
        assert cfg["block_q"] % hw.sublane_count == 0
        assert cfg["block_kv"] % hw.sublane_count == 0
    if op == "nn_rmsnorm":
        assert cfg["block_rows"] % hw.sublane_count == 0
    if op == "spmv_ell":
        # whole lane-dense (sublane x lane) row tiles
        assert cfg["block_m"] % (hw.sublane_count * hw.lane_count) == 0
        assert cfg["block_k"] >= 1
    if op == "spmv_sellp":
        assert OPS_AND_SHAPES[op]["stride_factor"] % cfg["block_cols"] == 0
    if op in ("nn_rwkv6_scan", "nn_ssd_scan"):
        c = cfg["chunk"]
        assert c & (c - 1) == 0


@pytest.mark.parametrize("op", sorted(OPS_AND_SHAPES))
def test_default_table_covers_all_targets(op):
    table = tuning.default_table()
    for target in hw_params.TARGETS:
        assert (op, target) in table


def test_vmem_shrink_never_overflows():
    """A starved target shrinks the geometry instead of overflowing."""
    tiny = dataclasses.replace(
        hw_params.CPU_INTERPRET, vmem_limit_bytes=4 * 1024 * 1024
    )
    big = hw_params.CPU_INTERPRET
    # the VMEM-resident pallas tile families (spmv has its own fallback
    # test); the chunked-xla scan is XLA-managed
    for op in ("nn_attention", "nn_rmsnorm", "nn_rwkv6_scan", "nn_ssd_scan"):
        shapes = OPS_AND_SHAPES[op]
        cfg_tiny = tuning.resolve(op, shapes, tiny)
        cfg_big = tuning.resolve(op, shapes, big)
        assert cfg_tiny.vmem_bytes <= tiny.vmem_limit_bytes // tuning.VMEM_HEADROOM
        assert sum(cfg_tiny.block.values()) <= sum(cfg_big.block.values())


def test_spmv_infeasible_reports_not_fitting():
    """When x cannot be VMEM-resident no shrink helps: fits_vmem goes False
    (the binding then falls back to the portable kernel space)."""
    tiny = dataclasses.replace(
        hw_params.CPU_INTERPRET, vmem_limit_bytes=256 * 1024
    )
    shapes = {"m": 10**6, "k": 64, "n": 10**6, "itemsize": 4}
    cfg = tuning.resolve("spmv_ell", shapes, tiny)
    assert not cfg.fits_vmem


def test_table_override_wins_over_seed():
    target = "tpu_v4"
    try:
        tuning.set_table_entry("nn_rmsnorm", target, {"block_rows": 512})
        cfg = tuning.resolve(
            "nn_rmsnorm", {"rows": 4096, "d": 1024, "itemsize": 4},
            hw_params.get_target(target),
        )
        assert cfg["block_rows"] == 512
        assert cfg.source == "table"
    finally:
        tuning._TABLE.pop(("nn_rmsnorm", target), None)


def test_stale_table_entry_missing_params_is_ignored():
    """An override that lacks the current spec's params falls back to the
    seed instead of crashing the kernel call."""
    shapes = {"S": 128, "Skv": 128, "D": 64, "itemsize": 4}
    seeded = tuning.resolve("nn_attention", shapes, hw_params.TPU_V5E)
    try:
        tuning.set_table_entry("nn_attention", "tpu_v5e", {"block_q": 64})
        cfg = tuning.resolve("nn_attention", shapes, hw_params.TPU_V5E)
        assert cfg.source == seeded.source
        assert cfg.block == seeded.block
    finally:
        tuning._TABLE.pop(("nn_attention", "tpu_v5e"), None)


def test_shrunk_geometry_is_marked():
    """``source`` is ``"table"`` unless the budget check shrank the geometry."""
    shapes = OPS_AND_SHAPES["nn_rmsnorm"]
    big = tuning.resolve("nn_rmsnorm", shapes, hw_params.CPU_INTERPRET)
    tiny = tuning.resolve(
        "nn_rmsnorm", shapes,
        dataclasses.replace(hw_params.CPU_INTERPRET, vmem_limit_bytes=4 * 1024 * 1024),
    )
    assert big.source == "table"
    assert tiny.source == "table+shrunk"
    assert tiny["block_rows"] < big["block_rows"]


def test_executor_launch_config_entry_point():
    ex = PallasInterpretExecutor()
    cfg = ex.launch_config("nn_attention", {"S": 128, "Skv": 128, "D": 64,
                                            "itemsize": 4})
    assert cfg.target == "cpu_interpret"
    assert cfg["block_q"] >= 8 and cfg["block_kv"] >= 8
    # the xla executor resolves against its own target row
    cfg_xla = XlaExecutor().launch_config(
        "nn_rwkv6_scan", {"S": 256, "K": 64, "V": 64, "itemsize": 4}
    )
    assert cfg_xla.target == "cpu_xla"


def test_unknown_op_raises():
    with pytest.raises(KeyError):
        tuning.resolve("no_such_op", {}, hw_params.CPU_XLA)


@pytest.mark.parametrize("n, want", [(0, 1), (1, 1), (3, 2), (1024, 1024), (1025, 1024)])
def test_prev_pow2(n, want):
    assert tuning.prev_pow2(n) == want
