"""Executor model: dispatch, fallback chains, strict mode — the paper's §3."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    NotCompiledError,
    PallasInterpretExecutor,
    PallasTpuExecutor,
    ReferenceExecutor,
    XlaExecutor,
    instantiate_common,
    make_executor,
    operation,
    use_executor,
)


@pytest.fixture(scope="module")
def demo_op():
    op = operation("test_demo_op")

    @op.register("reference")
    def _ref(ex, x):
        return x + 1.0

    @op.register("xla")
    def _xla(ex, x):
        return x + 1.0

    return op


def test_dispatch_per_space(demo_op):
    x = jnp.zeros(3)
    assert demo_op.space_used(ReferenceExecutor()) == "reference"
    assert demo_op.space_used(XlaExecutor()) == "xla"
    np.testing.assert_allclose(demo_op(x, executor=XlaExecutor()), 1.0)


def test_fallback_chain(demo_op):
    # pallas executor has no pallas kernel for this op -> falls to xla
    assert demo_op.space_used(PallasInterpretExecutor()) == "xla"


def test_strict_raises_notcompiled(demo_op):
    # Ginkgo's gko::NotCompiled semantics
    ex = PallasTpuExecutor(strict=True)
    with pytest.raises(NotCompiledError):
        demo_op.space_used(ex)
    with pytest.raises(NotCompiledError):
        demo_op(jnp.zeros(3), executor=ex)


def test_ambient_executor(demo_op):
    ex = ReferenceExecutor()
    with use_executor(ex):
        demo_op(jnp.zeros(2))
    assert ex.dispatch_log["test_demo_op"] == 1


def test_dispatch_telemetry(demo_op):
    ex = XlaExecutor()
    for _ in range(3):
        demo_op(jnp.zeros(2), executor=ex)
    assert ex.dispatch_log["test_demo_op"] == 3


def test_master_executor():
    # paper: every device executor carries a CPU-side master
    ex = PallasInterpretExecutor()
    assert isinstance(ex.master, ReferenceExecutor)
    assert ex.master.master is ex.master


def test_make_executor_factory():
    for kind in ("reference", "xla", "pallas", "pallas_interpret"):
        ex = make_executor(kind)
        assert ex.kernel_space in ("reference", "xla", "pallas")
    with pytest.raises(KeyError):
        make_executor("cuda")


def test_instantiate_common():
    # the "common/ folder" analogue: one skeleton, per-space parameters
    def skeleton(ex, x, *, block):
        return x * block

    op = instantiate_common(
        "test_common_skel", skeleton, {"reference": {"block": 2}, "xla": {"block": 3}}
    )
    assert float(op(jnp.ones(()), executor=ReferenceExecutor())) == 2.0
    assert float(op(jnp.ones(()), executor=XlaExecutor())) == 3.0


def test_duplicate_registration_rejected(demo_op):
    with pytest.raises(ValueError):
        demo_op.register("reference")(lambda ex, x: x)


# -- PR: launch-config subsystem satellites -----------------------------------


def test_make_executor_accepts_target_names():
    from repro.core import params as hw_params

    ex = make_executor("tpu_v4")
    assert isinstance(ex, PallasTpuExecutor)
    assert ex.hw is hw_params.TPU_V4
    ex2 = make_executor("cpu_interpret")
    assert isinstance(ex2, PallasInterpretExecutor)
    assert ex2.interpret
    ex3 = make_executor("cpu_xla")
    assert isinstance(ex3, XlaExecutor)
    ex4 = make_executor("cpu_reference")
    assert isinstance(ex4, ReferenceExecutor)


def test_reset_default_executor():
    from repro.core import default_executor, reset_default_executor

    reset_default_executor()
    first = default_executor()
    assert default_executor() is first  # cached
    reset_default_executor()
    second = default_executor()
    assert second is not first  # cache actually dropped
    assert type(second) is type(first)


KERNEL_OPS = (
    "nn_attention",
    "nn_rmsnorm",
    "nn_rwkv6_scan",
    "nn_ssd_scan",
    "spmv_ell",
    "spmv_sellp",
)


@pytest.mark.parametrize("op_name", KERNEL_OPS)
def test_each_registered_op_serves_expected_space(op_name):
    """Dispatch telemetry: every kernel family serves each executor from the
    expected kernel space (paper: executor picks the backend, not the op)."""
    import repro.kernels  # noqa: F401

    op = operation(op_name)
    assert op.space_used(ReferenceExecutor()) == "reference"
    assert op.space_used(PallasInterpretExecutor()) == "pallas"
    # xla executors fall back to reference only when no xla impl exists
    expected_xla = "xla" if "xla" in op._impls else "reference"
    assert op.space_used(XlaExecutor()) == expected_xla


@pytest.mark.parametrize("op_name", ("spmv_coo", "spmv_csr", "blas_dot"))
def test_strict_mode_raises_for_missing_pallas_kernels(op_name):
    """strict=True refuses the fallback chain: ops without a pallas kernel
    raise NotCompiledError on a strict pallas executor (gko::NotCompiled)."""
    import repro.sparse.ops  # noqa: F401 — populate the operations

    ex = PallasTpuExecutor(strict=True)
    with pytest.raises(NotCompiledError):
        operation(op_name).space_used(ex)


def test_dispatch_log_counts_model_ops(rng):
    import numpy as np
    import repro.kernels  # noqa: F401

    ex = PallasInterpretExecutor()
    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    op = operation("nn_rmsnorm")
    op(x, w, executor=ex)
    op(x, w, executor=ex)
    assert ex.dispatch_log["nn_rmsnorm"] == 2


# -- device selection by device_kind -------------------------------------------


class _StubDevice:
    """Stands in for a ``jax.Device``: only the fields selection reads."""

    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_tpu_v5e_kind_selects_tpu_v5e():
    from repro.core import executor_for_device, params_for_device

    dev = _StubDevice("tpu", "TPU v5 lite")
    assert params_for_device(dev).name == "tpu_v5e"
    ex = executor_for_device(dev)
    assert isinstance(ex, PallasTpuExecutor) and not ex.interpret
    assert ex.hw.name == "tpu_v5e"


def test_unknown_tpu_kind_raises():
    from repro.core import executor_for_device

    with pytest.raises(KeyError, match="TPU v99"):
        executor_for_device(_StubDevice("tpu", "TPU v99"))


def test_cpu_kind_selects_cpu_xla():
    import jax

    from repro.core import executor_for_device

    ex = executor_for_device(_StubDevice("cpu", "cpu"))
    assert isinstance(ex, XlaExecutor) and ex.hw.name == "cpu_xla"
    # the real CPU device of this process maps the same way
    assert executor_for_device(jax.devices()[0]).hw.name == "cpu_xla"


def test_v5e_peaks_are_the_published_ones():
    from repro.core import params as hw_params

    v5e = hw_params.TPU_V5E
    assert v5e.peak_flops_bf16 == 197e12
    assert v5e.hbm_bandwidth == 819e9
    assert v5e.ici_bandwidth == 1600e9 / 8  # 1,600 Gbit/s
