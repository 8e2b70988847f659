"""Executors — the paper's central abstraction, adapted to JAX.

Ginkgo §3: "the executor is a central class that provides all important
primitives for allocating/deallocating memory on a device, transferring data to
other supported devices, and basic intra-device communication (e.g.,
synchronization)"; kernels are selected "during execution via dynamic
polymorphism".

JAX adaptation:

* memory allocation / transfer  -> ``device_put`` with the executor's device or
  sharding (explicit copies, mirroring Ginkgo's decision to avoid UVM);
* synchronization               -> ``block_until_ready`` over a pytree;
* kernel selection              -> :mod:`repro.core.registry` dispatch over the
  executor's kernel-space chain at trace time;
* the "master executor" (host-side twin every device executor carries)
  -> :attr:`Executor.master`, a :class:`ReferenceExecutor` on CPU.

The four executors mirror the paper's backends:

=================  =====================  =======================================
Ginkgo backend     This repo              Role
=================  =====================  =======================================
Reference          ReferenceExecutor      sequential oracle; correctness tests
OpenMP             XlaExecutor            portable compiler-parallelized backend
CUDA / HIP         PallasTpuExecutor      hardware-native hand-written kernels
(HIP-on-nvcc)      PallasInterpretExec.   native kernels on foreign hw (validation)
=================  =====================  =======================================
"""

from __future__ import annotations

import contextlib
import contextvars
import collections
from typing import Any, Dict, Optional, Tuple

import jax

from repro.core import params as params_lib
from repro.core.params import HardwareParams
from repro.observability.events import DispatchEvent, DispatchLog

__all__ = [
    "Executor",
    "ReferenceExecutor",
    "XlaExecutor",
    "PallasTpuExecutor",
    "PallasInterpretExecutor",
    "current_executor",
    "use_executor",
    "default_executor",
    "executor_for_device",
    "reset_default_executor",
    "make_executor",
]


class Executor:
    """Base executor: owns a hardware parameter table and a kernel-space chain."""

    #: kernel spaces this executor may dispatch into, in preference order.
    spaces: Tuple[str, ...] = ("reference",)

    def __init__(
        self,
        hw: HardwareParams,
        *,
        strict: bool = False,
        device: Optional[jax.Device] = None,
    ):
        self.hw = hw
        self.strict = strict
        self.device = device
        #: dispatch telemetry: Counter face (op name -> count, used by
        #: portability tests and BENCH launch-count pins) plus a bounded
        #: deque of structured DispatchEvents filled while tracing is on.
        self.dispatch_log: DispatchLog = DispatchLog()
        #: most recent LaunchConfig resolved via :meth:`launch_config`
        #: (attached to the in-flight dispatch event by the registry).
        self._last_launch_config = None

    # -- identity ----------------------------------------------------------------
    @property
    def name(self) -> str:
        return f"{type(self).__name__}({self.hw.name})"

    @property
    def kernel_space(self) -> str:
        return self.spaces[0]

    @property
    def interpret(self) -> bool:
        """Pallas interpret mode flag (True on the CPU validation path)."""
        return self.hw.interpret

    # -- master executor (paper: every device executor has a CPU-side master) ----
    @property
    def master(self) -> "Executor":
        if isinstance(self, ReferenceExecutor):
            return self
        if not hasattr(self, "_master"):
            self._master = ReferenceExecutor(params_lib.CPU_REFERENCE)
        return self._master

    # -- memory primitives (gko::Executor::alloc / copy_from) --------------------
    def to_device(self, tree: Any) -> Any:
        """Explicit copy of a pytree onto this executor's device."""
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def copy_to(self, other: "Executor", tree: Any) -> Any:
        """Transfer a pytree to another executor (paper: inter-device copies
        route through the master when no direct path exists; device_put is our
        direct path and the host bounce is XLA's problem, which we note)."""
        return other.to_device(tree)

    def synchronize(self, tree: Any) -> Any:
        """Block until all arrays in ``tree`` are ready (queue.wait analogue)."""
        return jax.block_until_ready(tree)

    # -- dispatch ----------------------------------------------------------------
    def run(self, op_name: str, *args, **kwargs):
        """Submit a registered operation to this executor (gko ``run``)."""
        from repro.core.registry import operation

        return operation(op_name)(*args, executor=self, **kwargs)

    def _note_dispatch(
        self, op_name: str, event: Optional[DispatchEvent] = None
    ) -> None:
        self.dispatch_log.record(op_name, event)

    @property
    def dispatch_events(self):
        """Structured dispatch events (only populated while tracing)."""
        return self.dispatch_log.events

    # -- launch configuration (paper: per-architecture kernel parameters) --------
    def launch_config(self, op_name: str, shapes: Dict[str, int]):
        """Resolve the tile geometry for ``op_name`` at ``shapes`` on this
        executor's hardware target (table override, else HardwareParams
        seed, VMEM-budget checked)."""
        from repro.core import tuning

        cfg = tuning.resolve(op_name, shapes, self.hw)
        self._last_launch_config = cfg
        return cfg

    @contextlib.contextmanager
    def activate(self):
        """Make this the ambient executor for registered-op dispatch."""
        token = _CURRENT.set(self)
        try:
            yield self
        finally:
            _CURRENT.reset(token)

    def __repr__(self) -> str:
        return self.name


class ReferenceExecutor(Executor):
    """Sequential-semantics oracle. Pure jnp, no fusion tricks, no kernels."""

    spaces = ("reference",)

    def __init__(self, hw: HardwareParams = params_lib.CPU_REFERENCE, **kw):
        super().__init__(hw, **kw)


class XlaExecutor(Executor):
    """The portable compiler backend (Ginkgo's OpenMP slot): jnp lowered by XLA."""

    spaces = ("xla", "reference")

    def __init__(self, hw: HardwareParams = params_lib.CPU_XLA, **kw):
        super().__init__(hw, **kw)


class PallasTpuExecutor(Executor):
    """Hardware-native backend: hand-written Pallas TPU kernels."""

    spaces = ("pallas", "xla", "reference")

    def __init__(self, hw: HardwareParams = params_lib.TPU_V5E, **kw):
        super().__init__(hw, **kw)


class PallasInterpretExecutor(PallasTpuExecutor):
    """Pallas kernels executed in interpret mode on CPU.

    The validation backend: the same kernel bodies as :class:`PallasTpuExecutor`,
    run on foreign hardware — the analogue of compiling the HIP backend on the
    nvcc platform to check the portability layer itself.
    """

    def __init__(self, hw: HardwareParams = params_lib.CPU_INTERPRET, **kw):
        super().__init__(hw, **kw)


# -- ambient executor ---------------------------------------------------------

_CURRENT: contextvars.ContextVar[Optional[Executor]] = contextvars.ContextVar(
    "repro_current_executor", default=None
)
_DEFAULT: Optional[Executor] = None


def executor_for_device(device) -> Executor:
    """The natural executor for a JAX device, from its ``device_kind``.

    A TPU v5e ("TPU v5 lite") -> PallasTpuExecutor(tpu_v5e); the CPU ->
    XlaExecutor(cpu_xla).  (Mirrors Ginkgo applications constructing
    ``CudaExecutor`` when a GPU is present and ``OmpExecutor`` otherwise.)
    A kind without a hardware table raises.
    """
    return _executor_for_params(params_lib.params_for_device(device))


def default_executor() -> Executor:
    """The executor for the first JAX device (cached)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = executor_for_device(jax.devices()[0])
    return _DEFAULT


def reset_default_executor() -> None:
    """Drop the cached platform-default executor.

    Tests (and anything that mutates the default target table) use this so the
    module-level cache cannot leak one test's executor into the next.
    """
    global _DEFAULT
    _DEFAULT = None


def current_executor() -> Executor:
    ex = _CURRENT.get()
    return ex if ex is not None else default_executor()


@contextlib.contextmanager
def use_executor(ex: Executor):
    with ex.activate():
        yield ex


_EXECUTOR_FACTORY = {
    "reference": lambda hw, **kw: ReferenceExecutor(hw or params_lib.CPU_REFERENCE, **kw),
    "xla": lambda hw, **kw: XlaExecutor(hw or params_lib.CPU_XLA, **kw),
    "pallas": lambda hw, **kw: PallasTpuExecutor(hw or params_lib.TPU_V5E, **kw),
    "pallas_interpret": lambda hw, **kw: PallasInterpretExecutor(
        hw or params_lib.CPU_INTERPRET, **kw
    ),
}


def _executor_for_params(hw: HardwareParams, **kw) -> Executor:
    """Pick the executor class a hardware target naturally runs under."""
    if hw.kernel_space == "pallas":
        cls = PallasInterpretExecutor if hw.interpret else PallasTpuExecutor
    elif hw.kernel_space == "xla":
        cls = XlaExecutor
    else:
        cls = ReferenceExecutor
    return cls(hw, **kw)


def make_executor(kind: str, hw: Optional[HardwareParams] = None, **kw) -> Executor:
    """Factory used by configs/CLIs: ``--executor pallas_interpret`` etc.

    ``kind`` is either a kernel-space kind (``reference`` / ``xla`` /
    ``pallas`` / ``pallas_interpret``) or a hardware target name from
    :data:`repro.core.params.TARGETS` (``tpu_v4``, ``cpu_interpret``, ...) —
    the latter picks both the parameter table and the executor class.
    """
    factory = _EXECUTOR_FACTORY.get(kind)
    if factory is not None:
        return factory(hw, **kw)
    if kind in params_lib.TARGETS:
        return _executor_for_params(hw or params_lib.get_target(kind), **kw)
    raise KeyError(
        f"unknown executor kind {kind!r}; known kinds: "
        f"{sorted(_EXECUTOR_FACTORY)}, targets: {sorted(params_lib.TARGETS)}"
    ) from None
