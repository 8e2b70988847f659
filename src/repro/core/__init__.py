"""repro.core — the paper's contribution: executor-based platform portability.

Public surface:

* :mod:`repro.core.linop` — the LinOp hierarchy (``gko::LinOp``): the one
  ``apply`` interface every format, preconditioner, and solver composes
  through, plus the combinators (Composition / Sum / ScaledIdentity /
  Transpose / MatrixFreeOp / Identity).
* :mod:`repro.core.executor` — the Executor hierarchy (Reference / Xla /
  PallasTpu / PallasInterpret) and the ambient-executor context.
* :mod:`repro.core.registry` — operation registration and dynamic dispatch
  (``GKO_REGISTER_OPERATION`` analogue).
* :mod:`repro.core.coop` — cooperative groups on TPU lane tiles.
* :mod:`repro.core.params` — per-target hardware parameter tables.
* :mod:`repro.core.tuning` — launch-configuration resolution (per-target
  tuning tables seeded from ``params``) behind ``Executor.launch_config``.
"""

from repro.core.linop import (
    Composition,
    Identity,
    LinOp,
    MatrixFreeOp,
    ScaledIdentity,
    Sum,
    Transpose,
    as_linop,
)
from repro.core.executor import (
    Executor,
    PallasInterpretExecutor,
    PallasTpuExecutor,
    ReferenceExecutor,
    XlaExecutor,
    current_executor,
    default_executor,
    executor_for_device,
    make_executor,
    reset_default_executor,
    use_executor,
)
from repro.core.params import (
    CPU_INTERPRET,
    CPU_REFERENCE,
    CPU_XLA,
    TPU_V4,
    TPU_V5E,
    HardwareParams,
    get_target,
    params_for_device,
)
from repro.core.registry import (
    NotCompiledError,
    Operation,
    all_operations,
    instantiate_common,
    operation,
    register,
    registered_spaces,
)
from repro.core.tuning import LaunchConfig, TuningSpec
from repro.core import coop, tuning

__all__ = [
    "LinOp",
    "Composition",
    "Sum",
    "ScaledIdentity",
    "Transpose",
    "MatrixFreeOp",
    "Identity",
    "as_linop",
    "Executor",
    "ReferenceExecutor",
    "XlaExecutor",
    "PallasTpuExecutor",
    "PallasInterpretExecutor",
    "current_executor",
    "default_executor",
    "executor_for_device",
    "reset_default_executor",
    "use_executor",
    "make_executor",
    "LaunchConfig",
    "TuningSpec",
    "tuning",
    "HardwareParams",
    "get_target",
    "params_for_device",
    "TPU_V5E",
    "TPU_V4",
    "CPU_INTERPRET",
    "CPU_XLA",
    "CPU_REFERENCE",
    "NotCompiledError",
    "Operation",
    "operation",
    "register",
    "registered_spaces",
    "all_operations",
    "instantiate_common",
    "coop",
]
