"""Launch-configuration subsystem — per-target kernel tile geometry.

Ginkgo's ``common/`` folder keeps one kernel skeleton per algorithm and each
backend instantiates it with architecture-specific launch parameters (warp
size, ``launch_bounds``, block dimensions).  This module is that layer for the
Pallas kernels: every kernel family registers a :class:`TuningSpec` describing
its tile parameters, how to derive them from a :class:`HardwareParams` table,
and its VMEM working-set model.  Call sites never hard-code block sizes — they
ask the executor for a :class:`LaunchConfig`:

    cfg = executor.launch_config("nn_attention", {"S": 2048, "D": 128, ...})
    flash_attention(..., block_q=cfg["block_q"], block_kv=cfg["block_kv"])

Resolution (``resolve``) takes an explicit per-``(op, target)`` **table
override** (the one-table change that onboards a new hardware target) when
one is set, and otherwise the spec's **seed** derivation from
``HardwareParams`` (mxu_dim, lane/sublane counts).  The block geometry is
then constrained to the target's alignment rules and *shrunk* (never
overflowed) until the estimated working set fits
``vmem_limit_bytes / VMEM_HEADROOM`` — the paper's "the executor owns the
kernel configuration" discipline with a safety valve.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, Mapping, Optional, Tuple

from repro.core.params import TARGETS, HardwareParams

__all__ = [
    "LaunchConfig",
    "TuningSpec",
    "register_spec",
    "get_spec",
    "all_specs",
    "resolve",
    "set_table_entry",
    "table_entry",
    "default_table",
    "prev_pow2",
    "VMEM_HEADROOM",
]

Shapes = Mapping[str, int]
Block = Dict[str, int]

#: fraction of ``vmem_limit_bytes`` one kernel invocation may claim — the rest
#: is headroom for double-buffered pipelining and compiler-managed spills.
VMEM_HEADROOM = 4


def prev_pow2(n: int) -> int:
    """Largest power of two <= n (tile-alignment granule for constraints)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n.bit_length() - 1)


@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """Resolved launch geometry for one (op, target, shapes).

    ``block`` holds the named tile parameters the kernel wrapper consumes
    (e.g. ``block_q``/``block_kv`` for attention, ``chunk`` for the scans).
    ``vmem_bytes`` is the spec's working-set estimate for that geometry;
    ``fits_vmem`` is False only when no shrink step could bring it under the
    target's budget (the caller should fall back to a portable kernel space).
    ``source`` is ``"table"``, or ``"table+shrunk"`` when the budget check
    reduced the geometry.
    """

    op: str
    target: str
    block: Mapping[str, int]
    vmem_bytes: int
    fits_vmem: bool
    source: str

    def __getitem__(self, key: str) -> int:
        return self.block[key]

    def get(self, key: str, default: Optional[int] = None) -> Optional[int]:
        return self.block.get(key, default)


def _default_vmem(shapes: Shapes, block: Block) -> int:
    return 0


@dataclasses.dataclass(frozen=True)
class TuningSpec:
    """Everything the resolver needs to know about one kernel family.

    * ``seed(hw)``            — shape-independent default geometry derived from
      the hardware table (Ginkgo: the per-architecture config header).
    * ``vmem_bytes(shapes, block)`` — working-set model for the budget check.
    * ``constrain(hw, shapes, block)`` — clamp/align a proposed geometry to the
      target's rules (sublane multiples, power-of-two lanes, divisibility).
    * ``floors``              — per-parameter lower bounds for the shrink loop.
    """

    op: str
    params: Tuple[str, ...]
    seed: Callable[[HardwareParams], Block]
    vmem_bytes: Callable[[Shapes, Block], int] = _default_vmem
    constrain: Optional[Callable[[HardwareParams, Shapes, Block], Block]] = None
    floors: Mapping[str, int] = dataclasses.field(default_factory=dict)

    def floor(self, param: str) -> int:
        return int(self.floors.get(param, 1))

    def shrink(self, block: Block) -> Optional[Block]:
        """One shrink step: halve the largest still-shrinkable parameter."""
        shrinkable = [
            (v, k) for k, v in block.items()
            if k in self.params and v // 2 >= self.floor(k)
        ]
        if not shrinkable:
            return None
        _, key = max(shrinkable)
        out = dict(block)
        out[key] = block[key] // 2
        return out


_LOCK = threading.Lock()
_SPECS: Dict[str, TuningSpec] = {}
#: explicit per-(op, target) geometry overrides — "the one-table change".
_TABLE: Dict[Tuple[str, str], Block] = {}


def register_spec(spec: TuningSpec) -> TuningSpec:
    with _LOCK:
        existing = _SPECS.get(spec.op)
        if existing is not None and existing is not spec:
            raise ValueError(f"tuning spec for {spec.op!r} already registered")
        _SPECS[spec.op] = spec
    return spec


def get_spec(op: str) -> TuningSpec:
    # kernel families register their specs from their ops.py bindings, which
    # importing the package runs (repro/__init__.py)
    try:
        return _SPECS[op]
    except KeyError:
        raise KeyError(
            f"no tuning spec registered for op {op!r}; known: {sorted(_SPECS)}"
        ) from None


def all_specs() -> Dict[str, TuningSpec]:
    return dict(_SPECS)


# -- tables -------------------------------------------------------------------


def set_table_entry(op: str, target: str, block: Mapping[str, int]) -> None:
    """Pin an explicit geometry for (op, target) — the new-target entry point."""
    with _LOCK:
        _TABLE[(op, target)] = dict(block)


def table_entry(op: str, target: str) -> Optional[Block]:
    entry = _TABLE.get((op, target))
    return dict(entry) if entry is not None else None


def default_table() -> Dict[Tuple[str, str], Block]:
    """The full seeded tuning table: every registered op x every known target.

    This is what Ginkgo's per-backend config headers flatten to — inspect it,
    or use it as the starting point for a new target's table file.
    """
    out: Dict[Tuple[str, str], Block] = {}
    for op, spec in all_specs().items():
        for name, hw in TARGETS.items():
            out[(op, name)] = _TABLE.get((op, name), spec.seed(hw))
    return out


# -- resolution ---------------------------------------------------------------


def resolve(op: str, shapes: Shapes, hw: HardwareParams) -> LaunchConfig:
    """Resolve the launch geometry for ``op`` on target ``hw`` at ``shapes``.

    Table override, else HardwareParams seed, then constrain to the target's
    alignment rules and shrink until the working set fits the VMEM budget.
    """
    spec = get_spec(op)
    shapes = dict(shapes)

    # an override missing spec params is ignored rather than crashing the
    # first kernel call downstream
    override = _TABLE.get((op, hw.name))
    if override is not None and not set(spec.params) <= set(override):
        override = None
    block = dict(override) if override is not None else spec.seed(hw)

    if spec.constrain is not None:
        block = spec.constrain(hw, shapes, block)

    budget = hw.vmem_limit_bytes // VMEM_HEADROOM
    vmem = spec.vmem_bytes(shapes, block)
    shrunk = False
    while vmem > budget:
        nxt = spec.shrink(block)
        if nxt is None:
            break
        if spec.constrain is not None:
            nxt = spec.constrain(hw, shapes, nxt)
        if nxt == block:
            break
        block, shrunk = nxt, True
        vmem = spec.vmem_bytes(shapes, block)

    return LaunchConfig(
        op=op,
        target=hw.name,
        block=block,
        vmem_bytes=int(vmem),
        fits_vmem=vmem <= budget,
        source="table+shrunk" if shrunk else "table",
    )
