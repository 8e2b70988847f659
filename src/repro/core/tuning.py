"""Launch-configuration subsystem — per-target kernel tile geometry.

Ginkgo's ``common/`` folder keeps one kernel skeleton per algorithm and each
backend instantiates it with architecture-specific launch parameters (warp
size, ``launch_bounds``, block dimensions).  This module is that layer for the
Pallas kernels: every kernel family registers a :class:`TuningSpec` describing
its tile parameters, how to derive them from a :class:`HardwareParams` table,
its VMEM working-set model, and its autotune candidate space.  Call sites never
hard-code block sizes — they ask the executor for a :class:`LaunchConfig`:

    cfg = executor.launch_config("nn_attention", {"S": 2048, "D": 128, ...})
    flash_attention(..., block_q=cfg["block_q"], block_kv=cfg["block_kv"])

Resolution order (``resolve``):

1. the shape-bucketed **autotune cache** (winners measured by
   ``benchmarks --autotune`` and persisted as a per-target table);
2. an explicit per-``(op, target)`` **table override** (the one-table change
   that onboards a new hardware target);
3. the spec's **seed** derivation from ``HardwareParams`` (mxu_dim,
   lane/sublane counts).

Whatever the source, the block geometry is then constrained to the target's
alignment rules and *shrunk* (never overflowed) until the estimated working
set fits ``vmem_limit_bytes / VMEM_HEADROOM`` — the paper's "the executor owns
the kernel configuration" discipline with a safety valve.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

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
    "record_autotuned",
    "autotune_entries",
    "clear_autotune_cache",
    "save_table",
    "load_table",
    "bucket_shapes",
    "next_pow2",
    "prev_pow2",
    "VMEM_HEADROOM",
]

Shapes = Mapping[str, int]
Block = Dict[str, int]

#: fraction of ``vmem_limit_bytes`` one kernel invocation may claim — the rest
#: is headroom for double-buffered pipelining and compiler-managed spills.
VMEM_HEADROOM = 4

#: environment variable naming a persisted tuning table (JSON) to preload.
TUNING_PATH_ENV = "REPRO_TUNING_PATH"


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (bucketing granule for the autotune cache)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def prev_pow2(n: int) -> int:
    """Largest power of two <= n (tile-alignment granule for constraints)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n.bit_length() - 1)


def bucket_shapes(shapes: Shapes) -> Tuple[Tuple[str, int], ...]:
    """Canonical shape bucket: sizes rounded up to powers of two.

    ``itemsize`` is kept exact (4 vs 2 bytes is a real boundary, not a size
    regime), everything else is pow2-bucketed so a tiling measured at S=1000
    also serves S=1024.
    """
    return tuple(
        sorted(
            (k, int(v) if k == "itemsize" else next_pow2(v))
            for k, v in shapes.items()
        )
    )


@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """Resolved launch geometry for one (op, target, shape-bucket).

    ``block`` holds the named tile parameters the kernel wrapper consumes
    (e.g. ``block_q``/``block_kv`` for attention, ``chunk`` for the scans).
    ``vmem_bytes`` is the spec's working-set estimate for that geometry;
    ``fits_vmem`` is False only when no shrink step could bring it under the
    target's budget (the caller should fall back to a portable kernel space).
    ``source`` records where the geometry came from: ``"table"`` /
    ``"autotuned"`` with a ``"+shrunk"`` suffix when the budget check reduced
    it.
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
    * ``candidates(hw, shapes)`` — the autotune sweep space.
    """

    op: str
    params: Tuple[str, ...]
    seed: Callable[[HardwareParams], Block]
    vmem_bytes: Callable[[Shapes, Block], int] = _default_vmem
    constrain: Optional[Callable[[HardwareParams, Shapes, Block], Block]] = None
    floors: Mapping[str, int] = dataclasses.field(default_factory=dict)
    candidates: Optional[Callable[[HardwareParams, Shapes], List[Block]]] = None

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
#: shape-bucketed autotune winners: (op, target, bucket) -> block.
_AUTOTUNED: Dict[Tuple[str, str, Tuple[Tuple[str, int], ...]], Block] = {}
_ENV_LOADED = False


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


# -- autotune cache -----------------------------------------------------------


def record_autotuned(
    op: str, target: str, shapes: Shapes, block: Mapping[str, int]
) -> None:
    """Store a measured winner for (op, target, bucket(shapes))."""
    with _LOCK:
        _AUTOTUNED[(op, target, bucket_shapes(shapes))] = dict(block)


def autotune_entries() -> List[Dict[str, Any]]:
    """The live cache as JSON-ready records (also the persistence format)."""
    with _LOCK:
        return [
            {
                "op": op,
                "target": target,
                "bucket": [list(kv) for kv in bucket],
                "block": dict(block),
            }
            for (op, target, bucket), block in sorted(_AUTOTUNED.items())
        ]


def clear_autotune_cache() -> None:
    with _LOCK:
        _AUTOTUNED.clear()


def save_table(path: str, *, target: Optional[str] = None) -> int:
    """Persist the autotune cache (optionally one target's slice) as JSON."""
    entries = [
        e for e in autotune_entries() if target is None or e["target"] == target
    ]
    payload = {"version": 1, "entries": entries}
    dirname = os.path.dirname(os.path.abspath(path))
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return len(entries)


def load_table(path: str) -> int:
    """Load a persisted tuning table into the autotune cache."""
    with open(path) as f:
        payload = json.load(f)
    entries = payload.get("entries", [])
    with _LOCK:
        for e in entries:
            bucket = tuple((str(k), int(v)) for k, v in e["bucket"])
            _AUTOTUNED[(e["op"], e["target"], bucket)] = {
                k: int(v) for k, v in e["block"].items()
            }
    return len(entries)


def _maybe_load_env_table() -> None:
    global _ENV_LOADED
    if _ENV_LOADED:
        return
    _ENV_LOADED = True
    path = os.environ.get(TUNING_PATH_ENV)
    if path and os.path.exists(path):
        try:
            load_table(path)
        except (OSError, ValueError, KeyError, TypeError) as e:
            # a corrupt cache must not take the program down — seeds still work
            warnings.warn(
                f"ignoring unreadable tuning table {path!r} "
                f"({TUNING_PATH_ENV}): {e}"
            )


# -- resolution ---------------------------------------------------------------


def resolve(op: str, shapes: Shapes, hw: HardwareParams) -> LaunchConfig:
    """Resolve the launch geometry for ``op`` on target ``hw`` at ``shapes``.

    autotune cache -> table override -> HardwareParams seed, then constrain to
    the target's alignment rules and shrink until the working set fits the
    VMEM budget.
    """
    _maybe_load_env_table()
    spec = get_spec(op)
    shapes = dict(shapes)

    # entries missing spec params (hand-edited / older-spec table files) are
    # ignored rather than crashing the first kernel call downstream
    tuned = _AUTOTUNED.get((op, hw.name, bucket_shapes(shapes)))
    if tuned is not None and not set(spec.params) <= set(tuned):
        tuned = None
    if tuned is not None:
        block, source = dict(tuned), "autotuned"
    else:
        override = _TABLE.get((op, hw.name))
        if override is not None and not set(spec.params) <= set(override):
            override = None
        block = dict(override) if override is not None else spec.seed(hw)
        source = "table"

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
        source=source + ("+shrunk" if shrunk else ""),
    )
