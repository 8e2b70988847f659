"""Structured dispatch events — the record behind ``Executor.dispatch_log``.

PR-6 and earlier kept a bare ``Counter`` of op names on each executor.  That
counter is load-bearing (launch-count pins in the convergence and portability
tests), so it stays — but it is now a *derived view*: :class:`DispatchLog`
subclasses ``Counter`` and additionally keeps a bounded deque of
:class:`DispatchEvent` records when tracing is enabled.  Each event captures
what Ginkgo's operation logger sees at a kernel launch:

* which operation ran, and which **kernel space** served it
  (``reference`` / ``xla`` / ``pallas``);
* the executor and hardware **target** it ran on;
* operand **shapes** and a power-of-two **shape bucket** of the largest
  operand;
* the resolved :class:`~repro.core.tuning.LaunchConfig`, when the kernel
  consulted one;
* **wall time** of the dispatch (trace-time under ``jit`` — structure, not
  steady-state perf; see :mod:`repro.observability.trace`) and **estimated
  bytes moved**.  No bandwidth is derived from the two: under ``jit`` the
  wall time is tracing, not the device (device time comes from a profiler
  trace, by the op's named scope).

This module is stdlib-only on purpose: it is imported by
``repro.core.registry`` at module load, before JAX-heavy modules come up.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "EVENT_CAPACITY",
    "DispatchEvent",
    "DispatchLog",
    "summarize_operands",
    "shape_bucket",
    "make_event",
]

#: bounded so a long-running traced process cannot grow without limit; the
#: Chrome trace keeps the full stream, this deque is the queryable tail.
EVENT_CAPACITY = 4096


def _next_pow2(n: int) -> int:
    if n <= 1:
        return 1
    return 1 << (int(n) - 1).bit_length()


def shape_bucket(shapes) -> int:
    """Power-of-two bucket of the largest operand's element count.

    Events of one op that differ only within a power of two share a bucket.
    """
    biggest = 0
    for shp in shapes:
        size = 1
        for d in shp:
            size *= int(d)
        biggest = max(biggest, size)
    return _next_pow2(biggest)


def summarize_operands(objs) -> Tuple[List[tuple], int]:
    """Extract ``(shapes, estimated_bytes)`` from a bag of operands.

    Understands three operand kinds, in priority order: format objects
    exposing ``memory_bytes`` (CSR/ELL/...), array-likes with
    ``shape``/``dtype`` (including tracers — only static metadata is read),
    and containers (tuple/list/dict), walked recursively.  Scalars and
    unknown objects are ignored.
    """
    shapes: List[tuple] = []
    nbytes = 0
    stack = list(objs)
    budget = 256  # defensive bound on pathological nesting
    while stack and budget:
        budget -= 1
        o = stack.pop()
        if o is None or isinstance(o, (bool, int, float, complex, str, bytes)):
            continue
        shp = getattr(o, "shape", None)
        if shp is not None:
            try:
                shp = tuple(int(d) for d in shp)
            except (TypeError, ValueError):
                continue
            shapes.append(shp)
            mb = getattr(o, "memory_bytes", None)
            if mb is not None:
                try:
                    nbytes += int(mb)
                    continue
                except (TypeError, ValueError):
                    pass
            dt = getattr(o, "dtype", None)
            itemsize = int(getattr(dt, "itemsize", 0) or 4)
            size = 1
            for d in shp:
                size *= d
            nbytes += size * itemsize
        elif isinstance(o, (tuple, list)):
            stack.extend(o)
        elif isinstance(o, dict):
            stack.extend(o.values())
    return shapes, nbytes


@dataclasses.dataclass(frozen=True)
class DispatchEvent:
    """One operation dispatch, fully described."""

    op: str
    space: str
    executor: str
    target: str
    shapes: Tuple[tuple, ...]
    shape_bucket: int
    launch: Optional[Dict[str, Any]]
    wall_us: float
    est_bytes: int
    ts_us: float

    def to_args(self) -> Dict[str, Any]:
        """The ``args`` payload of the Chrome trace event for this dispatch."""
        args: Dict[str, Any] = {
            "space": self.space,
            "executor": self.executor,
            "target": self.target,
            "shapes": [list(s) for s in self.shapes],
            "shape_bucket": self.shape_bucket,
            "est_bytes": self.est_bytes,
        }
        if self.launch is not None:
            args["launch"] = self.launch
        return args


def make_event(
    *,
    op: str,
    space: str,
    executor,
    launch,
    wall_us: float,
    ts_us: float,
    operands,
    out,
) -> DispatchEvent:
    """Build a :class:`DispatchEvent` from a finished dispatch."""
    in_shapes, in_bytes = summarize_operands(operands)
    out_shapes, out_bytes = summarize_operands([out])
    launch_dict = None
    if launch is not None and dataclasses.is_dataclass(launch):
        launch_dict = dataclasses.asdict(launch)
    return DispatchEvent(
        op=op,
        space=space,
        executor=type(executor).__name__,
        target=executor.hw.name,
        shapes=tuple(in_shapes),
        shape_bucket=shape_bucket(in_shapes),
        launch=launch_dict,
        wall_us=wall_us,
        est_bytes=in_bytes + out_bytes,
        ts_us=ts_us,
    )


class DispatchLog(collections.Counter):
    """``Counter`` of op names + bounded deque of structured events.

    The counter face is bitwise-identical to the pre-PR-7 ``dispatch_log``
    (portability tests and BENCH launch-count pins diff it exactly); the
    ``events`` deque only fills while tracing is enabled.
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.events: collections.deque = collections.deque(maxlen=EVENT_CAPACITY)

    def record(self, op_name: str, event: Optional[DispatchEvent] = None) -> None:
        self[op_name] += 1
        if event is not None:
            self.events.append(event)

    def clear(self) -> None:  # tests clear counts + events as one unit
        super().clear()
        self.events.clear()
