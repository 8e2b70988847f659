"""Span-based tracer with Chrome trace-event export — the ``gko::log`` analogue.

Ginkgo's logging subsystem hangs Logger objects off executors and operations so
every allocation, kernel launch, and solver iteration can be observed without
touching algorithm code.  This module is that seam for the repo: a process-wide
tracer that

* records **nested spans** (``with trace.span("solve", n=4096): ...``) and
  **complete events** (used by the dispatch layer for per-op kernel records);
* costs **near zero when disabled** — the dispatch hot path reads one module
  attribute (:data:`TRACING`) and ``span()`` returns a shared no-op context
  manager, no allocation, no clock read;
* exports the **Chrome trace-event JSON** format (``{"traceEvents": [...]}``),
  viewable in Perfetto / ``chrome://tracing``.

Activation:

* ``REPRO_TRACE=1`` in the environment enables tracing at import time and
  registers an atexit export to ``REPRO_TRACE_PATH`` (default
  ``repro_trace.json``);
* every driver in :mod:`repro.launch` takes ``--trace out.json``;
* programmatic: ``with trace.tracing("out.json"): ...`` or
  ``trace.enable(...)`` / ``trace.export()`` / ``trace.disable()``.

While tracing is enabled, every span also enters
``jax.profiler.TraceAnnotation(name)``: under a profiler session
(``jax.profiler.trace``) it lands on the host plane of the same ``.xplane.pb``
as the device ops, on the device trace's clock, so a device idle gap can be
named by the host step that held it.  JAX is imported on the first enabled
span, so this module stays importable without it.

Timing caveat (documented, deliberate): under ``jit``, registered operations
run once at *trace time* — dispatch events therefore measure dispatch/trace
cost and launch *structure* (counts, shapes, geometry), while wall-clock truth
lives in the driver-level spans that wrap ``block_until_ready`` and in the
profiler's device trace.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "TRACING",
    "Tracer",
    "enable",
    "disable",
    "enabled",
    "get_tracer",
    "span",
    "instant",
    "export",
    "tracing",
    "validate_trace",
    "maybe_enable_from_env",
    "ENV_FLAG",
    "ENV_PATH",
]

#: fast-path flag read by the dispatch layer on every operation call.  Module
#: attribute access is the cheapest check Python offers short of inlining.
TRACING: bool = False

ENV_FLAG = "REPRO_TRACE"
ENV_PATH = "REPRO_TRACE_PATH"
DEFAULT_PATH = "repro_trace.json"

_TRACER: Optional["Tracer"] = None
_EXPORT_PATH: Optional[str] = None
_ATEXIT_REGISTERED = False
_LOCK = threading.Lock()

#: phases understood by the Chrome trace-event format that we emit/validate.
_VALID_PHASES = ("X", "i", "I", "B", "E", "C", "M")


class _NullSpan:
    """Shared no-op context manager returned by ``span()`` when disabled.

    A singleton with empty ``__slots__``: entering/exiting allocates nothing,
    which is what the overhead-guard test pins.
    """

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, or ``None`` without JAX."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation(name)


class _Span:
    """Open span: a profiler annotation while open, and one complete ("X")
    event on exit."""

    __slots__ = ("tracer", "name", "cat", "args", "start_us", "annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.start_us = 0.0
        self.annotation = None

    def __enter__(self):
        self.annotation = _annotation(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.start_us = self.tracer.now_us()
        return self

    def annotate(self, **args) -> None:
        """Add ``args`` to the event, for what is known only inside the span."""
        self.args.update(args)

    def __exit__(self, *exc):
        end = self.tracer.now_us()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        self.tracer.complete(
            self.name, self.start_us, end - self.start_us,
            cat=self.cat, args=self.args,
        )
        return False


class Tracer:
    """Accumulates trace events; one per process is the normal arrangement."""

    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self.t0 = time.perf_counter()
        self.pid = os.getpid()

    # -- clock ----------------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    def rel_us(self, perf_counter_s: float) -> float:
        """Convert an absolute ``time.perf_counter()`` stamp to trace time."""
        return (perf_counter_s - self.t0) * 1e6

    # -- event emission -------------------------------------------------------
    def _emit(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self.events.append(ev)

    def complete(
        self,
        name: str,
        start_us: float,
        dur_us: float,
        *,
        cat: str = "span",
        args: Optional[dict] = None,
    ) -> None:
        """Record a complete ("X") event: a closed [start, start+dur) span."""
        self._emit({
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": round(start_us, 3),
            "dur": round(max(dur_us, 0.0), 3),
            "pid": self.pid,
            "tid": threading.get_ident(),
            "args": args or {},
        })

    def instant(self, name: str, *, cat: str = "instant", **args) -> None:
        self._emit({
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "t",
            "ts": round(self.now_us(), 3),
            "pid": self.pid,
            "tid": threading.get_ident(),
            "args": args,
        })

    def span(self, name: str, *, cat: str = "span", **args) -> _Span:
        return _Span(self, name, cat, args)

    # -- export ---------------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            events = list(self.events)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.observability.trace"},
        }

    def export(self, path: str) -> str:
        data = self.to_json()
        with open(path, "w") as f:
            # default=str: span args may carry dtypes/shapes/dataclasses
            json.dump(data, f, default=str)
            f.write("\n")
        return path


# =============================================================================
# module-level switchboard
# =============================================================================


def enable(path: Optional[str] = None) -> Tracer:
    """Turn tracing on (idempotent).  ``path`` registers an atexit export."""
    global TRACING, _TRACER, _EXPORT_PATH, _ATEXIT_REGISTERED
    with _LOCK:
        if _TRACER is None:
            _TRACER = Tracer()
        if path is not None:
            _EXPORT_PATH = path
            if not _ATEXIT_REGISTERED:
                atexit.register(_export_at_exit)
                _ATEXIT_REGISTERED = True
        TRACING = True
        return _TRACER


def disable() -> None:
    global TRACING
    TRACING = False


def enabled() -> bool:
    return TRACING


def get_tracer() -> Optional[Tracer]:
    """The active tracer, or None when tracing has never been enabled."""
    return _TRACER


def reset() -> None:
    """Drop the tracer and its events (tests)."""
    global TRACING, _TRACER, _EXPORT_PATH
    with _LOCK:
        TRACING = False
        _TRACER = None
        _EXPORT_PATH = None


def span(name: str, *, cat: str = "span", **args):
    """A span context manager — the shared no-op singleton when disabled."""
    if not TRACING or _TRACER is None:
        return _NULL_SPAN
    return _TRACER.span(name, cat=cat, **args)


def instant(name: str, *, cat: str = "instant", **args) -> None:
    if TRACING and _TRACER is not None:
        _TRACER.instant(name, cat=cat, **args)


def export(path: Optional[str] = None) -> Optional[str]:
    """Write the accumulated trace; ``None`` uses the configured path."""
    target = path or _EXPORT_PATH
    if _TRACER is None or target is None:
        return None
    return _TRACER.export(target)


def _export_at_exit() -> None:
    try:
        export()
    except Exception:
        pass  # never let telemetry break process teardown


class _TracingContext:
    def __init__(self, path: Optional[str]):
        self.path = path

    def __enter__(self) -> Tracer:
        return enable(self.path)

    def __exit__(self, *exc):
        if self.path is not None:
            export(self.path)
        disable()
        return False


def tracing(path: Optional[str] = None) -> _TracingContext:
    """``with trace.tracing("out.json"):`` — enable, run, export, disable."""
    return _TracingContext(path)


# =============================================================================
# validation — the CI trace-schema gate
# =============================================================================


def validate_trace(data) -> List[str]:
    """Validate a Chrome trace-event object (or a path to one).

    Returns a list of human-readable problems; empty means valid.  Checks the
    envelope and per-event requirements Perfetto relies on: ``name``/``ph``/
    ``ts`` everywhere, ``dur`` on complete events, integer ``pid``/``tid``.
    """
    errors: List[str] = []
    if isinstance(data, (str, os.PathLike)):
        try:
            with open(data) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"unreadable trace file: {e}"]
    if not isinstance(data, dict):
        return [f"top level must be an object, got {type(data).__name__}"]
    events = data.get("traceEvents")
    if not isinstance(events, list):
        return ["missing 'traceEvents' list"]
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        name = ev.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: missing/empty 'name'")
        ph = ev.get("ph")
        if ph not in _VALID_PHASES:
            errors.append(f"{where}: bad phase {ph!r}")
        if not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"{where}: missing numeric 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: complete event needs 'dur' >= 0")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                errors.append(f"{where}: missing integer {key!r}")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"{where}: 'args' must be an object")
        if len(errors) > 50:
            errors.append("... (truncated)")
            break
    return errors


def add_cli_flag(parser) -> None:
    """Attach the standard ``--trace out.json`` flag to a launch driver."""
    parser.add_argument(
        "--trace",
        metavar="OUT_JSON",
        default=None,
        help="write a Chrome trace-event file (perfetto-viewable) of this run",
    )


def enable_from_args(args) -> Optional[str]:
    """Honor a parsed ``--trace`` flag; returns the export path if enabled.

    Drivers call this right after ``parse_args`` and :func:`export` before
    returning (the atexit hook is only the backstop for abnormal exits).
    """
    path = getattr(args, "trace", None)
    if path:
        enable(path)
        return path
    return None


def maybe_enable_from_env() -> bool:
    """Honor ``REPRO_TRACE=1`` (export to ``REPRO_TRACE_PATH`` at exit)."""
    flag = os.environ.get(ENV_FLAG, "").strip().lower()
    if flag in ("1", "true", "yes", "on"):
        enable(os.environ.get(ENV_PATH, DEFAULT_PATH))
        return True
    return False


maybe_enable_from_env()
