"""Observability: tracing, dispatch events, metrics, convergence telemetry.

The repo's ``gko::log`` layer.  Four pieces, each usable alone:

* :mod:`repro.observability.trace` — span tracer with Chrome trace-event
  export (``REPRO_TRACE=1`` or ``--trace out.json`` on launch drivers);
* :mod:`repro.observability.events` — structured dispatch events behind
  ``Executor.dispatch_log`` (the Counter face is a derived view);
* :mod:`repro.observability.metrics` — counters/gauges/histograms with
  JSONL and table exporters;
* :mod:`repro.observability.convergence` — jit-safe residual-history ring
  buffer powering the ``history=`` option on every solver.

``trace``/``events``/``metrics`` are stdlib-only so the core dispatch layer
can import them unconditionally; ``convergence`` needs ``jax.numpy`` and is
imported lazily here.
"""

from repro.observability import events, metrics, trace
from repro.observability.events import DispatchEvent, DispatchLog
from repro.observability.trace import span, validate_trace

__all__ = [
    "events",
    "metrics",
    "trace",
    "convergence",
    "DispatchEvent",
    "DispatchLog",
    "span",
    "validate_trace",
]


def __getattr__(name):
    if name == "convergence":
        import importlib

        return importlib.import_module("repro.observability.convergence")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
