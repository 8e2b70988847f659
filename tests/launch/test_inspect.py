"""The inspect tool's trace summary: spans, then dispatches by count and
estimated bytes (no bandwidth: a dispatch's wall time under ``jit`` is
tracing, not the device)."""

from repro.core import make_executor, registry
from repro.launch.inspect import summarize_trace
from repro.observability import trace

import jax.numpy as jnp


def test_summary_counts_dispatches_and_their_bytes():
    trace.reset()
    tracer = trace.enable()
    try:
        ex = make_executor("xla")
        x = jnp.ones(64, jnp.float32)
        with trace.span("solve"):
            for _ in range(3):
                registry.operation("blas_dot")(x, x, executor=ex)
        text = summarize_trace(tracer.to_json())
    finally:
        trace.reset()
    lines = text.splitlines()
    assert "dispatches:" in lines
    header = lines[lines.index("dispatches:") + 1].split()
    assert header == ["op", "space", "target", "count", "est_bytes"]
    row = next(line.split() for line in lines if line.startswith("blas_dot"))
    assert row[1] == "xla" and row[3] == "3" and int(row[4]) >= 3 * 2 * 64 * 4
    assert "solve" in text and "gbs" not in text and "GB/s" not in text
