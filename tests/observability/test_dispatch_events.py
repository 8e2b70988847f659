"""Structured dispatch events: the log's two faces, shapes, op scopes."""

import collections

import numpy as np
import jax.numpy as jnp
import pytest

from repro import sparse
from repro.core import make_executor, registry
from repro.observability import trace
from repro.observability.events import (
    DispatchLog,
    make_event,
    shape_bucket,
    summarize_operands,
)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.reset()
    yield
    trace.reset()


def test_dispatch_log_counter_face_is_plain_counter():
    """The Counter face must behave bitwise like the pre-observability log:
    portability tests and BENCH pins diff ``dict(ex.dispatch_log)``."""
    log = DispatchLog()
    assert isinstance(log, collections.Counter)
    log.record("spmv_csr")
    log.record("spmv_csr")
    log.record("blas_dot")
    assert dict(log) == {"spmv_csr": 2, "blas_dot": 1}
    assert log.most_common(1) == [("spmv_csr", 2)]
    assert not log.events  # no event objects without tracing
    log.clear()
    assert dict(log) == {} and not log.events


def test_shape_bucket_and_operand_summary():
    assert shape_bucket([(8,), (8, 8)]) == 64
    assert shape_bucket([(5,)]) == 8
    assert shape_bucket([]) == 1

    x = jnp.ones((16,), jnp.float32)
    shapes, nbytes = summarize_operands([x, 3, None, "label", [x, {"k": x}]])
    assert shapes == [(16,)] * 3
    assert nbytes == 3 * 16 * 4

    A = sparse.csr_from_dense(np.eye(8, dtype=np.float32))
    shapes, nbytes = summarize_operands([A])
    assert (8, 8) in shapes
    assert nbytes == A.memory_bytes  # format accounting wins over dense size


def test_events_recorded_only_while_tracing():
    ex = make_executor("xla")
    op = registry.operation("blas_norm2")
    x = jnp.ones(32, jnp.float32)
    ex.dispatch_log.clear()
    op(x, executor=ex)
    assert ex.dispatch_log["blas_norm2"] == 1
    assert len(ex.dispatch_events) == 0

    trace.enable()
    op(x, executor=ex)
    assert ex.dispatch_log["blas_norm2"] == 2
    (ev,) = ex.dispatch_events
    assert ev.op == "blas_norm2"
    assert ev.shapes == ((32,),)
    assert ev.wall_us >= 0.0
    assert ev.ts_us >= 0.0


def test_event_carries_resolved_launch_config():
    """When the kernel consults the tuning table, the event records the
    resolved LaunchConfig (the tile geometry that actually ran)."""
    ex = make_executor("pallas_interpret")
    a = np.eye(16, dtype=np.float32)
    A = sparse.ell_from_dense(a)
    trace.enable()
    ex.dispatch_log.clear()
    sparse.apply(A, jnp.ones(16, jnp.float32), executor=ex)
    events = [e for e in ex.dispatch_events if e.op == "spmv_ell"]
    assert events
    launches = [e.launch for e in events if e.launch is not None]
    if launches:  # kernels that consulted launch_config expose the geometry
        assert isinstance(launches[0], dict) and launches[0]


def test_traced_dispatch_feeds_no_metric_series():
    """A traced dispatch records its event and Chrome span, and derives no
    bandwidth: nothing lands in the metrics registry."""
    from repro.observability import metrics

    metrics.reset()
    ex = make_executor("xla")
    tracer = trace.enable()
    registry.operation("blas_dot")(jnp.ones(8), jnp.ones(8), executor=ex)
    assert [e.op for e in ex.dispatch_events] == ["blas_dot"]
    assert [ev["cat"] for ev in tracer.events] == ["dispatch"]
    assert not hasattr(ex.dispatch_events[0], "gbs")
    assert metrics.samples() == []


def test_event_deque_is_bounded():
    from repro.observability.events import EVENT_CAPACITY

    log = DispatchLog()
    for i in range(EVENT_CAPACITY + 10):
        log.record("op", event=object())
    assert len(log.events) == EVENT_CAPACITY
    assert log["op"] == EVENT_CAPACITY + 10  # counts are never dropped
