"""Metrics registry: series identity, kinds, exporters."""

import pytest

from repro.observability import metrics


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


def test_counter_gauge_histogram_basics():
    c = metrics.counter("reqs", op="spmv")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)

    g = metrics.gauge("gbs", op="spmv")
    g.set(12.5)
    g.set(10.0)
    assert g.value == 10.0

    h = metrics.histogram("wall_us", op="spmv")
    for v in (1.0, 3.0, 100.0):
        h.observe(v)
    assert h.count == 3 and h.min == 1.0 and h.max == 100.0
    assert h.mean == pytest.approx(104.0 / 3)
    assert h.buckets == {1: 1, 4: 1, 128: 1}  # pow2 upper bounds


def test_subunit_bucket_boundaries_for_wall_times():
    """The _bucket_of fix: second-scale samples no longer collapse into the
    ``1`` bucket — microsecond-scale values resolve to 2^-k bounds, pinned."""
    h = metrics.histogram("latency_s", path="serve")
    # (value, expected power-of-two upper bound)
    cases = [
        (3e-6, 2.0 ** -18),     # 1.907e-6 < 3e-6 <= 3.815e-6
        (1e-6, 2.0 ** -19),     # 9.537e-7 < 1e-6 <= 1.907e-6
        (250e-6, 2.0 ** -11),   # 2.44e-4 < 2.5e-4 <= 4.88e-4
        (0.003, 2.0 ** -8),     # 1.95e-3 < 3e-3 <= 3.9e-3
        (0.6, 1),               # (0.5, 1] keeps the historical ``1`` label
        (0.5, 0.5),
        (0.25, 0.25),
    ]
    for v, _ in cases:
        h.observe(v)
    for v, bound in cases:
        assert metrics._bucket_of(v) == bound, v
    assert sum(h.buckets.values()) == len(cases)
    # distinct second-scale magnitudes land in distinct buckets
    assert len(h.buckets) == len({b for _, b in cases})


def test_bucket_floor_and_legacy_labels():
    # everything at or below 2^-30 (incl. zero/negative) clamps to 2^-30
    floor = 2.0 ** metrics._MIN_BUCKET_EXP
    assert metrics._bucket_of(1e-12) == floor
    assert metrics._bucket_of(0.0) == floor
    assert metrics._bucket_of(floor) == floor
    # >= 1 buckets keep their integer labels exactly as before the fix
    assert metrics._bucket_of(1.0) == 1
    assert metrics._bucket_of(3.0) == 4
    assert metrics._bucket_of(100.0) == 128
    assert isinstance(metrics._bucket_of(3.0), int)
    # sample() stringifies mixed int/float bucket keys without conflict
    h = metrics.histogram("mixed")
    h.observe(0.003)
    h.observe(3.0)
    keys = set(h.sample()["buckets"])
    assert str(2.0 ** -8) in keys and "4" in keys


def test_histogram_quantile():
    h = metrics.histogram("q")
    assert h.quantile(0.5) is None
    for v in (1e-6,) * 50 + (1e-3,) * 45 + (0.8,) * 5:
        h.observe(v)
    assert h.quantile(0.5) == 2.0 ** -19   # median is a microsecond sample
    assert h.quantile(0.99) == 1.0         # p99 reaches the second-scale tail
    assert h.quantile(1.0) == 1.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_series_identity_and_kind_conflicts():
    # same (name, labels) -> same object; label order must not matter
    a = metrics.counter("n", op="x", space="xla")
    b = metrics.counter("n", space="xla", op="x")
    assert a is b
    assert metrics.counter("n", op="y") is not a
    with pytest.raises(TypeError):
        metrics.gauge("n", op="x", space="xla")


def test_jsonl_roundtrip_and_table(tmp_path):
    metrics.counter("dispatch_total", op="spmv_csr").inc(4)
    metrics.gauge("gbs", op="spmv_csr").set(1.25)
    metrics.histogram("wall", op="spmv_csr").observe(7.0)
    path = str(tmp_path / "m.jsonl")
    metrics.export_jsonl(path)
    records = metrics.load_jsonl(path)
    assert len(records) == 3
    by_name = {r["name"]: r for r in records}
    assert by_name["dispatch_total"]["value"] == 4
    assert by_name["dispatch_total"]["labels"] == {"op": "spmv_csr"}
    assert by_name["wall"]["count"] == 1

    table = metrics.render_table()
    assert "dispatch_total" in table and "op=spmv_csr" in table
    assert metrics.render_table() != "(no metrics recorded)"
    metrics.reset()
    assert metrics.render_table() == "(no metrics recorded)"
