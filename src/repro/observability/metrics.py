"""Metrics registry: counters, gauges, histograms with JSONL/table export.

A minimal, dependency-free metrics substrate for the repo's telemetry —
enough for the benchmark harness to publish achieved GB/s per
op x format x executor live, and for the future solve-server to report
latency percentiles, without inventing ad-hoc dicts in every module.

* :class:`Counter` — monotonically increasing (dispatch counts, iterations);
* :class:`Gauge` — last-write-wins (achieved GB/s, frac-of-bound);
* :class:`Histogram` — count/sum/min/max + power-of-two bucket counts
  (wall-time distributions; pow2 buckets match the shape buckets used by
  dispatch events).

Metrics are named and labelled (``gauge("spmv_gbs", op="spmv_csr",
executor="xla")``); a ``(name, labels)`` pair identifies one time series.
Exporters: :func:`export_jsonl` (one JSON object per series, greppable and
CI-artifact-friendly) and :func:`render_table` (aligned human table).
"""

from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "counter",
    "gauge",
    "histogram",
    "samples",
    "export_jsonl",
    "render_table",
    "reset",
]


#: smallest sub-unit bucket exponent: values below 2^-30 (~0.93 ns when the
#: unit is seconds) clamp into the 2^-30 bucket.
_MIN_BUCKET_EXP = -30


def _bucket_of(v: float):
    """Power-of-two bucket upper bound containing ``v``.

    Buckets ``>= 1`` keep their historical integer labels (1, 2, 4, ...);
    values in ``(0, 1]`` land in fractional buckets ``2^-1 .. 2^-30`` (the
    smallest bucket also absorbs everything at or below ``2^-30``, including
    non-positive values).  Without the sub-unit buckets every wall-time
    histogram measured in seconds collapsed into the ``1`` bin, making
    p50/p99 unreadable — exactly the statistics the solve-serve loop reports.
    """
    if v > 1:
        b = 1
        while b < v and b < (1 << 62):
            b <<= 1
        return b
    if v > 0.5:
        return 1
    floor = 2.0 ** _MIN_BUCKET_EXP
    b = 0.5
    while b * 0.5 >= v and b > floor:
        b *= 0.5
    return b


class Counter:
    __slots__ = ("value",)

    kind = "counter"

    def __init__(self):
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up; use a gauge")
        self.value += amount

    def sample(self) -> Dict[str, Any]:
        return {"value": self.value}


class Gauge:
    __slots__ = ("value",)

    kind = "gauge"

    def __init__(self):
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)

    def sample(self) -> Dict[str, Any]:
        return {"value": self.value}


class Histogram:
    __slots__ = ("count", "sum", "min", "max", "buckets")

    kind = "histogram"

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.buckets: Dict[int, int] = {}

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.sum += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        b = _bucket_of(max(value, 0.0))
        self.buckets[b] = self.buckets.get(b, 0) + 1

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def quantile(self, q: float) -> Optional[float]:
        """Upper bound of the bucket holding the ``q``-quantile (0 <= q <= 1).

        Resolution is one power of two — coarse, but monotone and cheap, and
        with the sub-unit buckets it distinguishes microseconds from
        milliseconds from seconds, which is what a p50/p99 latency report
        needs.  Returns None on an empty histogram.
        """
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        if not self.count:
            return None
        target = q * self.count
        cum = 0
        bound = None
        for b in sorted(self.buckets):
            bound = b
            cum += self.buckets[b]
            if cum >= target:
                break
        return float(bound)

    def sample(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "sum": self.sum,
            "min": self.min if self.count else None,
            "max": self.max if self.count else None,
            "mean": self.mean,
            "buckets": {str(k): v for k, v in sorted(self.buckets.items())},
        }


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricsRegistry:
    """Holds named, labelled metric series; thread-safe get-or-create."""

    def __init__(self):
        self._series: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Any] = {}
        self._lock = threading.Lock()

    def _get(self, kind: str, name: str, labels: Dict[str, Any]):
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        with self._lock:
            m = self._series.get(key)
            if m is None:
                m = self._series[key] = _KINDS[kind]()
            elif m.kind != kind:
                raise TypeError(
                    f"metric {name!r}{dict(key[1])} already registered as "
                    f"{m.kind}, requested {kind}"
                )
            return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get("histogram", name, labels)

    # -- export ---------------------------------------------------------------
    def samples(self) -> List[Dict[str, Any]]:
        with self._lock:
            items = sorted(self._series.items())
        out = []
        for (name, labels), metric in items:
            rec = {"name": name, "kind": metric.kind, "labels": dict(labels)}
            rec.update(metric.sample())
            out.append(rec)
        return out

    def export_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            for rec in self.samples():
                f.write(json.dumps(rec, default=str))
                f.write("\n")
        return path

    def render_table(self) -> str:
        rows = []
        for rec in self.samples():
            labels = ",".join(f"{k}={v}" for k, v in sorted(rec["labels"].items()))
            if rec["kind"] == "histogram":
                val = (
                    f"n={rec['count']} mean={rec['mean']:.3g} "
                    f"min={rec['min']:.3g} max={rec['max']:.3g}"
                    if rec["count"]
                    else "n=0"
                )
            else:
                val = f"{rec['value']:.6g}"
            rows.append((rec["name"], labels, rec["kind"], val))
        if not rows:
            return "(no metrics recorded)"
        widths = [max(len(r[i]) for r in rows) for i in range(3)]
        header = ("metric".ljust(widths[0]), "labels".ljust(widths[1]),
                  "kind".ljust(widths[2]), "value")
        lines = ["  ".join(header)]
        lines.append("  ".join("-" * len(h) for h in header))
        for r in rows:
            lines.append("  ".join(
                (r[0].ljust(widths[0]), r[1].ljust(widths[1]),
                 r[2].ljust(widths[2]), r[3])))
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._series.clear()


_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _DEFAULT


def counter(name: str, **labels) -> Counter:
    return _DEFAULT.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _DEFAULT.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    return _DEFAULT.histogram(name, **labels)


def samples() -> List[Dict[str, Any]]:
    return _DEFAULT.samples()


def export_jsonl(path: str) -> str:
    return _DEFAULT.export_jsonl(path)


def render_table() -> str:
    return _DEFAULT.render_table()


def reset() -> None:
    _DEFAULT.reset()


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    """Read back an exported metrics JSONL file (inspect tool, tests)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records
