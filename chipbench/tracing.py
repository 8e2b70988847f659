"""From a profiler trace to device busy time, op sums, collectives and gaps.

JAX's profiler writes an ``.xplane.pb``; :func:`read_xspace` keeps the two
things the metrics read from it: every device op (the ``XLA Ops`` line of
each ``/device:TPU:<i>`` plane) and the harness's own host spans (names in
``library.SPANS``, plus ``window``).  The reduction works on those plain
lists, so a test can hand it a synthetic trace.

- busy: the union of a device's op intervals inside the window;
- idle share: ``1 - busy / window``;
- op sums: seconds per op, less the ops nested in it, averaged over the
  devices;
- collective seconds: ops whose HLO opcode is a collective;
- gaps: the stretches of the window in which a device ran nothing, each
  named by the harness span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: device planes, and the line of each that holds one event per op executed
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: substrings of an HLO op name that make it a collective
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
WINDOW_SPAN = "window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds, on the trace's clock
    end: float
    collective: bool = False


@dataclasses.dataclass
class Trace:
    devices: dict  # device id -> [Event] (ops)
    spans: list  # [Event] host spans of the harness


#: ``%<id> = <shape> <opcode>(`` at the head of an op's HLO text
OP_TEXT = re.compile(r"^%?([\w.\-]+) = (.+?) ([\w\-]+)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
LABEL_CHARS = 96


def is_collective(name: str) -> bool:
    """An op that moves data between chips, by its HLO opcode."""
    m = OP_TEXT.match(name)
    opcode = m.group(3) if m else name
    return any(c in opcode.lower() for c in COLLECTIVES)


def newest_xspace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def op_label(hlo: str) -> str:
    """``%fusion.12 = f32[14680064]{0:T(1024)} fusion(...)`` ->
    ``fusion.12 fusion f32[14680064]``: id, opcode, result shape, no layout."""
    m = OP_TEXT.match(hlo)
    if not m:
        return hlo[:LABEL_CHARS]
    ident, shape, opcode = m.groups()
    shape = "(tuple)" if shape.startswith("(") else LAYOUT.sub("", shape)
    return f"{ident} {opcode} {shape}"[:LABEL_CHARS]


def read_xspace(path: str, span_names) -> Trace:
    """Device ops and harness spans of one profile."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {WINDOW_SPAN}
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    Event(op_label(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9,
                          is_collective(e.name))
                    for e in line.events
                )
            elif plane.name.startswith("/host:"):
                spans.extend(
                    Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events if e.name in wanted
                )
    return Trace(devices, spans)


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window_of(trace: Trace) -> tuple:
    """The harness's ``window`` span."""
    for s in trace.spans:
        if s.name == WINDOW_SPAN:
            return s.start, s.end
    raise ValueError("the trace holds no window span")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: dict  # device -> seconds
    collective_s: dict  # device -> seconds
    op_s: dict  # op name -> seconds, mean over devices
    gaps: list  # [(name, seconds)] of the first device, longest first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def summarize(trace: Trace) -> Summary:
    """Busy, collective and op seconds of every device in the window span,
    and the first device's idle gaps."""
    if not trace.devices:
        raise ValueError("the trace holds no device op")
    lo, hi = window_of(trace)
    busy, coll, op_s = {}, {}, {}
    for dev, events in trace.devices.items():
        inside = [e for e in events if e.end > lo and e.start < hi]
        busy[dev] = sum(e - s for s, e in merge(clip(
            [(e.start, e.end) for e in inside], lo, hi)))
        coll[dev] = sum(e - s for s, e in merge(clip(
            [(e.start, e.end) for e in inside if e.collective], lo, hi)))
        for e, own in self_times(inside, lo, hi):
            op_s[e.name] = op_s.get(e.name, 0.0) + own / len(trace.devices)
    first = min(trace.devices)
    return Summary(hi - lo, busy, coll, op_s,
                   name_gaps(trace.devices[first], trace.spans, lo, hi))


def self_times(events, lo: float, hi: float) -> list:
    """``[(event, seconds)]`` inside ``[lo, hi]`` less the time of the ops
    nested in it: a ``while`` op spans its whole loop, whose body ops are
    events of their own on the same line."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    own = {id(e): min(e.end, hi) - max(e.start, lo) for e in ordered}
    stack = []
    for e in ordered:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            own[id(stack[-1])] -= min(e.end, hi) - max(e.start, lo)
        stack.append(e)
    return [(e, own[id(e)]) for e in ordered]


def name_gaps(events, spans, lo: float, hi: float) -> list:
    """Idle stretches of one device in ``[lo, hi]``, longest first, each named
    by the harness span that overlaps it most (``host`` where none does)."""
    busy = merge(clip([(e.start, e.end) for e in events], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for g0, g1 in gaps:
        best, overlap = "host", 0.0
        for sp in spans:
            if sp.name == WINDOW_SPAN:
                continue
            o = min(g1, sp.end) - max(g0, sp.start)
            if o > overlap:
                best, overlap = sp.name, o
        named.append((best, g1 - g0))
    return sorted(named, key=lambda p: -p[1])


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(summary.op_s.items(), key=lambda p: -p[1])[:top]
    return {
        "device_ops": [[name, sec] for name, sec in ops],
        "idle_gaps": [[name, sec] for name, sec in summary.gaps[:top]],
    }


def device_seconds(trace: Trace) -> float:
    """Busy seconds of all devices' ops over the whole trace, mean per
    device: the device time of a program run alone under the profiler."""
    if not trace.devices:
        raise ValueError("the trace holds no device op")
    return sum(
        sum(e - s for s, e in merge([(x.start, x.end) for x in evs]))
        for evs in trace.devices.values()
    ) / len(trace.devices)
