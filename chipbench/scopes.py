"""The program's own names on a profiler trace: device ops by the scope they
ran in, host steps by the program's spans.

The program names its seams itself.  Every dispatched op runs in
``jax.named_scope(<op name>)`` and every ``LinOp.apply`` in
``<ClassName>.apply``; the distributed matvec names ``DistEll.halo_exchange``,
``.interior``, ``.boundary`` and ``.halo``.  XLA keeps the scopes in the
``op_name`` metadata of each HLO instruction, and the profiler stores the
optimized HLO of every program it saw run on its ``/host:metadata`` plane
(``Hlo Proto``).  While the program's tracer is on, each
``repro.observability.trace.span`` also enters a
``jax.profiler.TraceAnnotation``, so it lands on the host plane beside the
harness's spans, on the device ops' clock.

:func:`read` joins the two: each device op gets the ``op_name`` of its
instruction in the program it ran in (the enclosing ``XLA Modules`` event),
and the program's spans are kept apart from the harness's.  The reductions
then follow the program's names inside the fused loop, where a probe jitted
alone cannot look, and keep following them after a refactor renumbers
XLA's ops:

- :func:`scope_seconds`: self seconds of the device ops under any of some
  scopes in the window, mean over the devices the profile names;
- :func:`span_seconds`: seconds of one program span in the window;
- :func:`program_gaps`: the first device's idle gaps, each named by the
  innermost program or harness span that overlaps it most.

``run.py`` loads per-layer metrics only for a ``--trace 1`` run, before
set-up; the metrics that read these names call :func:`enable_for_traced_run`
as they are loaded, which turns the program's spans on there.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import sys
import typing

from chipbench import library, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
#: the ``XLA Ops`` line's sibling that holds one event per program run
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
NO_SPAN = "host"


@dataclasses.dataclass(frozen=True)
class ScopedEvent(tracing.Event):
    #: the HLO op_name, ``jit(f)/while/body/<scope>/.../<op>`` ("" where the
    #: instruction has none); ``None`` where the profile does not name the
    #: op's instruction in the program it filed the run under
    scope: typing.Optional[str] = ""


@dataclasses.dataclass
class ScopedTrace:
    devices: dict  # device id -> [ScopedEvent] (ops)
    spans: list  # [tracing.Event] host spans of the harness, with the window
    program_spans: list  # [tracing.Event] host spans of the program


# -- enabling -------------------------------------------------------------------
def enable() -> None:
    """The program's spans on (onto the profiler's host plane), and HLO
    metadata in the compile cache's key, so that no executable compiled
    without the scopes is loaded in their place."""
    import jax

    from repro.observability import trace

    trace.enable()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def enable_for_traced_run() -> None:
    """:func:`enable` where the benchmark's entry point is running: it loads
    per-layer metrics only for a ``--trace 1`` run, before set-up.  Anywhere
    else (a test loading every metric) it does nothing."""
    main = getattr(sys.modules.get("__main__"), "__file__", None)
    if main and os.path.realpath(main) == os.path.realpath(os.path.join(HERE, "run.py")):
        enable()


def program_span_names() -> set:
    """Names of the spans the program's tracer has recorded."""
    from repro.observability import trace

    tracer = trace.get_tracer()
    if tracer is None:
        return set()
    return {ev["name"] for ev in list(tracer.events) if ev.get("cat") != "dispatch"}


# -- reading --------------------------------------------------------------------
def _fields(buf: bytes):
    """``(field number, value)`` of a serialized protobuf message: an int for
    a varint, bytes for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            return  # groups: not in these messages
        yield key >> 3, value


def _varint(buf: bytes, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _text(value) -> str:
    return value.decode("utf-8", "replace") if isinstance(value, bytes) else ""


def hlo_op_names(xspace: bytes) -> dict:
    """``{module: {instruction: op_name}}`` of every HLO module the profile
    stored, under the name its runs carry on the ``XLA Modules`` line
    (``jit_f(<program id>)``).

    Fields: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry:
    key 1, value 2), .stat_metadata 5; XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1,
    .bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2.
    """
    modules = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        # the name comes second, after the id: only the metadata plane is
        # read through
        if _text(next((v for g, v in _fields(plane) if g == 2), b"")) != METADATA_PLANE:
            continue
        fields = list(_fields(plane))
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                meta = dict(_fields(next((v for h, v in _fields(entry) if h == 2), b"")))
                stat_names[meta.get(1)] = _text(meta.get(2))
        for g, entry in fields:
            if g != 4:
                continue
            meta = next((v for h, v in _fields(entry) if h == 2), b"")
            name, protos = "", []
            for h, v in _fields(meta):
                if h == 2:
                    name = _text(v)
                elif h == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        protos.append(stat.get(6, b""))
            for proto in protos:
                modules[name] = _instruction_op_names(proto)
    return modules


def _instruction_op_names(hlo_proto: bytes) -> dict:
    ops = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                name, op_name = "", ""
                for k, v in _fields(inst):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = _text(dict(_fields(v)).get(2, b""))
                ops[name] = op_name
    return ops


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` (TPU) or ``fusion.12`` (CPU) ->
    ``fusion.12``."""
    m = tracing.OP_TEXT.match(event_name)
    return m.group(1) if m else event_name.lstrip("%")


def read(path: str, span_names, program_names) -> ScopedTrace:
    """Device ops with their scopes, harness spans and program spans of one
    profile."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    modules = hlo_op_names(raw)
    harness = set(span_names) | {tracing.WINDOW_SPAN}
    program = set(program_names) - harness
    devices, spans, program_spans = {}, [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            runs = _module_runs(lines.get(MODULES_LINE))
            ops = devices.setdefault(int(m.group(1)), [])
            for e in lines[tracing.OPS_LINE].events if tracing.OPS_LINE in lines else ():
                start, end = e.start_ns * 1e-9, e.end_ns * 1e-9
                scope = modules.get(_module_at(runs, start), {}).get(instruction(e.name))
                ops.append(ScopedEvent(tracing.op_label(e.name), start, end,
                                       tracing.is_collective(e.name), scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in harness or e.name in program:
                        ev = tracing.Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        (spans if e.name in harness else program_spans).append(ev)
    return ScopedTrace(devices, spans, program_spans)


def _module_runs(line) -> tuple:
    """``(starts, [(start, end, name)])`` of a device's program runs, by start."""
    if line is None:
        return [], []
    runs = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name) for e in line.events)
    return [run[0] for run in runs], runs


def _module_at(runs: tuple, t: float) -> str:
    """The name of the program that ran at ``t`` ("" where none did)."""
    starts, runs = runs
    i = bisect.bisect_right(starts, t) - 1
    return runs[i][2] if i >= 0 and runs[i][1] >= t else ""


# -- reductions -----------------------------------------------------------------
def under(scope, scopes) -> bool:
    """Whether an op_name holds one of ``scopes`` as a whole component."""
    return bool(scope) and any(part in scopes for part in scope.split("/"))


def named_devices(trace: ScopedTrace) -> list:
    """The devices every op of which in the window the profile names: on
    four chips the profiler has been seen to file one device's run of the
    solve under another program and to name its ops ``region.<n>`` only,
    which would read as if nothing ran there under any scope."""
    lo, hi = tracing.window_of(trace)
    return sorted(d for d, events in trace.devices.items()
                  if all(e.scope is not None for e in events if e.end > lo and e.start < hi))


def scope_seconds(trace: ScopedTrace, scopes) -> float:
    """Self seconds in the window of the device ops under any of ``scopes``,
    mean over the :func:`named_devices` (0 where none ran)."""
    lo, hi = tracing.window_of(trace)
    scopes = set(scopes)
    devices = named_devices(trace)
    total = 0.0
    for d in devices:
        inside = [e for e in trace.devices[d] if e.end > lo and e.start < hi]
        total += sum(own for e, own in tracing.self_times(inside, lo, hi)
                     if under(e.scope, scopes))
    return total / len(devices) if devices else 0.0


def span_seconds(trace: ScopedTrace, name: str) -> float:
    """Seconds of the program span ``name`` inside the window."""
    lo, hi = tracing.window_of(trace)
    return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
               for s in trace.program_spans if s.name == name)


def idle_gaps(events, lo: float, hi: float) -> list:
    """``[(start, end)]`` in which one device ran nothing, in order."""
    gaps, t = [], lo
    for s, e in tracing.merge(tracing.clip([(e.start, e.end) for e in events], lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _host_spans(trace: ScopedTrace) -> list:
    return [s for s in trace.spans + trace.program_spans
            if s.name != tracing.WINDOW_SPAN]


def innermost(g0: float, g1: float, spans) -> str:
    """The span that overlaps ``[g0, g1]`` most, then among the spans inside
    it the one that overlaps most, and so on down: the innermost host step
    that held the device idle (``host`` where no span overlaps)."""
    best, candidates = None, spans
    while True:
        scored = [(min(g1, s.end) - max(g0, s.start), -(s.end - s.start), i)
                  for i, s in enumerate(candidates)]
        scored = [x for x in scored if x[0] > 0]
        if not scored:
            return best.name if best is not None else NO_SPAN
        best = candidates[max(scored)[2]]
        candidates = [s for s in candidates if s is not best
                      and best.start <= s.start and s.end <= best.end]


def program_gaps(trace: ScopedTrace) -> list:
    """``[(name, seconds)]`` of the first device's idle gaps in the window,
    longest first, each named by :func:`innermost`."""
    if not trace.devices:
        return []
    lo, hi = tracing.window_of(trace)
    spans = _host_spans(trace)
    gaps = idle_gaps(trace.devices[min(trace.devices)], lo, hi)
    return sorted(((innermost(g0, g1, spans), g1 - g0) for g0, g1 in gaps),
                  key=lambda p: -p[1])


# -- what the metrics call -------------------------------------------------------
_LOADED = {}


def load(ctx):
    """The window's :class:`ScopedTrace` of a traced run (``None`` in an
    untraced one), read once a process; the first read prints the ten
    longest idle gaps, named by program span, to standard error."""
    if ctx.summary is None:
        return None
    # the run's trace directory: ``run.py`` hands it to the Context
    path = tracing.newest_xspace(os.path.join(ctx._trace_dir, "window"))
    if path not in _LOADED:
        trace = read(path, library.SPANS, program_span_names())
        _LOADED[path] = trace
        gaps = program_gaps(trace)
        print("chipbench: idle gaps by program span: " + ", ".join(
            f"{name} {sec:.6f}" for name, sec in gaps[:10]), file=sys.stderr)
        unnamed = sorted(set(trace.devices) - set(named_devices(trace)))
        if unnamed:
            print(f"chipbench: no op of device(s) {unnamed} is named by program; "
                  f"scope metrics read devices {named_devices(trace)}", file=sys.stderr)
    return _LOADED[path]


def seconds_under(ctx, *scopes):
    """:func:`scope_seconds` of a traced run's window, or ``None`` (and one
    line on standard error) where no device op ran under any of ``scopes``."""
    trace = load(ctx)
    if trace is None:
        return None
    seconds = scope_seconds(trace, scopes)
    if seconds <= 0.0:
        print(f"chipbench: no device op under scope {' / '.join(scopes)} in the "
              "window", file=sys.stderr)
        return None
    return seconds


def loop_calls(ctx) -> int:
    """Calls of the operator, and of the preconditioner, in the window's
    solves: one per CG iteration and one for the initial residual."""
    return sum(r["iterations"] + 1 for r in ctx.requests)
