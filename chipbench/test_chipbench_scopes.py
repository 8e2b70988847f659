"""The program's names on a trace: op scopes, program spans, and the
per-layer metrics that read them."""

import glob
import os
import types

import pytest

from chipbench import roofline, scopes
from chipbench.bench import Bench
from chipbench.scopes import ScopedEvent, ScopedTrace
from chipbench.tracing import Event

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP = "jit(_solve_one_chip)/while/body/"


def synthetic() -> ScopedTrace:
    """A 20 s window on two devices.  Device 0: a CG iteration in a while op
    over [1, 5] (the SpMV gather and kernel, the block-Jacobi gather, apply
    and scatter), then the initial residual's SpMV over [12, 13].  Device 1:
    a SpMV gather over [1, 2] and the halo all-gather over [2, 2.5].  Device
    0 idles over [5, 12], while the host converts and generates, and over
    [13, 20], after the solve."""
    d0 = [
        ScopedEvent("while.1 while (tuple)", 1.0, 5.0, scope="jit(_solve_one_chip)/while"),
        ScopedEvent("fusion.12 fusion f32[8]", 1.0, 3.0,
                    scope=LOOP + "spmv_dot_ell/jit(spmv_dot_ell)/gather"),
        ScopedEvent("spmv_dot_ell custom-call", 3.0, 3.5,
                    scope=LOOP + "spmv_dot_ell/jit(spmv_dot_ell)/spmv_dot_ell"),
        ScopedEvent("fusion.13 fusion f32[8]", 3.5, 4.0, scope=LOOP + "BlockJacobi.apply/gather"),
        ScopedEvent("block_jacobi_apply custom-call", 4.0, 4.5,
                    scope=LOOP + "BlockJacobi.apply/block_jacobi_apply/pallas_call"),
        ScopedEvent("fusion.14 fusion f32[8]", 4.5, 5.0, scope=LOOP + "BlockJacobi.apply/gather"),
        ScopedEvent("fusion.5 fusion f32[8]", 12.0, 13.0,
                    scope="jit(_solve_one_chip)/Ell.apply/spmv_ell/gather"),
    ]
    d1 = [
        ScopedEvent("fusion.12 fusion f32[8]", 1.0, 2.0,
                    scope=LOOP + "spmv_dot_ell/jit(spmv_dot_ell)/gather"),
        ScopedEvent("all-gather.3 all-gather f32[8]", 2.0, 2.5, collective=True,
                    scope=LOOP + "MatrixFreeOp.apply/DistEll.halo_exchange/all_gather"),
    ]
    spans = [
        Event("window", 0.0, 20.0),
        Event("convert", 5.0, 8.0),
        Event("generate", 8.0, 12.0),
        Event("solve", 12.0, 13.0),
        Event("d2h", 13.0, 14.0),
    ]
    program = [
        Event("sparse.ell_from_csr_host", 5.2, 7.8),
        Event("block_jacobi.generate", 8.1, 11.9),
        Event("block_jacobi.extract", 8.2, 10.5),
        Event("block_jacobi.invert", 10.5, 11.0),
        Event("block_jacobi.maps", 11.0, 11.5),
        Event("block_jacobi.upload", 11.5, 11.9),
    ]
    return ScopedTrace({0: d0, 1: d1}, spans, program)


def test_a_scope_is_a_whole_component_of_the_op_name():
    assert scopes.under(LOOP + "spmv_dot_ell/gather", {"spmv_dot_ell"})
    assert not scopes.under(LOOP + "spmv_dot_ell/gather", {"spmv_dot"})
    assert not scopes.under("", {"spmv_dot_ell"})


@pytest.mark.parametrize("names, seconds", [
    (("spmv_dot_ell",), (2.5 + 1.0) / 2),
    (("spmv_dot_ell", "spmv_ell"), (3.5 + 1.0) / 2),
    (("BlockJacobi.apply",), 1.5 / 2),
    (("DistEll.halo_exchange",), 0.5 / 2),
    (("DistEll.boundary",), 0.0),
])
def test_scope_seconds_take_self_time_and_average_over_devices(names, seconds):
    assert scopes.scope_seconds(synthetic(), names) == pytest.approx(seconds)


def test_scope_seconds_keep_to_the_window():
    trace = synthetic()
    trace.spans[0] = Event("window", 2.0, 12.5)
    # device 0: gather [2, 3], kernel [3, 3.5], initial SpMV [12, 12.5]
    assert scopes.scope_seconds(trace, ("spmv_dot_ell", "spmv_ell")) == pytest.approx(
        (2.0 + 0.0) / 2)
    assert scopes.span_seconds(trace, "sparse.ell_from_csr_host") == pytest.approx(2.6)
    trace.spans[0] = Event("window", 6.0, 20.0)
    assert scopes.span_seconds(trace, "sparse.ell_from_csr_host") == pytest.approx(1.8)


def test_a_device_the_profile_leaves_unnamed_is_left_out():
    """A device whose ops carry no program names (``region.<n>``) would read
    as if nothing ran there under any scope."""
    t = synthetic()
    t.devices[2] = [ScopedEvent("fusion.12 fusion f32[8]", 0.5, 0.8, scope=LOOP + "spmv_dot_ell"),
                    ScopedEvent("region.84", 1.0, 3.0, scope=None)]
    assert scopes.named_devices(t) == [0, 1]
    assert scopes.scope_seconds(t, ("spmv_dot_ell",)) == pytest.approx((2.5 + 1.0) / 2)


def test_program_gaps_name_each_gap_by_its_innermost_span():
    assert scopes.program_gaps(synthetic()) == [
        ("block_jacobi.extract", pytest.approx(7.0)),
        ("d2h", pytest.approx(7.0)),
        ("host", pytest.approx(1.0)),
    ]


def test_innermost_descends_through_the_span_that_overlaps_most():
    t = synthetic()
    spans = t.spans[1:] + t.program_spans
    assert scopes.innermost(5.0, 8.0, spans) == "sparse.ell_from_csr_host"
    assert scopes.innermost(10.6, 11.2, spans) == "block_jacobi.invert"
    assert scopes.innermost(10.6, 12.0, spans) == "block_jacobi.maps"
    assert scopes.innermost(14.5, 20.0, spans) == "host"


def test_a_trace_without_devices_has_no_gaps():
    t = ScopedTrace({}, [Event("window", 0.0, 1.0)], [])
    assert scopes.program_gaps(t) == []
    assert scopes.scope_seconds(t, ("spmv_ell",)) == 0.0


def test_instruction_names_of_tpu_and_cpu_events():
    assert scopes.instruction("%fusion.12 = f32[14680064]{0:T(1024)} fusion(%p)") == "fusion.12"
    assert scopes.instruction("multiply_bitcast_fusion") == "multiply_bitcast_fusion"


def test_a_cpu_profile_gives_op_names_and_program_spans(tmp_path):
    """The HLO the profiler stores names each instruction's scope, and the
    program's spans come back on the host plane, apart from the harness's."""
    import jax
    import jax.numpy as jnp
    from repro.observability import trace

    @jax.jit
    def f(v, idx):
        with jax.named_scope("BlockJacobi.apply"):
            return v[idx] * 2.0

    x, idx = jnp.ones(64, jnp.float32), jnp.arange(64) % 7
    f(x, idx).block_until_ready()
    trace.reset()
    trace.enable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("window"):
                with trace.span("block_jacobi.extract"):
                    f(x, idx).block_until_ready()
        names = scopes.program_span_names()
    finally:
        trace.reset()
    assert names == {"block_jacobi.extract"}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        modules = scopes.hlo_op_names(fh.read())
    ops = next(v for k, v in modules.items() if isinstance(k, str) and k.startswith("jit_f("))
    assert any(scopes.under(op, {"BlockJacobi.apply"}) for op in ops.values())
    t = scopes.read(path, ("convert",), names)
    assert [s.name for s in t.spans] == ["window"]
    assert [s.name for s in t.program_spans] == ["block_jacobi.extract"]
    (window,), (span,) = t.spans, t.program_spans
    assert window.start <= span.start <= span.end <= window.end


def _ld(field: int, payload: bytes) -> bytes:
    """One length-delimited protobuf field."""
    def varint(n):
        out = b""
        while n > 0x7F:
            out += bytes([n & 0x7F | 0x80])
            n >>= 7
        return out + bytes([n])
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def _hlo_proto(instructions: dict) -> bytes:
    """An HloProto of one computation: ``{name: op_name}``."""
    comp = b"".join(_ld(2, _ld(1, name.encode()) + _ld(7, _ld(2, op.encode())))
                    for name, op in instructions.items())
    return _ld(1, _ld(3, comp))


def test_a_device_op_takes_the_scope_of_the_program_that_ran_it(tmp_path):
    """Two programs share an instruction name: each op event takes the
    op_name from the program whose ``XLA Modules`` run holds it."""
    from jax.profiler import ProfileData

    def octal(data: bytes) -> str:
        return "".join("\\%03o" % b for b in data)

    a = _hlo_proto({"fusion.12": LOOP + "spmv_dot_ell/gather"})
    b = _hlo_proto({"fusion.12": "jit(invert_blocks)/while/body/div"})
    op = "%fusion.12 = f32[8]{0} fusion(%p)"
    text = f"""
planes {{ id: 1 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_a(1)" stats {{ metadata_id: 1 bytes_value: "{octal(a)}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_b(2)" stats {{ metadata_id: 1 bytes_value: "{octal(b)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }}
    events {{ metadata_id: 2 offset_ps: 20000000 duration_ps: 10000000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 3 offset_ps: 21000000 duration_ps: 2000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_a(1)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_b(2)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{op}" }} }} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }}
    events {{ metadata_id: 2 offset_ps: 12000000 duration_ps: 5000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "block_jacobi.invert" }} }} }}
"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    t = scopes.read(str(path), (), {"block_jacobi.invert"})
    (first, second), = t.devices.values()
    assert first.name == "fusion.12 fusion f32[8]" and first.start == pytest.approx(2e-6)
    assert first.scope == LOOP + "spmv_dot_ell/gather"
    assert second.scope == "jit(invert_blocks)/while/body/div"
    assert [s.name for s in t.spans] == ["window"]
    assert [s.name for s in t.program_spans] == ["block_jacobi.invert"]
    assert scopes.scope_seconds(t, ("spmv_dot_ell",)) == pytest.approx(2e-6)
    assert scopes.program_gaps(t)[0] == ("block_jacobi.invert", pytest.approx(18e-6))


# -- the metrics ------------------------------------------------------------------
def _metrics(cell):
    return {e["name"]: m for e, m in Bench(REPO).metrics(cell, "per_layer")}


def _ctx(distributed=False, iterations=(3, 5)):
    system = types.SimpleNamespace(n=1000, nnz=6400)
    return types.SimpleNamespace(
        summary=types.SimpleNamespace(busy_s={0: 4.0, 1: 4.0}),
        requests=[{"iterations": k, "clock": {}} for k in iterations],
        config={"precond": {"kind": "block_jacobi", "block_size": 8}, "dtype": "float32"},
        lib=types.SimpleNamespace(distributed=distributed, system=system),
        peak=lambda key: 819e9,
    )


@pytest.fixture
def traced(monkeypatch):
    """Hands the metrics a trace instead of the run's profile."""
    def use(trace):
        monkeypatch.setattr(scopes, "load", lambda ctx: trace)
    return use


def test_the_loop_metrics_read_their_scopes(traced):
    traced(synthetic())
    m = _metrics("poisson3d-128-bj.rhs")
    calls = (3 + 1) + (5 + 1)
    least = roofline.spmv_min_bytes(1000, 6400, 4)
    assert m["spmv_loop_roofline"].read(_ctx()) == pytest.approx(
        100.0 * least / 819e9 / (2.25 / calls))
    assert m["precond_apply_loop_ms"].read(_ctx()) == pytest.approx(1e3 * 0.75 / calls)


def test_boundary_halo_share_and_convert_s_read_theirs(traced):
    t = synthetic()
    t.devices[0].append(ScopedEvent("fusion.26 fusion f32[8]", 13.0, 14.0,
                                    scope=LOOP + "MatrixFreeOp.apply/DistEll.boundary/gather"))
    traced(t)
    share = _metrics("poisson3d-128-jacobi-x4.rhs")["boundary_halo_share"]
    assert share.read(_ctx(distributed=True)) == pytest.approx(100.0 * (1.0 + 0.5) / 2 / 4.0)
    convert = _metrics("poisson3d-128-bj.transient")["convert_s"]
    assert convert.read(_ctx()) == pytest.approx(2.6 / 2)


@pytest.mark.parametrize("name, cell, distributed", [
    ("spmv_loop_roofline", "poisson3d-128-bj.rhs", False),
    ("precond_apply_loop_ms", "poisson3d-128-bj.rhs", False),
    ("boundary_halo_share", "poisson3d-128-jacobi-x4.rhs", True),
    ("convert_s", "poisson3d-128-bj.transient", False),
])
def test_a_metric_is_none_and_says_so_where_its_names_are_absent(
        traced, capsys, name, cell, distributed):
    t = synthetic()
    t.devices = {d: [ScopedEvent(e.name, e.start, e.end, e.collective, "jit(f)/while")
                     for e in evs] for d, evs in t.devices.items()}
    t.program_spans = []
    traced(t)
    assert _metrics(cell)[name].read(_ctx(distributed)) is None
    assert "chipbench: no " in capsys.readouterr().err


@pytest.mark.parametrize("name, cell", [
    ("spmv_loop_roofline", "poisson3d-128-bj.rhs"),
    ("precond_apply_loop_ms", "poisson3d-128-bj.transient"),
    ("boundary_halo_share", "poisson3d-128-jacobi-x4.rhs"),
    ("convert_s", "poisson3d-128-bj.transient"),
])
def test_an_untraced_run_reads_nothing(name, cell):
    ctx = _ctx(distributed=cell.endswith("x4.rhs"))
    ctx.summary = None
    assert _metrics(cell)[name].read(ctx) is None


def test_loading_the_metrics_outside_a_run_leaves_the_program_untraced():
    from repro.observability import trace

    trace.reset()
    _metrics("poisson3d-128-bj.transient")
    assert not trace.enabled()


def test_enable_turns_on_the_program_spans_and_metadata_in_the_cache_key():
    import jax
    from repro.observability import trace

    key = "jax_compilation_cache_include_metadata_in_key"
    before = jax.config.values[key]
    trace.reset()
    try:
        scopes.enable()
        assert trace.enabled() and jax.config.values[key]
        with trace.span("sparse.ell_from_csr_host"):
            pass
        assert scopes.program_span_names() == {"sparse.ell_from_csr_host"}
    finally:
        trace.reset()
        jax.config.update(key, before)


def test_the_first_load_prints_the_idle_gaps(tmp_path, capsys, monkeypatch):
    """``load`` reads the run's window profile once and names its gaps on
    standard error."""
    monkeypatch.setattr(scopes, "read", lambda path, harness, program: synthetic())
    window = tmp_path / "window" / "plugins" / "profile" / "1"
    window.mkdir(parents=True)
    (window / "host.xplane.pb").write_bytes(b"")
    ctx = _ctx()
    ctx._trace_dir = str(tmp_path)
    first = scopes.load(ctx)
    assert scopes.load(ctx) is first
    err = capsys.readouterr().err
    assert err.count("idle gaps by program span: block_jacobi.extract 7.0") == 1
    scopes._LOADED.pop(str(window / "host.xplane.pb"))
    ctx.summary = None
    assert scopes.load(ctx) is None
