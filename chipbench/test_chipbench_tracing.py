"""The reduction from a trace to busy time, op sums, collectives and gaps."""

import pytest

from chipbench import tracing
from chipbench.tracing import Event, Trace


def synthetic() -> Trace:
    """Two devices, a 10 s window.  Device 0: a while op over [1, 5] holding
    two fusions and an all-gather, then a fusion over [6, 8] that overlaps a
    copy over [7, 9].  Device 1: one fusion over [0, 4] and an all-reduce
    over [4, 5].  Host spans: solve [1, 5], d2h [5, 6], h2d [8, 10]."""
    d0 = [
        Event("while.1 while (tuple)", 1.0, 5.0),
        Event("fusion.2 fusion f32[8]", 1.0, 2.0),
        Event("all-gather.3 all-gather f32[8]", 2.0, 2.5, collective=True),
        Event("fusion.2 fusion f32[8]", 3.0, 4.0),
        Event("fusion.4 fusion f32[8]", 6.0, 8.0),
        Event("copy.5 copy f32[8]", 7.0, 9.0),
    ]
    d1 = [
        Event("fusion.2 fusion f32[8]", 0.0, 4.0),
        Event("all-reduce.6 all-reduce f32[]", 4.0, 5.0, collective=True),
    ]
    spans = [
        Event("window", 0.0, 10.0),
        Event("solve", 1.0, 5.0),
        Event("d2h", 5.0, 6.0),
        Event("h2d", 8.0, 10.0),
    ]
    return Trace({0: d0, 1: d1}, spans)


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = tracing.summarize(synthetic())
    assert s.window_s == pytest.approx(10.0)
    # device 0: [1, 5] and [6, 9]; device 1: [0, 5]
    assert s.busy_s == pytest.approx({0: 7.0, 1: 5.0})
    assert s.mean_busy_s == pytest.approx(6.0)
    assert s.idle_share == pytest.approx(0.4)


def test_window_clips_the_ops():
    trace = synthetic()
    trace.spans[0] = Event("window", 2.0, 7.0)
    s = tracing.summarize(trace)
    assert s.busy_s == pytest.approx({0: 4.0, 1: 3.0})
    assert s.window_s == pytest.approx(5.0)


def test_op_sums_take_self_time_and_average_over_devices():
    s = tracing.summarize(synthetic())
    # the while op holds 2.5 s of nested ops in its 4 s
    assert s.op_s["while.1 while (tuple)"] == pytest.approx(1.5 / 2)
    assert s.op_s["fusion.2 fusion f32[8]"] == pytest.approx((2.0 + 4.0) / 2)
    assert s.op_s["copy.5 copy f32[8]"] == pytest.approx(2.0 / 2)
    assert sum(s.op_s.values()) == pytest.approx(
        (4.0 + 2.0 + 2.0) / 2 + (4.0 + 1.0) / 2)


def test_collective_seconds_per_device():
    s = tracing.summarize(synthetic())
    assert s.collective_s == pytest.approx({0: 0.5, 1: 1.0})


def test_gaps_are_named_by_the_host_span_over_them():
    s = tracing.summarize(synthetic())
    # device 0 idles over [0, 1] (no span but the window), [5, 6] (d2h)
    # and [9, 10] (h2d)
    assert sorted(s.gaps) == sorted([("host", 1.0), ("d2h", 1.0), ("h2d", 1.0)])


def test_breakdown_lists_the_top_ops_and_gaps():
    trace = synthetic()
    trace.devices[0] += [Event(f"op.{i} add f32[]", 9.0 + i * 0.05, 9.0 + i * 0.05 + 0.01)
                         for i in range(12)]
    b = tracing.breakdown(tracing.summarize(trace))
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0] == ["fusion.2 fusion f32[8]", pytest.approx(3.0)]
    assert all(isinstance(n, str) and sec > 0 for n, sec in b["idle_gaps"])


def test_a_trace_without_device_ops_or_window_is_refused():
    with pytest.raises(ValueError):
        tracing.summarize(Trace({}, [Event("window", 0.0, 1.0)]))
    with pytest.raises(ValueError):
        tracing.summarize(Trace(synthetic().devices, []))


@pytest.mark.parametrize("hlo, label, collective", [
    ("%fusion.12 = f32[14680064]{0:T(1024)} fusion(f32[2097152]{0:T(1024)S(1)} "
     "%get-tuple-element.190), kind=kCustom", "fusion.12 fusion f32[14680064]", False),
    ("%while.1 = (f32[2097152]{0:T(1024)}, s32[]{:T(128)}) while((f32[2097152]"
     "{0:T(1024)}) %tuple.27), condition=%c", "while.1 while (tuple)", False),
    ("%all-gather.10 = f32[2097152]{0:T(1024)S(1)} all-gather(f32[524288]{0} %g), "
     "dimensions={0}", "all-gather.10 all-gather f32[2097152]", True),
    ("%all-reduce = u32[]{:T(128)} all-reduce(u32[]{:T(128)} %bitcast.2)",
     "all-reduce all-reduce u32[]", True),
    ("region.84", "region.84", False),
])
def test_op_labels_and_collectives(hlo, label, collective):
    assert tracing.op_label(hlo) == label
    assert tracing.is_collective(hlo) is collective


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 4000000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000000 }
    events { metadata_id: 2 offset_ps: 3000000000000 duration_ps: 1000000000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.12 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p), kind=kCustom" } }
  event_metadata { key: 2 value { id: 2
    name: "%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %q), dimensions={0}" } }
  event_metadata { key: 9 value { id: 9 name: "jit_solve(1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000000 }
    events { metadata_id: 2 offset_ps: 500000000000 duration_ps: 4500000000000 }
    events { metadata_id: 3 offset_ps: 5000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "solve" } }
  event_metadata { key: 3 value { id: 3 name: "d2h" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(solve)" } }
}
"""


def test_an_xspace_file_is_read_and_reduced(tmp_path):
    """A profile as the profiler writes it: the device's ``XLA Ops`` and the
    harness's spans on the host are kept, the rest is not."""
    from jax.profiler import ProfileData

    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert tracing.newest_xspace(str(tmp_path)) == str(path)
    trace = tracing.read_xspace(str(path), ("solve", "d2h"))
    assert [e.name for e in trace.devices[0]] == [
        "fusion.12 fusion f32[8]", "all-gather.3 all-gather f32[8]"]
    assert [e.collective for e in trace.devices[0]] == [False, True]
    assert sorted(e.name for e in trace.spans) == ["d2h", "solve", "window"]
    s = tracing.summarize(trace)
    # ops over [1, 3] and [4, 5] in a window over [0, 6]
    assert s.window_s == pytest.approx(6.0)
    assert s.busy_s[0] == pytest.approx(3.0)
    assert s.collective_s[0] == pytest.approx(1.0)
    # idle over [0, 1] (half of it under solve), [3, 4] and [5, 6]
    assert sorted(s.gaps) == [("d2h", 1.0), ("solve", 1.0), ("solve", 1.0)]
    assert tracing.device_seconds(trace) == pytest.approx(3.0)
