"""The trace reduction: busy time as a union, per-kernel sums, idle gaps
labelled by the host span they fall in, on synthetic events and on a
recorded trace of one chip."""

from __future__ import annotations

import json
import pathlib

import pytest

import _vbench_tiny  # noqa: F401  (puts the checkout on the path)
from vbench import trace

MS = 1e6            # trace times are nanoseconds


def test_merge_is_a_clipped_union():
    got = trace.merge([(5, 8), (0, 3), (2, 4), (8, 9), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 9), (20, 25)]
    assert trace.gaps(got, 0, 30) == [(0, 1), (4, 5), (9, 20), (25, 30)]


def test_label_by_the_innermost_span_covering_most_of_the_gap():
    spans = [("vbench.step", 0, 100), ("vbench.dispatch", 10, 20),
             ("vbench.complete", 30, 90)]
    assert trace.label((12, 18), spans) == "vbench.dispatch"
    # 5 in dispatch, 10 in step alone, 5 in complete
    assert trace.label((15, 35), spans) == "vbench.step"
    assert trace.label((40, 60), spans) == "vbench.complete"
    assert trace.label((200, 300), spans) == "host:other"
    assert trace.label((95, 300), spans) == "host:other"


def test_op_names_are_the_hlo_instruction_names():
    ev = ("%vita_layer_int8.3 = f32[1,196,192]{2,1,0} custom-call("
          "f32[1,196,192]{2,1,0} %vita_layer_int8.2), "
          'custom_call_target="tpu_custom_call"')
    assert trace.op_name(ev) == "vita_layer_int8.3"
    assert trace.op_kind(ev) == "vita_layer_int8"
    # an operand named after the kernel does not make a kernel event
    fusion = "%fusion.7 = f32[1,196,192] fusion(%vita_layer_int8.11)"
    r = trace.reduce_events({0: [(ev, 0, 2), (fusion, 2, 3)]}, [],
                            (0, 10), {"vita_layer": "vita_layer"})
    assert r.kernel_calls["vita_layer"] == 1
    assert dict(r.device_ops) == {"vita_layer_int8": 2e-9, "fusion": 3e-9}


def test_reduce_events_two_chips():
    dev = {
        0: [("vita_layer_a", 0, 4 * MS), ("fusion.1", 3 * MS, 2 * MS),
            ("vita_layer_b", 10 * MS, 2 * MS)],
        1: [("vita_layer_a", 1 * MS, 1 * MS)],
    }
    host = [("vbench.window", 0, 20 * MS), ("vbench.step", 0, 20 * MS),
            ("vbench.sleep", 5 * MS, 10 * MS), ("python", 0, 1)]
    r = trace.reduce_events(dev, host, (0, 20 * MS),
                            {"vita_layer": "vita_layer",
                             "other": "^fusion"})
    assert r.window_s == pytest.approx(0.020)
    assert r.busy_s == pytest.approx((7 * MS + 1 * MS) / 2 / 1e9)
    assert r.kernel_s["vita_layer"] == pytest.approx(7e-3)
    assert r.kernel_calls == {"vita_layer": 3, "other": 1}
    assert r.device_ops[0] == ("vita_layer_a", pytest.approx(5e-3))
    # chip 0 idles 5-10 ms (host asleep) and 12-20 ms (host stepping)
    assert r.idle_gaps == [("vbench.step", pytest.approx(8e-3)),
                           ("vbench.sleep", pytest.approx(5e-3))]


def test_events_outside_the_window_are_clipped():
    dev = {0: [("k", -5 * MS, 10 * MS), ("k", 18 * MS, 5 * MS)]}
    r = trace.reduce_events(dev, [], (0, 20 * MS), {"k": "^k$"})
    assert r.kernel_s["k"] == pytest.approx(7e-3)
    assert r.busy_s == pytest.approx(7e-3)


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({}, [], (0, 1), {})


def test_harness_spans_reach_the_trace(tmp_path):
    """The window and the harness's spans are written into the profiler's
    trace, where the reduction finds them (recorded here on the CPU)."""
    from vbench import harness
    tracer = harness._Tracer(tmp_path / "trace")
    rec = harness._Recorder()
    tracer.start()
    tracer.open_window()
    rec.annotate = True
    rec.sleep(0.01)
    rec.span("vbench.step", sum, [1, 2])
    rec.annotate = False
    tracer.close_window()
    path = tracer.stop()
    _, spans = trace.read_xplane(path)
    names = [s[0] for s in spans]
    assert names.count("vbench.window") == 1
    assert "vbench.sleep" in names and "vbench.step" in names
    lo, hi = trace.window_of(spans)
    sleep = next(s for s in spans if s[0] == "vbench.sleep")
    assert lo <= sleep[1] < sleep[2] <= hi
    assert sleep[2] - sleep[1] >= 0.009e9


RECORDED = pathlib.Path(__file__).parent / "data" / "frames_trace.json"


def _recorded():
    d = json.loads(RECORDED.read_text())
    return (d, {0: [tuple(e) for e in d["device"]]},
            [tuple(s) for s in d["spans"]], tuple(d["window"]))


def _busy_by_sweep(events, lo, hi):
    """Busy time by counting the operations open at each boundary."""
    marks = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(marks):
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    return busy


def test_recorded_chip_trace():
    """A window of a traced ``deit_t_int8.frames`` run on one chip: the
    reduction of its recorded events gives what the chip run reported,
    busy time is their union, every frame ran 12 encoder-layer kernels,
    and the longest idle gaps are the longest holes in the union."""
    d, device, spans, window = _recorded()
    r = trace.reduce_events(device, spans, window,
                            {"vita_layer": "vita_layer"})
    want = d["reduced"]
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert r.kernel_s["vita_layer"] == pytest.approx(want["kernel_s"],
                                                     rel=1e-12)
    assert r.kernel_calls["vita_layer"] == want["kernel_calls"]
    assert [list(x) for x in r.device_ops] == want["device_ops"]
    assert [list(x) for x in r.idle_gaps] == want["idle_gaps"]

    assert r.busy_s * 1e9 == pytest.approx(
        _busy_by_sweep(device[0], *window), rel=1e-12)
    assert 0 < r.kernel_s["vita_layer"] < r.busy_s < r.window_s

    frames = [s for s in spans if s[0] == "vbench.dispatch"
              and window[0] <= s[1] < window[1]]
    assert r.kernel_calls["vita_layer"] == 12 * len(frames)

    busy = trace.merge([(s, s + dur) for _, s, dur in device[0]], *window)
    holes = sorted((b - a for a, b in trace.gaps(busy, *window)),
                   reverse=True)[:trace.TOP]
    assert [g for _, g in r.idle_gaps] == pytest.approx(
        [h / 1e9 for h in holes], rel=1e-12)
    assert {n for n, _ in r.idle_gaps} <= \
        {s[0] for s in spans} | {"host:other"}
    # one frame at a time: the chip waits while the host dispatches
    assert r.idle_gaps[0][0] == "vbench.dispatch"
