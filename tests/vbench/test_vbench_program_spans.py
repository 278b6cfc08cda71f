"""The per-layer metrics that read the program's own spans: each of the
six readers on a synthetic run (a number inside the window, None outside
it, None after the ring dropped spans from it, None where the program
has no recorder), and, on the CPU, the program's spans in a profiler
trace nested inside the harness's ``vbench.*`` spans, on one clock."""

from __future__ import annotations

import gc
import pathlib
import sys

import numpy as np
import pytest

from _vbench_tiny import REPO

from vbench import harness, program_spans
from vbench import spec as spec_lib

harness.program_path()
from repro.core import spans  # noqa: E402
from repro.launch.admission import AdmissionController  # noqa: E402
from repro.launch.vision_serve import ServeConfig, make_server  # noqa: E402

# each metric, and what it reads of `batches` in a window of 20 of them
READERS = {
    "server.stack_ms.throughput": 2.0,
    "server.place_ms.throughput": 3.0,
    "host.gc_per_batch.throughput": 1.5,
    "server.place_ms.frame": 3.0,
    "server.wait_ms.frame": 5.0,
    "server.readback_ms.frame": 0.5,
}


class Run:
    def __init__(self, window):
        self.window = window


def batches(rec, t0, n):
    """``n`` micro-batches from ``t0``, one every 10 ms: dispatch (stack
    2 ms, place 3 ms, launch 1 ms), then complete (wait 5 ms, readback
    0.5 ms), with three collections in every second batch."""
    ms = 1e-3
    for b in range(n):
        t = t0 + 10 * ms * b
        rec.record("serve.dispatch", t, t + 6 * ms, b)
        rec.record("serve.stack", t, t + 2 * ms, b)
        rec.record("serve.place", t + 2 * ms, t + 5 * ms, b)
        rec.record("serve.launch", t + 5 * ms, t + 6 * ms, b)
        rec.record("serve.complete", t + 6 * ms, t + 12 * ms, b)
        rec.record("serve.wait", t + 6 * ms, t + 11 * ms, b)
        rec.record("serve.readback", t + 11 * ms, t + 11.5 * ms, b)
        if b % 2:
            for g in range(3):
                rec.record("host.gc", t + 1 * ms, t + 1.1 * ms, g)


@pytest.fixture
def recorder(monkeypatch):
    rec = spans.Recorder(capacity=1 << 10)
    monkeypatch.setattr(spans, "RECORDER", rec)
    return rec


def reader(name):
    return spec_lib.load_reader(REPO, name)


def test_every_reader_is_declared():
    import json
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert declared[name]["source"] == "host_clock"
        assert len(declared[name]["workloads"]) == 1


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_in_the_window(recorder, name):
    batches(recorder, 100.0, 40)
    value = reader(name)(Run((100.095, 100.295)))      # batches 10..29
    assert value == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_outside_the_window_is_none(recorder, name):
    batches(recorder, 100.0, 40)
    assert reader(name)(Run((200.0, 210.0))) is None
    assert reader(name)(Run((90.0, 100.0))) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_after_a_drop_is_none(recorder, name):
    batches(recorder, 100.0, 400)            # far more than the ring holds
    assert recorder.dropped > 0
    assert reader(name)(Run((100.0, 110.0))) is None
    # a window wholly inside what the ring kept still reads
    kept = Run((103.895, 103.935))                       # batches 390..393
    assert reader(name)(kept) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_the_programs_recorder_is_none(monkeypatch, name):
    """A program that predates the recorder: the import fails, the
    reader reads nothing and raises nothing."""
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert program_spans.recorder() is None
    assert reader(name)(Run((0.0, 1e9))) is None


def test_no_collections_read_zero(recorder):
    recorder.record("serve.dispatch", 1.0, 1.01, 0)
    assert reader("host.gc_per_batch.throughput")(Run((0.0, 2.0))) == 0.0


# ---------------------------------------------------------------------------
# the program's spans in a profiler trace, on the CPU
# ---------------------------------------------------------------------------


def host_events(path):
    """Every host event of a trace: (name, start ns, end ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns)))
    return out


def inside(events, name, outer):
    """Whether an event ``name`` lies inside an event ``outer``."""
    outs = [(s, e) for n, s, e in events if n == outer]
    return any(os <= s and e <= oe for n, s, e in events if n == name
               for os, oe in outs)


def test_program_spans_nest_in_the_harness_spans(tmp_path):
    import jax
    server = make_server("vit_edge", ServeConfig(backend="xla",
                                                 buckets=(2,)))
    ctl = AdmissionController({"vit_edge": server}, measure_repeats=1)
    complete = server.complete

    def complete_and_collect(inflight):
        gc.collect(0)                        # a collection inside complete
        return complete(inflight)

    server.complete = complete_and_collect
    rec = harness._Recorder()
    rec.wrap(server, ctl)
    image = np.zeros((server.cfg.image, server.cfg.image, 3), np.float32)
    n0 = spans.RECORDER.n
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        rec.annotate = True
        ctl.submit("vit_edge", image)
        ctl.submit("vit_edge", image)
        assert ctl.step() == 2
    finally:
        rec.annotate = False
        jax.profiler.stop_trace()
    assert not spans.RECORDER.enabled
    assert spans.RECORDER.n > n0             # recorded while profiling
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    events = host_events(str(path))
    assert inside(events, "admission.step", "vbench.step")
    assert inside(events, "admission.assemble", "admission.step")
    for name in ("serve.stack", "serve.place", "serve.launch"):
        assert inside(events, name, "serve.dispatch")
    assert inside(events, "serve.place", "vbench.dispatch")
    assert inside(events, "serve.dispatch", "vbench.dispatch")
    for name in ("serve.wait", "serve.readback", "serve.stamp"):
        assert inside(events, name, "serve.complete")
    assert inside(events, "serve.complete", "vbench.complete")
    assert inside(events, "host.gc", "vbench.complete")
