"""The span-and-counter recorder (`core.spans`) and the spans the serving
path records with it: nesting, self time, the ring's wrap and its
``dropped`` count, silence while off, no GC-tracked object left alive by
recording, the GC hook, and one admission step's ``serve.*`` spans
sharing the batch id its requests carry."""

import gc

import numpy as np
import pytest

from repro.core import spans
from repro.core.spans import Recorder
from repro.launch.admission import AdmissionController
from repro.launch.vision_serve import ServeConfig, make_server


def fake_clock(monkeypatch, times):
    it = iter(times)
    monkeypatch.setattr(spans, "_clock", lambda: float(next(it)))


def rows(rec, since=0):
    """(name, arg, parent name or None) of each span from ``since`` on."""
    t = rec.table()
    seqs = list(t["seq"])
    out = []
    for k in range(len(seqs)):
        if t["seq"][k] < since:
            continue
        p = t["parent"][k]
        parent = rec.names[t["name"][seqs.index(p)]] if p in seqs else None
        out.append((rec.names[t["name"][k]], int(t["arg"][k]), parent))
    return out


def test_nesting_and_parent():
    rec = Recorder(capacity=16)
    rec.enabled = True
    assert rec.on()
    a = rec.begin("a", 1)
    b = rec.begin("b", 2)
    rec.end(b)
    c = rec.begin("c", 3)
    rec.end(c)
    rec.end(a)
    d = rec.begin("d")
    rec.end(d)
    assert rows(rec) == [("a", 1, None), ("b", 2, "a"), ("c", 3, "a"),
                         ("d", 0, None)]
    t = rec.table()
    assert list(t["parent"]) == [-1, a, a, -1]
    assert (t["t1"] >= t["t0"]).all()


def test_self_time(monkeypatch):
    """A span's self time is its duration less its direct children's."""
    rec = Recorder(capacity=16)
    fake_clock(monkeypatch, [0, 1, 3, 4, 8, 10])
    a = rec.begin("outer")
    b = rec.begin("inner")
    b = rec.switch(b, "inner")
    rec.end(b)
    rec.end(a)
    s = rec.summary()["spans"]
    assert s["outer"]["total_s"] == 10.0
    assert s["outer"]["self_s"] == 10.0 - 2.0 - 4.0
    assert s["inner"]["count"] == 2
    assert s["inner"]["total_s"] == s["inner"]["self_s"] == 6.0
    assert s["inner"]["p50_ms"] == 3000.0


def test_switch_ends_and_begins_a_sibling():
    rec = Recorder(capacity=16)
    top = rec.begin("top")
    part = rec.begin("x")
    part = rec.switch(part, "y")
    rec.end(part)
    rec.end(top)
    assert rows(rec) == [("top", 0, None), ("x", 0, "top"),
                         ("y", 0, "top")]


def test_end_closes_spans_left_open_inside():
    """A call that raised leaves its children open; ending the call's
    span restores the parent of what comes next."""
    rec = Recorder(capacity=16)
    top = rec.begin("top")
    rec.begin("left open")
    rec.end(top)
    nxt = rec.begin("next")
    rec.end(nxt)
    assert rows(rec)[-1] == ("next", 0, None)
    assert len(rec.select("left open")) == 0    # never closed


def test_ring_wraps_and_counts_dropped():
    rec = Recorder(capacity=8)
    for i in range(20):
        rec.record("s", float(i), i + 0.5, arg=i)
    assert rec.n == 20 and rec.dropped == 12
    t = rec.table()
    assert list(t["seq"]) == list(range(12, 20))
    assert list(t["arg"]) == list(range(12, 20))
    # spans that started before the oldest one kept may be lost: no answer
    assert rec.select("s", 0.0, 100.0) is None
    assert rec.select("s", 12.0, 100.0) is None
    np.testing.assert_allclose(rec.select("s", 12.5, 100.0), [0.5] * 7)
    np.testing.assert_allclose(rec.select("s", 12.5, 15.0), [0.5] * 2)
    assert rec.summary()["counters"]["dropped"] == 12


def test_select_window_is_half_open_and_skips_open_spans():
    rec = Recorder(capacity=16)
    rec.record("s", 1.0, 1.25)
    rec.record("s", 2.0, 2.5)
    rec.record("other", 2.0, 3.0)
    np.testing.assert_allclose(rec.select("s", 1.0, 2.0), [0.25])
    np.testing.assert_allclose(rec.select("s", 1.5, 9.0), [0.5])
    assert len(rec.select("never", 0.0, 9.0)) == 0
    rec.begin("s")
    assert len(rec.select("s", 3.0, np.inf)) == 0


def test_records_nothing_while_off():
    rec = Recorder(capacity=16)
    assert not rec.enabled and not rec.on()
    gc.disable()
    rec.watch_gc()
    try:
        gc.collect(0)
    finally:
        rec.unwatch_gc()
        gc.enable()
    assert rec.n == 0
    assert rec.gc_collections[0] == 1           # counters are always on


def test_gc_hook_records_collections_inside_the_open_span():
    rec = Recorder(capacity=16)
    rec.enabled = True
    gc.disable()
    rec.watch_gc()
    try:
        top = rec.begin("work")
        gc.collect(1)
        rec.end(top)
    finally:
        rec.unwatch_gc()
        gc.enable()
    assert rows(rec) == [("work", 0, None), ("host.gc", 1, "work")]
    assert rec.gc_collections == [0, 1, 0]
    assert rec.gc_pause_s[1] > 0.0
    (pause,) = rec.select("host.gc")
    assert pause == pytest.approx(rec.gc_pause_s[1])


def test_recording_leaves_no_tracked_object_alive():
    """10,000 recorded spans leave no GC-tracked object behind, and do not
    move the young generation's count toward a collection."""
    rec = Recorder(capacity=1 << 12)
    rec.enabled = True
    assert rec.on()
    rec.end(rec.begin("warm", 0))               # the name's first use
    gc.disable()
    try:
        n0 = len(gc.get_objects())
        c0 = gc.get_count()[0]
        for i in range(10_000):
            top = rec.begin("warm", i)
            part = rec.begin("warm", i)
            part = rec.switch(part, "warm", i)
            rec.end(part)
            rec.end(top)
        c1 = gc.get_count()[0]
        n1 = len(gc.get_objects())
    finally:
        gc.enable()
    assert n1 - n0 == 0
    assert c1 - c0 <= 1
    assert rec.n == 1 + 30_000 and rec.dropped == rec.n - (1 << 12)


def test_capacity_is_a_power_of_two():
    with pytest.raises(ValueError):
        Recorder(capacity=1000)


@pytest.fixture
def recording():
    spans.enable()
    try:
        yield spans.RECORDER
    finally:
        spans.disable()


@pytest.fixture(scope="module")
def controller():
    server = make_server("vit_edge", ServeConfig(backend="xla",
                                                 buckets=(2,)))
    return server, AdmissionController({"vit_edge": server},
                                       max_inflight=2, measure_repeats=1)


def test_admission_step_records_serve_spans_of_one_batch(controller,
                                                         recording):
    server, ctl = controller
    image = np.zeros((server.cfg.image, server.cfg.image, 3), np.float32)
    reqs = [ctl.submit("vit_edge", image) for _ in range(2)]
    n0 = recording.n
    assert ctl.step() == 2
    got = [r for r in rows(recording, n0) if r[0] != "host.gc"]
    batch = reqs[0].batch
    assert batch is not None and reqs[1].batch == batch
    assert got == [
        ("admission.step", 0, None),
        ("admission.assemble", 0, "admission.step"),
        ("serve.dispatch", batch, "admission.step"),
        ("serve.stack", batch, "serve.dispatch"),
        ("serve.place", batch, "serve.dispatch"),
        ("serve.launch", batch, "serve.dispatch"),
        ("admission.assemble", 0, "admission.step"),   # nothing left
        ("serve.complete", batch, "admission.step"),
        ("serve.wait", batch, "serve.complete"),
        ("serve.readback", batch, "serve.complete"),
        ("serve.stamp", batch, "serve.complete"),
    ]
    t = recording.table()
    new = t["seq"] >= n0
    assert (t["t1"][new] >= t["t0"][new]).all()


def test_batch_ids_differ_between_dispatches(controller, recording):
    server, ctl = controller
    image = np.zeros((server.cfg.image, server.cfg.image, 3), np.float32)
    a = ctl.submit("vit_edge", image)
    ctl.step()
    b = ctl.submit("vit_edge", image)
    ctl.step()
    assert a.batch is not None and b.batch is not None
    assert a.batch != b.batch


def test_serving_records_nothing_while_off(controller):
    server, ctl = controller
    assert not spans.on()
    image = np.zeros((server.cfg.image, server.cfg.image, 3), np.float32)
    req = ctl.submit("vit_edge", image)
    n0 = spans.RECORDER.n
    ctl.step()
    assert spans.RECORDER.n == n0
    assert req.batch is not None and req.t_done is not None
