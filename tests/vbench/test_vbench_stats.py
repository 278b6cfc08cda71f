"""The metric arithmetic on synthetic stamps: what counts as in the
window, failures as infinitely late, the 95th percentile, and the
readers of the end-to-end and admission metrics."""

from __future__ import annotations

import math
import types

import numpy as np
import pytest

from _vbench_tiny import REPO
from vbench import spec, stats


def test_percentile_matches_numpy_on_finite_values():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        v = rng.exponential(size=n)
        for q in (50, 95, 99):
            assert stats.percentile(v, q) == pytest.approx(
                np.percentile(v, q))


def test_percentile_with_failures_is_infinite_only_when_reached():
    v = list(range(1, 101)) + [math.inf]       # 1 failure in 101
    assert stats.percentile(v, 95) == pytest.approx(np.percentile(
        list(range(1, 101)) + [101], 95))
    v = list(range(1, 91)) + [math.inf] * 10   # 10 failures in 100
    assert stats.percentile(v, 95) == math.inf


def test_in_window_is_half_open():
    w = (10.0, 20.0)
    assert stats.in_window(10.0, w) and not stats.in_window(20.0, w)
    assert stats.count_in_window([9.9, 10.0, 15.0, 19.999, 20.0, None],
                                 w) == 3


def test_late_latencies_count_missing_and_late_answers_as_infinite():
    lat = stats.late_latencies_ms([0.0, 1.0, 2.0], [0.010, None, 2.5],
                                  deadline=2.2)
    assert lat[0] == pytest.approx(10.0)
    assert lat[1] == math.inf and lat[2] == math.inf


def _req(t_submit, t_start, t_done):
    return types.SimpleNamespace(t_submit=t_submit, t_start=t_start,
                                 t_done=t_done)


def _run(requests, dispatches=(), sla_ms=100.0, seconds=10.0):
    from vbench import harness
    cell = types.SimpleNamespace(traffic={"sla_ms": sla_ms}, chips=1)
    return harness.Run(cell=cell, seconds=seconds, window=(100.0, 110.0),
                       setup_s=42.0, requests=list(requests),
                       dispatches=list(dispatches), spans=[],
                       lateness_s=[], compiles_in_window=0, padded=0,
                       geometry={}, peaks={}, arithmetic="bf16", work=None)


def _reader(name):
    return spec.load_reader(REPO, name)


def test_images_per_s_counts_answers_inside_the_window_only():
    reqs = [_req(99.0, 99.0, 99.5),            # before
            _req(99.0, 99.5, 100.0),           # at the open: counts
            _req(105.0, 105.0, 105.1),
            _req(109.0, 109.0, 110.0),         # at the close: does not
            _req(109.9, 110.0, 110.3)]         # drained after
    assert _reader("images_per_s")(_run(reqs)) == pytest.approx(0.2)
    assert _reader("frame_ms")(_run(reqs)) == pytest.approx(5000.0)
    assert _reader("setup_s")(_run(reqs)) == 42.0


def test_latency_p95_over_requests_due_in_the_window():
    reqs = [_req(100.0 + i * 0.1, 100.0 + i * 0.1,
                 100.0 + i * 0.1 + 0.001 * (i + 1)) for i in range(100)]
    reqs.append(_req(99.0, 99.0, 105.0))       # due before: not counted
    got = _reader("latency_p95_ms")(_run(reqs))
    assert got == pytest.approx(np.percentile(np.arange(1, 101), 95))


def test_latency_p95_counts_unanswered_as_infinitely_late():
    reqs = [_req(100.0 + i * 0.1, 100.0 + i * 0.1, 100.0 + i * 0.1 + 0.001)
            for i in range(90)]
    # ten due in the window: never answered, or only after one SLA past
    # the close
    reqs += [_req(105.0, None, None)] * 5
    reqs += [_req(109.0, 109.0, 110.2)] * 5
    got = _reader("latency_p95_ms")(_run(reqs))
    assert got == 1e9
    q = _reader("admission.queue_wait_p95_ms")(_run(reqs))
    assert q is None                            # reaches the undispatched


def test_admission_readers():
    reqs = [_req(100.0 + i * 0.05, 100.0 + i * 0.05 + 0.002 * i, 101.0 + i)
            for i in range(100)]
    q = _reader("admission.queue_wait_p95_ms")(_run(reqs))
    assert q == pytest.approx(np.percentile(2.0 * np.arange(100), 95))
    disp = [(100.5, 100.6, 8, 6), (101.0, 101.1, 8, 8), (99.0, 99.1, 8, 1)]
    fill = _reader("admission.batch_fill")(_run([], disp))
    assert fill == pytest.approx(100.0 * 14 / 16)


def test_answers_compared_are_all_or_a_seeded_sample(monkeypatch):
    from vbench import harness
    monkeypatch.setattr(harness, "MAX_COMPARED", 10)
    reqs = list(range(25))
    a = harness.compared_requests(reqs, 2 ** 31 + 3)
    assert a == harness.compared_requests(reqs, 2 ** 31 + 3)
    assert len(a) == 10 and len(set(a)) == 10 and a == sorted(a)
    assert harness.compared_requests(reqs[:10], 1) == reqs[:10]


def test_logit_gap_is_the_widest_gap_over_the_reference_scale():
    from vbench import harness
    ref = np.array([[1.0, -4.0], [2.0, 0.5]])
    answers = np.array([[2.0, 0.5], [1.0, -3.0], [1.0, -4.0]])
    images = np.array([1, 0, 0])
    assert harness.logit_gap(answers, images, ref) == pytest.approx(1 / 4)
