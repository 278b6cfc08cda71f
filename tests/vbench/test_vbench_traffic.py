"""The traffic generator: the same seed gives the same requests, and an
open loop's due times are fixed before any request is sent."""

from __future__ import annotations

import numpy as np
import pytest

from _vbench_tiny import REPO
from vbench import traffic

POISSON = {"loop": "open", "arrivals": "poisson", "rate_per_s": 3000.0,
           "sla_ms": 100, "warm_s": 1.0, "bank": 64}
BIG_SEED = 2 ** 31 + 977


@pytest.mark.parametrize("seed", [0, 12345, BIG_SEED])
def test_same_seed_same_plan(seed):
    a = traffic.plan(POISSON, seed, 10.0, (1, 8))
    b = traffic.plan(POISSON, seed, 10.0, (1, 8))
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.picks, b.picks)


def test_seeds_reorder_one_set_of_gaps():
    a = traffic.plan(POISSON, 1, 10.0, (1,))
    b = traffic.plan(POISSON, 2, 10.0, (1,))
    assert len(a.due) == len(b.due)
    assert not np.array_equal(a.due, b.due)
    gaps = [np.sort(np.diff(np.concatenate([[-1.0], p.due])))
            for p in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    # same offered load in the window on every seed, within a few requests
    in_a = np.sum((a.due >= 0) & (a.due < 10.0))
    in_b = np.sum((b.due >= 0) & (b.due < 10.0))
    assert abs(in_a - in_b) <= 0.05 * in_a
    assert abs(in_a - 30000) <= 0.05 * 30000


def test_poisson_schedule_spans_warmup_window_and_tail():
    p = traffic.plan(POISSON, 3, 10.0, (1,))
    assert p.due[0] >= -1.0 and p.due[0] < -0.99
    assert np.all(np.diff(p.due) > 0)
    assert abs(p.due[-1] - 10.1) < 0.05
    gaps = np.diff(p.due)
    assert abs(gaps.mean() - 1 / 3000) < 0.02 / 3000


def test_closed_loop_clients_follow_the_largest_bucket():
    mix = {"loop": "closed", "clients": {"per_largest_bucket": 3},
           "warm_s": 1.0, "bank": 64}
    assert traffic.plan(mix, 1, 5.0, (1, 8, 32)).clients == 96
    assert traffic.plan(mix, 1, 5.0, (4, 8, 32, 128)).clients == 384
    assert traffic.plan(dict(mix, clients=1), 1, 5.0, (1, 8)).clients == 1
    p = traffic.plan(mix, BIG_SEED, 5.0, (1,))
    assert p.due is None and 0 <= p.picks.min() and p.picks.max() < 64
    assert p.image_of(len(p.picks) + 5) == p.image_of(5)


@pytest.mark.parametrize("name", ["saturate", "frames", "poisson"])
def test_committed_mixes_plan(name):
    import json
    mix = json.loads((REPO / "vbench" / "traffic" / f"{name}.json")
                     .read_text())
    p = traffic.plan(mix, 7, 10.0, (1, 8, 32, 128))
    assert p.bank == mix["bank"]


def test_due_times_are_fixed_before_any_send(monkeypatch):
    """``drive_open`` stamps each request with the due time the
    plan fixed, however late it could send it."""
    from vbench import harness

    class Req:
        def __init__(self, t):
            self.t_submit, self.t_done, self.t_start = t, None, None

    class Ctl:
        pending = 0
        ring = []

        def submit(self, model, image, sla_ms=None, t_submit=None):
            return Req(t_submit)

        def step(self):
            pass

    p = traffic.plan(dict(POISSON, rate_per_s=2000.0, warm_s=0.05,
                          sla_ms=0), 5, 0.1, (1,))
    due_before = p.due.copy()
    rec = harness._Recorder()
    window, sent, late = harness.drive_open(
        Ctl(), "m", p, np.zeros((64, 1)), rec, harness._Tracer(None),
        type("C", (), {"on": False})())
    got = np.array([r.t_submit for r in sent]) - window[0]
    np.testing.assert_allclose(got, due_before, atol=1e-6)
    np.testing.assert_array_equal(p.due, due_before)
    assert len(late) == len(sent) and min(late) >= 0


def test_a_mix_names_the_buckets_it_serves():
    assert traffic.served_buckets({}, (1, 8, 32)) == (1, 8, 32)
    assert traffic.served_buckets({"buckets": [8, 1]}, (1, 8, 32)) == (1, 8)
    assert traffic.served_buckets({"buckets": "largest"},
                                  (1, 8, 32, 128)) == (128,)
    with pytest.raises(ValueError):
        traffic.served_buckets({"buckets": [4]}, (1, 8))
