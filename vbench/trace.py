"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

* busy time: the union of the intervals in which an operation ran on a
  chip, inside the traced window, averaged over the chips used;
* kernel time: the summed device durations of the events whose
  operation name matches a kernel's pattern, over all chips used;
* the kinds of device operation that took most time (an event's name is
  its HLO instruction, ``%vita_layer_int8.3 = f32[...] custom-call(...)``;
  its kind is the instruction's name without ``%`` and the ``.3``);
* the longest idle gaps of the first chip, each labelled by the harness
  span the host was in: the innermost span at each instant of the gap,
  and of those the one that covers most of it.

The window is the host span ``vbench.window`` that the harness writes
around the traced part of a run.  Times in the trace are nanoseconds.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

WINDOW_SPAN = "vbench.window"
SPAN_PREFIX = "vbench."
OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTANCE = re.compile(r"\.\d+$")
TOP = 10

Event = Tuple[str, float, float]          # name, start ns, duration ns
Span = Tuple[str, float, float]           # name, start ns, end ns


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # mean over the chips used
    chips: int
    kernel_s: Dict[str, float]         # summed over the chips used
    kernel_calls: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def merge(intervals: Sequence[Tuple[float, float]],
          lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] around the disjoint ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``%vita_layer_int8.3 = f32[...] custom-call(...)`` ->
    ``vita_layer_int8.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(event_name: str) -> str:
    """``vita_layer_int8.3`` -> ``vita_layer_int8``."""
    return _INSTANCE.sub("", op_name(event_name))


def label(gap: Tuple[float, float], spans: Sequence[Span]) -> str:
    """What the host was doing during ``gap``: at each instant the
    innermost (shortest) span open then, and of those the one that
    covers most of the gap; ``host:other`` where no span is open."""
    lo, hi = gap
    inside = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    cuts = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                              if lo < t < hi})
    covered: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [sp for sp in inside if sp[1] <= mid < sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
            else "host:other"
        covered[name] = covered.get(name, 0.0) + (b - a)
    return max(covered.items(), key=lambda kv: kv[1])[0]


def reduce_events(device_events: Mapping[int, Sequence[Event]],
                  host_spans: Sequence[Span],
                  window: Tuple[float, float],
                  kernels: Mapping[str, str]) -> Reduction:
    """The reduction of one traced window.  ``device_events`` maps each
    chip used to its operations; ``kernels`` maps a kernel's name to the
    regular expression its events' names match."""
    lo, hi = window
    if not device_events:
        raise ValueError("the trace holds no device operations")
    pats = {k: re.compile(p) for k, p in kernels.items()}
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    by_op: Dict[str, float] = {}
    busy_total = 0.0
    first = min(device_events)
    for chip in sorted(device_events):
        evs = device_events[chip]
        busy = merge([(s, s + d) for _, s, d in evs], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if chip == first:
            first_busy = busy
        for name, s, d in evs:
            if s + d <= lo or s >= hi:
                continue
            dur = min(s + d, hi) - max(s, lo)
            kind = op_kind(name)
            by_op[kind] = by_op.get(kind, 0.0) + dur
            for k, pat in pats.items():
                if pat.search(op_name(name)):
                    kernel_s[k] += dur
                    kernel_calls[k] += 1
    spans = [sp for sp in host_spans
             if sp[0].startswith(SPAN_PREFIX) and sp[0] != WINDOW_SPAN]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(device_events) / 1e9,
        chips=len(device_events),
        kernel_s={k: v / 1e9 for k, v in kernel_s.items()},
        kernel_calls=kernel_calls,
        device_ops=[(n, v / 1e9) for n, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[(label(g, spans), (g[1] - g[0]) / 1e9)
                   for g in idle[:TOP]])


def read_xplane(path: str, chips: Optional[int] = None
                ) -> Tuple[Dict[int, List[Event]], List[Span]]:
    """The device operations (line ``XLA Ops`` of each ``/device:TPU:<i>``
    plane, ``i < chips``) and the harness's host spans of a trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[int, List[Event]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OP_LINE:
                    device.setdefault(chip, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        spans.append((e.name, s, s + float(e.duration_ns)))
    return device, spans


def window_of(spans: Sequence[Span]) -> Tuple[float, float]:
    w = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0][1], w[0][2]


def reduce_file(path: str, kernels: Mapping[str, str],
                chips: Optional[int] = None) -> Reduction:
    device, spans = read_xplane(path, chips)
    return reduce_events(device, spans, window_of(spans), kernels)
