"""Spans and counters of the serving path, on the host's clock.

A span is a name, a start and an end on `time.perf_counter()` (the clock
of the benchmark's window), the span it nests in, and one integer
argument: a micro-batch's id, or a collection's generation.  Spans are
kept in a fixed ring of preallocated arrays, so recording one creates no
object that outlives it and gives Python's young collections nothing to
count.  When the ring is full the oldest spans are overwritten and
counted as ``dropped``.

Recording is on while a `jax.profiler` session is active, or after
`enable()`.  While a session is active every span is also written as a
`jax.profiler.TraceAnnotation`, which puts the program's spans on the
device trace's clock.  Each serving call (`AdmissionController.step`,
`VisionServer.dispatch` / `complete`) asks `on()` once and records its
child spans only then: off, a call costs that one check.

A hook on `gc.callbacks` counts the collections of each generation and
their pause seconds, always, and while recording is on writes each
collection as a span ``host.gc`` (argument: the generation) inside
whatever span was open.

The serving path's spans, by parent::

    admission.step     > admission.assemble, serve.dispatch, serve.complete
    serve.dispatch     > serve.stack, serve.place, serve.launch
    serve.complete     > serve.wait, serve.readback, serve.stamp

``serve.dispatch`` and ``serve.complete`` carry the micro-batch's id,
which is also ``InFlight.batch`` and each request's ``batch``.

    from repro.core import spans
    spans.enable()
    ...                                  # serve
    spans.summary()                      # per name: count, p50, p95, total
    spans.select("serve.place", t0, t1)  # durations that started in a window
"""

from __future__ import annotations

import gc
import math
import time
from array import array
from typing import Dict, List, Optional

import jax
import numpy as np

# 2^19 spans: about 80 s of the one-frame-at-a-time traffic, whose
# frames take 11 spans each at some 540 frames/s
CAPACITY = 1 << 19
GC_SPAN = "host.gc"

_clock = time.perf_counter
_Note = jax.profiler.TraceAnnotation
_profiling = _Note.is_enabled
_OPEN = math.nan


class Recorder:
    """A ring of spans with a stack of the open ones, and the GC counters."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity <= 0 or capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, got "
                             f"{capacity}")
        self.capacity = capacity
        self._mask = capacity - 1
        self._name = array("i", [0]) * capacity
        self._arg = array("q", [0]) * capacity
        self._parent = array("q", [0]) * capacity
        self._t0 = array("d", [0.0]) * capacity
        self._t1 = array("d", [0.0]) * capacity
        self.names: List[str] = []
        self._ids: Dict[str, int] = {}
        self.n = 0                   # spans begun; the next one's sequence
        self._top = -1               # the innermost open span
        self.enabled = False
        self.annotate = False        # write spans to the profiler too
        self._notes: list = []       # open annotations, innermost last
        self._note_seqs: List[int] = []
        self.gc_collections = [0, 0, 0]
        self.gc_pause_s = [0.0, 0.0, 0.0]
        self._gc_t0 = 0.0
        self._gc_note = None

    # -- recording --------------------------------------------------------

    def on(self) -> bool:
        """Whether the calling serving call records its spans: after
        `enable()`, or while a profiler session is active (its spans then
        go to the profiler's trace as well)."""
        self.annotate = _profiling()
        return self.enabled or self.annotate

    def _id(self, name: str) -> int:
        i = self._ids.get(name)
        if i is None:
            i = self._ids[name] = len(self.names)
            self.names.append(name)
        return i

    def begin(self, name: str, arg: int = 0) -> int:
        """Open a span inside the innermost open one; returns its
        sequence number, for `end`."""
        if self.annotate:
            # made first: making it may run a collection, whose span is
            # then complete before this one takes its place in the ring
            note = _Note(name, arg=arg)
            note.__enter__()
            self._notes.append(note)
            self._note_seqs.append(self.n)
        seq = self._top = self.record(name, 0.0, _OPEN, arg)
        self._t0[seq & self._mask] = _clock()
        return seq

    def end(self, seq: int) -> None:
        """Close span ``seq``, and with it any span opened inside it and
        left open (a call that raised)."""
        t = _clock()
        i = seq & self._mask
        self._t1[i] = t
        self._top = self._parent[i]
        seqs = self._note_seqs
        while seqs and seqs[-1] >= seq:
            seqs.pop()
            self._notes.pop().__exit__(None, None, None)

    def switch(self, seq: int, name: str, arg: int = 0) -> int:
        """End span ``seq`` and begin its next sibling."""
        self.end(seq)
        return self.begin(name, arg)

    def record(self, name: str, t0: float, t1: float, arg: int = 0) -> int:
        """Write a span whose times are known, inside the innermost open
        span."""
        seq = self.n
        self.n = seq + 1
        i = seq & self._mask
        self._name[i] = self._id(name)
        self._arg[i] = arg
        self._parent[i] = self._top
        self._t0[i] = t0
        self._t1[i] = t1
        return seq

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if _profiling():
                self._gc_note = _Note(GC_SPAN, arg=gen)
                self._gc_note.__enter__()
            self._gc_t0 = _clock()
            return
        t = _clock()
        if self._gc_note is not None:
            self._gc_note.__exit__(None, None, None)
            self._gc_note = None
        self.gc_collections[gen] += 1
        self.gc_pause_s[gen] += t - self._gc_t0
        if self.enabled or _profiling():
            self.record(GC_SPAN, self._gc_t0, t, gen)

    def watch_gc(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    # -- reading ----------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Spans the ring has overwritten."""
        return max(0, self.n - self.capacity)

    def table(self) -> Dict[str, np.ndarray]:
        """The spans the ring holds, oldest first, as columns: ``seq``,
        ``name`` (an index into ``names``), ``arg``, ``parent`` (a
        sequence number; -1 for none), ``t0``, ``t1`` (NaN while open)."""
        first = self.n - min(self.n, self.capacity)
        seq = np.arange(first, self.n, dtype=np.int64)
        i = seq & self._mask
        col = {k: np.frombuffer(getattr(self, "_" + k), dtype=dtype)[i]
               for k, dtype in (("name", np.int32), ("arg", np.int64),
                                ("parent", np.int64), ("t0", np.float64),
                                ("t1", np.float64))}
        col["seq"] = seq
        return col

    def select(self, name: str, lo: float = -math.inf,
               hi: float = math.inf) -> Optional[np.ndarray]:
        """Durations (s) of the closed spans ``name`` that started in
        [lo, hi), in order; None where the ring has overwritten spans
        that may have started there."""
        if self.dropped and self._t0[self.n & self._mask] >= lo:
            return None
        t = self.table()
        pick = ((t["name"] == self._ids.get(name, -1)) & (t["t0"] >= lo)
                & (t["t0"] < hi) & (t["t1"] >= t["t0"]))
        return (t["t1"] - t["t0"])[pick]

    def summary(self, lo: float = -math.inf,
                hi: float = math.inf) -> Dict[str, object]:
        """Per span name, of the closed spans that started in [lo, hi):
        count, p50 and p95 (ms), total and self seconds (self: less the
        time of the spans directly inside); and the counters."""
        t = self.table()
        closed = t["t1"] >= t["t0"]
        dur = np.where(closed, t["t1"] - t["t0"], 0.0)
        own = dur.copy()
        first = t["seq"][0] if len(t["seq"]) else 0
        inside = (t["parent"] >= first) & closed
        np.subtract.at(own, t["parent"][inside] - first, dur[inside])
        keep = closed & (t["t0"] >= lo) & (t["t0"] < hi)
        out = {}
        for k, name in enumerate(self.names):
            pick = keep & (t["name"] == k)
            if not pick.any():
                continue
            d = dur[pick]
            out[name] = {"count": int(pick.sum()),
                         "p50_ms": float(np.percentile(d, 50)) * 1e3,
                         "p95_ms": float(np.percentile(d, 95)) * 1e3,
                         "total_s": float(d.sum()),
                         "self_s": float(own[pick].sum())}
        return {"spans": out,
                "counters": {"dropped": self.dropped,
                             "gc_collections": list(self.gc_collections),
                             "gc_pause_s": list(self.gc_pause_s)}}


RECORDER = Recorder()
RECORDER.watch_gc()

on = RECORDER.on
begin = RECORDER.begin
end = RECORDER.end
switch = RECORDER.switch
select = RECORDER.select
summary = RECORDER.summary


def enable() -> None:
    """Record spans with no profiler session (an operator's own run)."""
    RECORDER.enabled = True


def disable() -> None:
    RECORDER.enabled = False
