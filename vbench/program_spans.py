"""Readers of the program's own spans (``repro.core.spans``) in a run's
window: the spans the serving path records where the work happens, on
the window's clock (``time.perf_counter``).  The program records them in
a traced run, while the profiler is on.

Each reader returns None where the program records no spans (a program
that predates them), where the window holds none of the spans read, or
where the program's ring overwrote spans that may have started in the
window."""

from __future__ import annotations

from vbench import stats


def recorder():
    """The program's span recorder, or None where it has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.RECORDER


def durations(run, name: str):
    """Durations (s) of the program's spans ``name`` that started in the
    window, or None (see above)."""
    rec = recorder()
    if rec is None:
        return None
    d = rec.select(name, *run.window)
    return d if d is not None and len(d) else None


def median_ms(run, name: str):
    d = durations(run, name)
    return stats.percentile(d, 50) * 1e3 if d is not None else None


def gc_per_batch(run):
    """Python collections (spans ``host.gc``, every generation) per
    micro-batch dispatched (spans ``serve.dispatch``) in the window."""
    rec = recorder()
    if rec is None:
        return None
    batches = durations(run, "serve.dispatch")
    collections = rec.select("host.gc", *run.window)
    if batches is None or collections is None:
        return None
    return len(collections) / len(batches)
