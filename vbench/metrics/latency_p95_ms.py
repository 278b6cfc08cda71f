"""95th percentile, over every request due in the window, of the time
from its due time to its logits on the host.  A request not answered one
SLA after the window closes counts as infinitely late."""

from vbench import stats

# a p95 that reaches unanswered requests is reported at this many ms
UNANSWERED_MS = 1e9


def read(run):
    due = [r for r in run.requests if run.in_window(r.t_submit)]
    if not due:
        return None
    deadline = run.window[1] + (run.sla_ms or 0.0) / 1e3
    lat = stats.late_latencies_ms([r.t_submit for r in due],
                                  [r.t_done for r in due], deadline)
    p95 = stats.percentile(lat, 95)
    return UNANSWERED_MS if p95 == float("inf") else p95
