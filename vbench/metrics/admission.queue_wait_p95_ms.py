"""95th percentile of the admission queue wait (dispatch stamp minus due
stamp, both stamped by the program) over the requests due in the
window; one never dispatched counts as infinite."""

import math

from vbench import stats


def read(run):
    waits = [(r.t_start - r.t_submit) * 1e3 if r.t_start is not None
             else math.inf
             for r in run.requests if run.in_window(r.t_submit)]
    if not waits:
        return None
    p95 = stats.percentile(waits, 95)
    return None if math.isinf(p95) else p95
