"""95th percentile, over the requests due in the window, of the wait
from submission to admission (``RequestMetrics.admitted_at -
submitted_at``; host clock)."""

from bench import stats


def read(run):
    waits = [r.admitted - r.submitted for r in run.requests
             if r.admitted is not None]
    return 1e3 * stats.percentile(waits, 95) if waits else None
