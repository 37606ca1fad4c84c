"""95th percentile of every gap between consecutive tokens of every
request due in the window (host clock; a token is available at the end
of the scheduler step that produced it)."""

from bench import stats


def read(run):
    gaps = [g for r in run.requests for g in r.gaps()]
    return 1e3 * stats.percentile(gaps, 95) if gaps else None
