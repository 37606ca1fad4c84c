"""95th percentile, over every request due in the window, of the time from
when it was due to its first token (host clock).  A request that never
gave one counts the time to the end of the run."""

from bench import stats


def read(run):
    return 1e3 * stats.percentile(run.ttfts(), 95) if run.requests else None
