"""Median completion latency, due time to the answer seen, over every
request due in the window (one that never came counts as infinite)."""
from bench import stats


def read(run):
    return stats.percentile((r.latency for r in run.window_records()), 50)
