"""95th-percentile completion latency over every request due in the
window (nearest rank; one that never came counts as infinite)."""
from bench import stats


def read(run):
    return stats.percentile((r.latency for r in run.window_records()), 95)
