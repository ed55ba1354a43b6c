"""95th percentile of due time -> admission (``RequestResult.admit_wall``
moved onto the harness clock through the submit stamp), over every
request due in the window: the scheduler's queueing."""
import math

from bench import stats


def _wait(rec):
    res = rec.result
    if res is None or not res.completed:
        return math.inf
    return rec.submitted + (res.admit_wall - res.submit_wall) - rec.due


def read(run):
    return stats.percentile((_wait(r) for r in run.window_records()), 95)
