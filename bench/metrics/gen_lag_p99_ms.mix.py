"""99th percentile of how late the harness submitted a request after its
due time, in ms: a starved load generator shows here."""
from bench import stats


def read(run):
    lag = stats.percentile(((r.submitted - r.due) * 1e3
                            for r in run.window_records()), 99)
    return lag
