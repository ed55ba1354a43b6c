"""Device milliseconds per ladder level under the group programs'
``sa.sweep`` scope (the sweep kernel and its glue), from the trace."""
from bench import stats, tracescopes


def read(run):
    got, levels = tracescopes.of_run(run), stats.levels(run)
    if not got or "sa.sweep" not in got["scopes"] or not levels:
        return None
    return got["scopes"]["sa.sweep"] * 1e3 / levels
