"""Device idle milliseconds per ladder level while no ``sa.*`` span of the
engine was open on the host (the harness's loop, gaps between phases,
whatever else the process did), from the trace."""
from bench import stats, tracescopes


def read(run):
    got, levels = tracescopes.of_run(run), stats.levels(run)
    if not got or not got["spans"] or not levels:
        return None
    return got["idle_by_span"].get(tracescopes.NONE, 0.0) * 1e3 / levels
