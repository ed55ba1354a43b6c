"""Host milliseconds per ladder level moving chain state between host and
chip: the engine's ``sa.dispatch.h2d`` and ``sa.materialize.d2h`` spans
in the trace."""
from bench import stats, tracescopes

SPANS = ("sa.dispatch.h2d", "sa.materialize.d2h")


def read(run):
    got, levels = tracescopes.of_run(run), stats.levels(run)
    if not got or not any(s in got["spans"] for s in SPANS) or not levels:
        return None
    return sum(got["spans"].get(s, 0.0) for s in SPANS) * 1e3 / levels
