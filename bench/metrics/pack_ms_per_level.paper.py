"""Host milliseconds per ladder level packing the group's state and
controls and scattering the state back into slots: the engine's
``sa.dispatch.pack`` and ``sa.materialize.scatter`` spans in the trace."""
from bench import stats, tracescopes

SPANS = ("sa.dispatch.pack", "sa.materialize.scatter")


def read(run):
    got, levels = tracescopes.of_run(run), stats.levels(run)
    if not got or not any(s in got["spans"] for s in SPANS) or not levels:
        return None
    return sum(got["spans"].get(s, 0.0) for s in SPANS) * 1e3 / levels
