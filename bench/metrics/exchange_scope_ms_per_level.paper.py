"""Device milliseconds per ladder level under the group programs'
``sa.exchange`` scope (``core/exchange.serving_exchange``: champion
reduce, adoption, PT swap, PA resample), from the trace."""
from bench import stats, tracescopes


def read(run):
    got, levels = tracescopes.of_run(run), stats.levels(run)
    if not got or "sa.exchange" not in got["scopes"] or not levels:
        return None
    return got["scopes"]["sa.exchange"] * 1e3 / levels
