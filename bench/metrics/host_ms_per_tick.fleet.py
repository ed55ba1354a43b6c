"""Host milliseconds of the engine's tick phases per tick in the window,
over all shards (telemetry, device fence left out; traced run)."""
from bench import stats


def read(run):
    host = stats.host_seconds(run)
    return host * 1e3 / run.ticks if host is not None and run.ticks else None
