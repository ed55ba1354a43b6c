"""Host milliseconds of the engine's tick phases per ladder level served
in the window (telemetry, device fence left out; traced run)."""
from bench import stats


def read(run):
    host, levels = stats.host_seconds(run), stats.levels(run)
    return host * 1e3 / levels if host is not None and levels else None
