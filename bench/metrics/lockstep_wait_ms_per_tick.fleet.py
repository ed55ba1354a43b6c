"""Device lock-step wait per tick in the window, in milliseconds: in each
tick, the end of the last device's group program minus the end of the
first device's (the tick collects every shard before the next level);
from the trace (``bench/tracefleet.py``)."""
from bench import tracefleet


def read(run):
    got = tracefleet.of_run(run)
    if not got or not run.ticks:
        return None
    return sum(got["lockstep_s"]) * 1e3 / run.ticks
