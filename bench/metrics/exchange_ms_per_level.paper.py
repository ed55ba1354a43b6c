"""Device milliseconds per ladder level of the group program outside the
sweep kernel: the ``_group_tick`` programs' time minus the sweep
kernel's, from the trace (the exchange and its control glue)."""
from bench import stats, tracereduce

PROGRAM = "_group_tick"
KERNEL = "metropolis_sweep"


def read(run):
    if run.trace is None:
        return None
    program = sum(s for n, s in run.trace["modules"].items() if PROGRAM in n)
    kernel = tracereduce.kernel_seconds(run.trace, KERNEL)
    levels = stats.levels(run)
    if not program or not levels:
        return None
    return (program - kernel) * 1e3 / levels
