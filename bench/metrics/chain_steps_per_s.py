"""Metropolis proposals (chain-steps) of the ladder levels completed
inside the window, over the window's seconds."""


def read(run):
    steps = sum(n * chains * lv for _o, _d, n, chains, lv in run.job_levels)
    return steps / run.window_s if steps else None
