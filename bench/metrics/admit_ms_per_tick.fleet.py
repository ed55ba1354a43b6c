"""Host milliseconds of the engine's ``admit`` phase per tick in the window:
executing the scheduler's plans, with the spans under it
(``sa.admit.place``, ``admit.init_state``, ``admit.restore``); traced
run."""


def read(run):
    if not run.phases or not run.ticks:
        return None
    return run.phases.get("admit", 0.0) * 1e3 / run.ticks
