"""Arithmetic shared by the metric readers (``bench/metrics``)."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) over every value,
    an infinite one (an answer that never came) included; None if empty."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def host_seconds(run) -> Optional[float]:
    """Host seconds of the engine's tick phases inside the window, the
    device fence (``device_wait``) left out; traced runs only."""
    if not run.phases:
        return None
    return sum(s for p, s in run.phases.items() if p != "device_wait")


def levels(run) -> int:
    """Ladder levels served inside the window, summed over jobs."""
    return sum(lv for *_shape, lv in run.job_levels)
