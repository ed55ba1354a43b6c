"""How far the devices of a fleet drift apart inside one engine tick.

``SAServeEngine.tick`` launches one group program per shard, then
collects every shard before the next level: the tick ends with the last
device.  This reduces, for each harness tick (``bench.tick`` on the
host) inside the window (``bench.window``), the end of each device's
last group program (a ``_group_tick*`` execution on the ``XLA Modules``
line of its plane) from the ``.xplane.pb`` the traced run kept:

* ``lockstep_s``: per tick, the last device's end minus the first
  device's, over the devices that ran a group program in it (0 where
  fewer than two did);
* ``program_s``: each device's group-program seconds in the window.

It reads nothing of the engine's own spans, so it reads a program that
opens none as well.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import tracereduce

#: The engine's group programs, as their modules are named on the chip
#: (``jit__group_tick(<hash>)``, ``jit__group_tick_fused(...)``).
PROGRAM = "_group_tick"
TICK = "bench.tick"


def lockstep(ticks: List[Tuple[int, int]],
             ends: List[List[int]]) -> List[float]:
    """Per tick ``(start, end)`` (ns), the spread in seconds of the
    devices' last program ends inside it; ``ends`` holds each device's
    program end times (ns)."""
    ends = [sorted(dev) for dev in ends]
    out = []
    for a, b in ticks:
        last = [dev[i - 1] for dev in ends
                if (i := bisect.bisect_right(dev, b)) and dev[i - 1] >= a]
        out.append((max(last) - min(last)) * 1e-9 if len(last) > 1 else 0.0)
    return out


def reduce_profile(pd, n_devices: int) -> Optional[dict]:
    """``lockstep_s`` and ``program_s`` of one ``ProfileData``; None
    without a window or a group program in it."""
    host = next((p for p in pd.planes if p.name == tracereduce.HOST_PLANE),
                None)
    if host is None:
        return None
    window, ticks = None, []
    for line in host.lines:
        for e in line.events:
            if e.name == tracereduce.WINDOW and window is None:
                window = (e.start_ns, e.end_ns)
            elif e.name == TICK:
                ticks.append((e.start_ns, e.end_ns))
    if window is None:
        return None
    ticks = sorted(c for a, b in ticks
                   if (c := tracereduce._clip(a, b, *window)))
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(tracereduce.DEVICE_PREFIX)
                      and p.name[len(tracereduce.DEVICE_PREFIX):].isdigit()),
                     key=lambda p: int(
                         p.name[len(tracereduce.DEVICE_PREFIX):]))
    ends: List[List[int]] = []
    program_s: Dict[str, float] = {}
    for plane in devices[:n_devices]:
        mine, secs = [], 0.0
        for line in plane.lines:
            if line.name != tracereduce.MODULES_LINE:
                continue
            for e in line.events:
                c = (tracereduce._clip(e.start_ns, e.end_ns, *window)
                     if PROGRAM in e.name else None)
                if c is not None:
                    mine.append(e.end_ns)
                    secs += (c[1] - c[0]) * 1e-9
        if mine:
            ends.append(mine)
            program_s[plane.name] = secs
    if not ends:
        return None
    return {"lockstep_s": lockstep(ticks, ends), "program_s": program_s}


_CACHE: Dict[tuple, Optional[dict]] = {}


def reduce_dir(logdir: Path, n_devices: int) -> Optional[dict]:
    """:func:`reduce_profile` of the newest trace under ``logdir``, read
    once however many readers ask."""
    from jax.profiler import ProfileData
    path = tracereduce.newest_xplane(logdir)
    if path is None:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size, n_devices)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_profile(ProfileData.from_file(str(path)),
                                     n_devices)
    return _CACHE[key]


def of_run(run) -> Optional[dict]:
    """The reduction of a traced run's window (the trace the harness kept
    for the run's cell); None for an untraced run."""
    from bench import harness
    if run.trace is None:
        return None
    n_devices = int(run.cell.config["engine"].get("n_devices", 1))
    return reduce_dir(harness.TRACE_DIR / run.cell.name, n_devices)
