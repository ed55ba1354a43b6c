"""Reduce a profiler trace of the measured window to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with nothing but
``jax.profiler.ProfileData``.  The window is the harness's
``bench.window`` annotation on the host.  On each device plane
(``/device:TPU:<n>``) the ``XLA Ops`` line holds one event per device
operation and the ``XLA Modules`` line one per program execution.

* busy: the union of the operations' intervals inside the window, per
  device; ``busy_s`` is its mean over the devices the cell uses;
* ``ops``/``modules``: summed device seconds per operation / program
  name, over all devices;
* idle gaps: the stretches of the window in which no operation ran on a
  device, each named after the harness annotation (``bench.tick``,
  ``bench.sleep``, ...) that overlaps it most: what the host was doing.
"""
from __future__ import annotations

import collections
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
ANNOTATIONS = ("bench.tick", "bench.submit", "bench.observe", "bench.sleep")
TOP = 10


def newest_xplane(logdir: Path) -> Optional[Path]:
    found = sorted(Path(logdir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _overlap(gap, spans) -> Dict[str, float]:
    got = collections.Counter()
    for name, a, b in spans:
        c = _clip(a, b, *gap)
        if c:
            got[name] += c[1] - c[0]
    return got


def reduce_profile(pd, n_devices: int) -> Optional[dict]:
    """Numbers of one ``ProfileData``; None without a window or device."""
    host = next((p for p in pd.planes if p.name == HOST_PLANE), None)
    if host is None:
        return None
    window = None
    spans = []                  # (annotation, start_ns, end_ns)
    for line in host.lines:
        for e in line.events:
            if e.name == WINDOW and window is None:
                window = (e.start_ns, e.end_ns)
            elif e.name in ANNOTATIONS:
                spans.append((e.name, e.start_ns, e.end_ns))
    if window is None:
        return None
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(DEVICE_PREFIX)
                      and p.name[len(DEVICE_PREFIX):].isdigit()),
                     key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    ops: Dict[str, float] = collections.Counter()
    modules: Dict[str, float] = collections.Counter()
    op_text: Dict[str, str] = {}
    busy, gaps = [], []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            into = ops if line.name == OPS_LINE else modules
            for e in line.events:
                c = _clip(e.start_ns, e.end_ns, *window)
                if c is None:
                    continue
                into[e.name] += (c[1] - c[0]) * 1e-9
                if line.name == OPS_LINE:
                    intervals.append(c)
                    if e.name not in op_text:
                        op_text[e.name] = " ".join(str(v) for _k, v
                                                   in e.stats)
        if not intervals:
            continue
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                got = _overlap((a, b), spans)
                label = got.most_common(1)[0][0] if got else "other"
                gaps.append((label, (b - a) * 1e-9))
    if not busy:
        return None
    busy = busy[:n_devices]
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "devices_busy_s": busy,
        "ops": dict(ops),
        "op_text": op_text,
        "modules": dict(modules),
        "idle_by_host": dict(_sum_labels(gaps)),
        "breakdown": {
            "device_ops": [[n, s] for n, s in collections.Counter(ops)
                           .most_common(TOP)],
            "idle_gaps": [[n, s] for n, s in gaps[:TOP]],
        },
    }


def _sum_labels(gaps) -> Dict[str, float]:
    out = collections.Counter()
    for label, s in gaps:
        out[label] += s
    return out


def reduce_dir(logdir: Path, n_devices: int) -> Optional[dict]:
    from jax.profiler import ProfileData
    path = newest_xplane(logdir)
    if path is None:
        return None
    return reduce_profile(ProfileData.from_file(str(path)), n_devices)


def seconds_named(table: Dict[str, float], prefix: str) -> float:
    """Summed seconds of the entries whose name starts with ``prefix``."""
    return sum(s for n, s in table.items() if n.startswith(prefix))


def kernel_seconds(trace: dict, kernel: str) -> float:
    """Device seconds of the operations of a named kernel: those whose
    name starts with ``kernel``, or, where no operation is so named, the
    custom calls whose details (the event's stats) name it."""
    named = seconds_named(trace["ops"], kernel)
    if named:
        return named
    text = trace.get("op_text", {})
    return sum(s for n, s in trace["ops"].items()
               if "custom-call" in n and kernel in text.get(n, ""))
