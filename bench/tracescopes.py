"""The engine's own spans and scopes in a profiler trace of the window.

With telemetry on, the engine opens a profiler annotation for each tick
phase (``sa.<phase>``) and each sub-span under one (``sa.<phase>.<sub>``);
its group programs name their stages with ``jax.named_scope``
(``sa.controls``, ``sa.sweep``, ``sa.exchange``, and inside the exchange
``champion``, ``adopt``, ``pt_swap``, ``pa_resample``).  This reduces
them over the window that the harness's ``bench.window`` annotation
marks, from the ``.xplane.pb`` that :mod:`bench.tracereduce` reads:

* ``spans``: host seconds per ``sa.*`` annotation, clipped to the window;
* ``scopes``: device seconds per top-level ``sa.*`` scope, and per
  exchange stage as ``sa.exchange.<stage>``; operations under no ``sa.*``
  scope are summed under ``none``.  Operations nest (a ``while`` holds
  its body's), so each instant of device time goes to the innermost
  operation running: the values add up to ``busy_s``;
* ``idle_by_span``: device idle seconds (no operation running), each
  stretch named after the innermost ``sa.*`` span open on the host, or
  ``none``.  The values add up to the window minus ``busy_s``.

Both sums run over the cell's devices.  On the chip an operation's
scope path is the ``tf_op`` stat of its event's metadata (e.g.
``jit(_group_tick)/sa.exchange/champion/scatter-min:``), which
``jax.profiler.ProfileData`` does not expose: :func:`op_scopes` reads
it from the file's protobuf encoding, with nothing but the standard
library.  A program that opens no such span and names no such scope
gives empty ``spans`` and ``scopes``: its readers report nothing.
"""
from __future__ import annotations

import collections
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import tracereduce

PREFIX = "sa."
NONE = "none"
#: The exchange's stages, as ``serving_exchange`` names its scopes.
STAGES = ("champion", "adopt", "pt_swap", "pa_resample")
#: The metadata stat that holds an operation's scope path.
SCOPE_STAT = "tf_op"


# ----------------------------------------------------- protobuf encoding
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, ``(start, end)`` offsets for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _entry(buf: bytes, span) -> Tuple[int, object]:
    """(key, value span) of one map entry."""
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """Device plane name -> {operation name: scope path} (XSpace.planes
    = 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, str_value = 5, ref_value = 7)."""
    out = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(data, *plane):
            if g == 2:
                name = _text(data, v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith(tracereduce.DEVICE_PREFIX):
            continue
        stat_names = {}
        for span in stats:
            key, value = _entry(data, span)
            for g, v in _fields(data, *value):
                if g == 2:
                    stat_names[key] = _text(data, v)
        scope_id = next((k for k, n in stat_names.items()
                         if n == SCOPE_STAT), None)
        scopes = {}
        for span in events:
            op, path = None, None
            for g, v in _fields(data, *_entry(data, span)[1]):
                if g == 2:
                    op = _text(data, v)
                elif g == 5:
                    path = _stat(data, v, scope_id, stat_names) or path
            if op is not None and path:
                scopes.setdefault(op, path)
        out[name] = scopes
    return out


def _stat(buf, span, want, stat_names) -> Optional[str]:
    fields = dict(_fields(buf, *span))
    if want is None or fields.get(1) != want:
        return None
    if 5 in fields:
        return _text(buf, fields[5])
    return stat_names.get(fields.get(7))


# -------------------------------------------------------------- reduction
def scope_of(path: str) -> Optional[Tuple[str, Optional[str]]]:
    """(top-level ``sa.*`` scope, exchange stage or None) of a scope path,
    e.g. ``jit(_group_tick)/sa.exchange/champion/scatter-min:`` ->
    ``("sa.exchange", "champion")``; None outside every ``sa.*`` scope."""
    parts = [p.rstrip(":") for p in path.split("/")]
    for i, part in enumerate(parts):
        if part.startswith(PREFIX):
            stage = parts[i + 1] if i + 1 < len(parts) else None
            return part, stage if stage in STAGES else None
    return None


def innermost(intervals: List[Tuple[str, int, int]],
              within: Optional[List[Tuple[int, int]]] = None
              ) -> Dict[str, float]:
    """Seconds per name of the innermost open interval (the one opened
    last), counted only inside ``within`` (everywhere when None); where
    none is open but ``within`` is, the time goes to :data:`NONE`.
    Nanosecond bounds."""
    points = []
    for i, (_name, a, b) in enumerate(intervals):
        points += [(a, 1, i), (b, -1, i)]
    for a, b in within or ():
        points += [(a, 2, -1), (b, -2, -1)]
    points.sort(key=lambda p: p[0])
    out: Dict[str, float] = collections.Counter()
    opened: Dict[int, Tuple[int, int, int]] = {}
    inside = 0 if within is not None else 1
    t_prev = None
    for t, kind, i in points:
        if inside and t_prev is not None and t > t_prev:
            if opened:
                name = intervals[max(opened.values())[2]][0]
            elif within is None:
                name = None
            else:
                name = NONE
            if name is not None:
                out[name] += (t - t_prev) * 1e-9
        t_prev = t
        if kind == 1:
            # Opened last wins; of two opened together, the shorter.
            opened[i] = (intervals[i][1], -intervals[i][2], i)
        elif kind == -1:
            opened.pop(i, None)
        elif within is not None:
            inside += 1 if kind == 2 else -1
    return dict(out)


def reduce_profile(pd, n_devices: int,
                   scopes_by_plane: Dict[str, Dict[str, str]]
                   ) -> Optional[dict]:
    """``spans``, ``scopes`` and ``idle_by_span`` of one ``ProfileData``,
    given each device plane's operation scopes (:func:`op_scopes`); None
    without a window or a device operation in it."""
    host = next((p for p in pd.planes if p.name == tracereduce.HOST_PLANE),
                None)
    if host is None:
        return None
    window, spans = None, []
    for line in host.lines:
        for e in line.events:
            if e.name == tracereduce.WINDOW and window is None:
                window = (e.start_ns, e.end_ns)
            elif e.name.startswith(PREFIX):
                spans.append((e.name, e.start_ns, e.end_ns))
    if window is None:
        return None
    spans = [(n, *c) for n, a, b in spans
             if (c := tracereduce._clip(a, b, *window))]
    span_s: Dict[str, float] = collections.Counter()
    for name, a, b in spans:
        span_s[name] += (b - a) * 1e-9
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(tracereduce.DEVICE_PREFIX)
                      and p.name[len(tracereduce.DEVICE_PREFIX):].isdigit()),
                     key=lambda p: int(
                         p.name[len(tracereduce.DEVICE_PREFIX):]))
    scopes: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    found = False
    for plane in devices[:n_devices]:
        paths = scopes_by_plane.get(plane.name, {})
        ops = []
        for line in plane.lines:
            if line.name != tracereduce.OPS_LINE:
                continue
            for e in line.events:
                c = tracereduce._clip(e.start_ns, e.end_ns, *window)
                if c is not None:
                    ops.append((e.name, *c))
        if not ops:
            continue
        found = True
        for op, s in innermost(ops).items():
            got = scope_of(paths.get(op, ""))
            if got is None:
                scopes[NONE] += s
                continue
            top, stage = got
            scopes[top] += s
            if stage is not None:
                scopes[f"{top}.{stage}"] += s
        merged = tracereduce._union([(a, b) for _n, a, b in ops])
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, s in innermost(spans, gaps).items():
            idle[name] += s
    if not found:
        return None
    if all(k == NONE for k in scopes):
        scopes = {}
    return {"spans": dict(span_s), "scopes": dict(scopes),
            "idle_by_span": dict(idle)}


def reduce_file(path: Path, n_devices: int) -> Optional[dict]:
    """:func:`reduce_profile` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    return reduce_profile(ProfileData.from_serialized_xspace(data),
                          n_devices, op_scopes(data))


_CACHE: Dict[tuple, Optional[dict]] = {}


def reduce_dir(logdir: Path, n_devices: int) -> Optional[dict]:
    """:func:`reduce_file` of the newest trace under ``logdir``, read once
    however many readers ask."""
    path = tracereduce.newest_xplane(logdir)
    if path is None:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size, n_devices)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_file(path, n_devices)
    return _CACHE[key]


def of_run(run) -> Optional[dict]:
    """The reduction of a traced run's window: the trace the harness kept
    for the run's cell (``bench/.traces/<cell>``); None for an untraced
    run."""
    from bench import harness
    if run.trace is None:
        return None
    n_devices = int(run.cell.config["engine"].get("n_devices", 1))
    return reduce_dir(harness.TRACE_DIR / run.cell.name, n_devices)
