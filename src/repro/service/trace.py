"""Chrome/Perfetto ``trace_event`` tracing for the serving engine.

:class:`TraceBuilder` collects trace events the engine emits while
serving — per-tick phase spans (``schedule / admit / dispatch /
device_wait / materialize / retire``, one timeline row per shard plus an
aggregate row) and per-request lifecycle tracks (submit → admit →
per-level ticks → preempt / migrate / shrink → complete) — and renders
them as one Trace Event Format JSON document (``serve_sa --trace
out.json``).  Open the file at https://ui.perfetto.dev (or
``chrome://tracing``): a drain-under-load run becomes a visually
debuggable timeline instead of a pile of counters.

Layout conventions
------------------
* ``pid`` 0 is the engine process.  ``tid`` 0 carries fleet-wide phase
  spans (schedule/admit); ``tid`` ``shard_index + 1`` carries that
  shard's dispatch/device_wait/materialize/retire spans.  Metadata
  events name them.  Sub-spans (category ``"subtick"``, named
  ``<phase>.<sub>``, telemetry.TICK_SUBPHASES) sit on their phase's
  track and nest inside it by time.
* Request lifecycles are **async** events: category ``"request"``, id
  ``req_id`` — ``b`` at submit, ``n`` instants for admit / level /
  preempt / resume / migrate / shrink, ``e`` at the terminal.  Perfetto
  draws each request as one track spanning its queueing + residence.
* Timestamps are **microseconds** on the engine's monotonic epoch — the
  same clock every wall figure in the repo shares (engine.py ``_now``).

The emitted document validates against the checked-in schema
(``trace_schema.json``, next to this module): :func:`validate_trace`
enforces it in tests and CI, so the trace contract cannot drift
silently.  The validator implements the JSON-Schema subset the schema
uses (type / required / properties / items / enum / minimum) — no
third-party dependency.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Optional

SCHEMA_PATH = Path(__file__).with_name("trace_schema.json")

_US = 1e6           # seconds -> trace microseconds


class TraceBuilder:
    """Accumulates Trace Event Format events (host-side, append-only)."""

    def __init__(self):
        self.events: List[dict] = []
        self._clock = None          # bound by the engine: epoch seconds
        self._named_tids = set()
        self._meta("process_name", {"name": "sa-serve-engine"}, tid=0)
        self._name_tid(0, "engine (schedule/admit)")

    # ------------------------------------------------------------- plumbing
    def bind_clock(self, clock) -> None:
        """Attach the engine's monotonic epoch clock (seconds)."""
        self._clock = clock

    def _now_us(self) -> float:
        return (self._clock() if self._clock is not None else 0.0) * _US

    def _meta(self, name: str, args: dict, tid: int) -> None:
        self.events.append({"ph": "M", "name": name, "pid": 0, "tid": tid,
                            "args": args})

    def _name_tid(self, tid: int, name: str) -> None:
        if tid not in self._named_tids:
            self._named_tids.add(tid)
            self._meta("thread_name", {"name": name}, tid=tid)

    def ensure_shard_track(self, shard_index: int) -> None:
        self._name_tid(shard_index + 1, f"shard {shard_index}")

    # ---------------------------------------------------------- phase spans
    def span(self, phase: str, t0: float, t1: float,
             shard: Optional[int] = None, tick: Optional[int] = None,
             cat: str = "tick") -> None:
        """One complete ('X') span, [t0, t1] in epoch seconds: a tick
        phase (``cat="tick"``) or a sub-span under one (``"subtick"``)."""
        tid = 0 if shard is None else shard + 1
        if shard is not None:
            self.ensure_shard_track(shard)
        ev = {"ph": "X", "name": phase, "cat": cat, "pid": 0, "tid": tid,
              "ts": t0 * _US, "dur": max(t1 - t0, 0.0) * _US}
        if tick is not None:
            ev["args"] = {"tick": tick}
        self.events.append(ev)

    # ------------------------------------------------------ request lifecycle
    def _async(self, ph: str, req_id: int, name: str, args: dict) -> None:
        self.events.append({"ph": ph, "cat": "request", "id": int(req_id),
                            "name": name, "pid": 0, "tid": 0,
                            "ts": self._now_us(), "args": args})

    def request_begin(self, req_id: int, **args) -> None:
        self._async("b", req_id, f"req{req_id}", args)

    def request_instant(self, req_id: int, what: str, **args) -> None:
        self._async("n", req_id, what, args)

    def request_end(self, req_id: int, **args) -> None:
        self._async("e", req_id, f"req{req_id}", args)

    # -------------------------------------------------------------- document
    def to_json(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def dumps(self) -> str:
        return json.dumps(self.to_json(), sort_keys=True)


# ------------------------------------------------------------------ validation
def load_schema() -> dict:
    return json.loads(SCHEMA_PATH.read_text(encoding="utf-8"))


def _check(doc, schema, path: str, errors: List[str]) -> None:
    t = schema.get("type")
    if t:
        ok = {"object": dict, "array": list, "string": str,
              "boolean": bool, "null": type(None)}
        if t == "number":
            good = isinstance(doc, (int, float)) \
                and not isinstance(doc, bool)
        elif t == "integer":
            good = isinstance(doc, int) and not isinstance(doc, bool)
        else:
            good = isinstance(doc, ok[t])
        if not good:
            errors.append(f"{path}: expected {t}, got {type(doc).__name__}")
            return
    if "enum" in schema and doc not in schema["enum"]:
        errors.append(f"{path}: {doc!r} not in {schema['enum']}")
    if "minimum" in schema and isinstance(doc, (int, float)) \
            and not isinstance(doc, bool) and doc < schema["minimum"]:
        errors.append(f"{path}: {doc} < minimum {schema['minimum']}")
    if isinstance(doc, dict):
        for req in schema.get("required", ()):
            if req not in doc:
                errors.append(f"{path}: missing required key {req!r}")
        for key, sub in schema.get("properties", {}).items():
            if key in doc:
                _check(doc[key], sub, f"{path}.{key}", errors)
    if isinstance(doc, list) and "items" in schema:
        for i, item in enumerate(doc):
            _check(item, schema["items"], f"{path}[{i}]", errors)


def validate_trace(doc: dict, schema: Optional[dict] = None) -> List[str]:
    """Validate a trace document against the checked-in schema.

    Returns the list of violations (empty == valid).  Phase-span events
    additionally get a semantic check the schema language cannot express:
    every ``X`` event's duration must be non-negative and its phase name
    drawn from the tick taxonomy (sub-spans: from its sub-span list).
    """
    from repro.service.telemetry import TICK_PHASES, TICK_SUBPHASES

    schema = load_schema() if schema is None else schema
    errors: List[str] = []
    _check(doc, schema, "$", errors)
    for i, ev in enumerate(doc.get("traceEvents", [])):
        if not isinstance(ev, dict):
            continue
        if ev.get("ph") == "X":
            if ev.get("dur", 0) < 0:
                errors.append(f"$.traceEvents[{i}]: negative dur")
            if ev.get("cat") == "tick" and ev.get("name") not in TICK_PHASES:
                errors.append(
                    f"$.traceEvents[{i}]: unknown tick phase "
                    f"{ev.get('name')!r}")
            if (ev.get("cat") == "subtick"
                    and ev.get("name") not in TICK_SUBPHASES):
                errors.append(
                    f"$.traceEvents[{i}]: unknown tick sub-span "
                    f"{ev.get('name')!r}")
    return errors
