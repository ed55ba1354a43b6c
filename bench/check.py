"""The comparison that decides ``correct``.

The answers judged are those the cell's traffic makes due: every request
due in the window (open loop, drained after the close), the jobs the
window completed (closed loop), and the jobs in flight at the close
(closed loop that ends its jobs there: each serves ``late_levels`` more
levels from the state it held at the close).  The reference module's
``judge`` reads two gaps of each answer (see
``bench/reference/continuous.py``); ``bench/checks/<cell>.json`` gives
``history_levels`` (the leading levels replayed; null: all),
``late_levels``, ``history_tol`` and the limits of the two numbers
compared:

* ``value_gap``: the widest value gap over the answers judged; an
  answer over the limit is failed;
* ``departed``: the share of the answers judged whose served best-so-far
  departs from the reference's replay by more than ``history_tol``.
  Two float32 programs with different transcendental code can flip one
  accept test now and then; a flip that lands on a level's champion
  parts the trajectory, which is a sound answer and not a fault.  A
  fault parts every trajectory.

An answer that never came, or was refused, is failed and departed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List


@dataclasses.dataclass
class Verdict:
    attempted: int
    failed: int
    numbers: Dict[str, dict]        # name -> {"value": ..., "limit": ...}
    widest_history_gap: float = 0.0  # for the record; not compared

    @property
    def correct(self) -> bool:
        return (self.attempted > 0 and self.failed == 0
                and all(v["value"] <= v["limit"]
                        for v in self.numbers.values()))

    def lines(self) -> List[str]:
        return [f"widest history_gap (not compared): "
                f"{self.widest_history_gap!r}"
                ] + [f"check {name}: {v['value']!r} limit {v['limit']!r}"
                     for name, v in self.numbers.items()]


def compared(gaps: List[dict], spec: dict) -> Dict[str, float]:
    """The numbers compared, from each judged answer's gaps (None for an
    answer that never came)."""
    tol = float(spec["history_tol"])
    value, departed = 0.0, 0
    for g in gaps:
        if g is None:
            value, departed = math.inf, departed + 1
            continue
        v = g["value_gap"]
        value = math.inf if math.isnan(v) else max(value, v)
        departed += not g["history_gap"] <= tol
    share = departed / len(gaps) if gaps else math.inf
    return {"value_gap": value, "departed": share}


def check(run, reference) -> Verdict:
    spec = run.cell.checks
    limits = spec["limits"]
    judged = [r for r in run.records if r.judged]
    gaps, failed = [], 0
    for rec in judged:
        res = rec.result
        if res is None or not res.completed:
            gaps.append(None)
            failed += 1
            continue
        g = reference.judge(rec.req, res, spec["history_levels"],
                            late=rec.late_start)
        gaps.append(g)
        failed += not g["value_gap"] <= limits["value_gap"]
    nums = compared(gaps, spec)
    hist = [math.inf if g is None else g["history_gap"] for g in gaps]
    return Verdict(attempted=len(judged), failed=failed,
                   numbers={n: {"value": nums[n], "limit": limits[n]}
                            for n in limits},
                   widest_history_gap=max(hist, default=0.0))
