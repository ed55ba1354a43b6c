"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, the program
under ``src/`` and this directory.  The cell's configuration, traffic,
checks and metrics are found by the names in ``BENCHMARK.json`` (see
``bench/spec.py``).  Set-up (JAX start, engine, warm-up of every program
the traffic uses) is timed as ``setup_s``; then the window runs for
``--seconds`` on the wall clock.  With ``--trace 1`` the window runs under
the profiler and engine telemetry, and the metrics are the cell's
per-layer ones; otherwise its end-to-end ones.

Progress and the numbers compared for ``correct`` (each beside its
limit, last) go to standard error; the last line of standard output is
one JSON object.  Without a TPU, or with fewer chips than the cell asks
for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402

#: Stands for a compared number that has no finite value (an answer that
#: never came or left its box): JSON has no infinity.
NOT_FINITE = 1e300


def _finite(obj):
    if isinstance(obj, float) and not math.isfinite(obj):
        return NOT_FINITE
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(_finite(out), allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
