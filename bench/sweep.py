"""Sweep an open-loop cell's offered rate on the chip to find its knee.

    python3 bench/sweep.py --workload mix-poisson --rates 8,12,16 --seconds 20

One process, one engine, warmed up once; for each rate the cell's traffic
runs for ``--seconds`` at that rate (same seed, same generator), then the
engine is drained before the next.  One JSON line per rate: requests
offered and completed in the window, the requests still queued (not
admitted) and in flight at the close, and the latency median and 95th
percentile of the requests due in the window that completed by the
close.  The knee is the highest rate at which the queue does not grow
(at most :func:`backlog` requests queued at the close); a cell offers
load at a fixed share of it.  The sweep stops after the first rate past
the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, loadgen, spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from repro.service.engine import EngineConfig, SAServeEngine

    cell = spec.load_cell(args.workload)
    harness.use_compile_cache()
    try:
        harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    engine = SAServeEngine(EngineConfig(**cell.config["engine"]))
    n_dev = int(cell.config["engine"].get("n_devices", 1))
    harness.warm_up(engine, cell.config, cell.traffic, n_dev)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate=rate, at_close="stop")
        gen = loadgen.Generator(cell.config, traffic, args.seed)
        records, t0, t_close, ticks, *_ = harness.serve_window(
            engine, gen, traffic, args.seconds, harness.no_annotation)
        queued = len(engine.scheduler)
        window = [r for r in records.values() if r.in_window]
        done = [r.latency for r in window
                if r.result is not None and r.observed <= t_close]
        print(json.dumps({
            "rate": rate, "window_s": t_close - t0, "ticks": ticks,
            "offered": len(window), "completed": len(done),
            "queued_at_close": queued, "active_at_close": engine.n_active,
            "latency_p50_s": stats.percentile(done, 50),
            "latency_p95_s": stats.percentile(done, 95)}), flush=True)
        t = time.perf_counter()
        while not engine.done:
            engine.tick()
        print(f"[sweep] drained in {time.perf_counter() - t:.3f} s",
              file=sys.stderr)
        if queued > backlog(len(window)):
            break            # past the knee: higher rates only queue more
    return 0


def backlog(offered: int) -> int:
    """The most requests left queued at the close of a window that still
    counts as no growing backlog."""
    return max(4, int(0.03 * offered))


if __name__ == "__main__":
    sys.exit(main())
