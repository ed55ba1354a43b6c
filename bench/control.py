"""The control of the correctness check, at a cell's own size.

    python3 bench/control.py --workload mix-poisson --seeds 1,2,3
    python3 bench/control.py --workload paper-d512 --seeds 1,2,3 --levels 60

The configuration states float32; the control is the plain reference
computed in bfloat16, put in the program's place: for the requests a
run of the cell judges (the window's requests, from the same generator
and seeds), the bfloat16 reference's answers go through the same
comparison (``judge``) as the served ones.  A sound check reads them
as not correct.  ``--levels`` ends each request after that many levels,
as a closed loop's close does (default: the whole ladder).  One JSON
line per seed with the numbers compared and the cell's limits.
Exits 2 without the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, loadgen, spec  # noqa: E402


def window_requests(cell, seed: int, seconds: float):
    """The requests a run of ``cell`` would judge, in order."""
    gen = loadgen.Generator(cell.config, cell.traffic, seed)
    if cell.traffic["loop"] == "open":
        reqs = []
        for off, req in gen.open_schedule(seconds):
            if off >= seconds:
                break
            reqs.append(req)
    else:
        stream = gen.closed_stream()
        reqs = [next(stream) for _ in range(int(cell.traffic["clients"]))]
    return reqs


def control_answer(ref, req, levels: int, dtype):
    """The reference's answer to ``req`` in ``dtype``, result-shaped."""
    history, x_best, f_best = ref.anneal(
        req.objective, req.dim, req.n_chains, req.seed, req.T0, req.rho,
        req.N, levels, dtype)
    return types.SimpleNamespace(x_best=x_best, f_best=f_best,
                                 champion_history=history,
                                 granted_chains=req.n_chains)


def control_numbers(cell, ref, reqs, levels: int, dtype) -> dict:
    """The numbers compared, over the control's answers to ``reqs``."""
    gaps = [ref.judge(req, control_answer(ref, req, levels or req.n_levels,
                                          dtype),
                      cell.checks["history_levels"])
            for req in reqs]
    return check.compared(gaps, cell.checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--levels", type=int, default=0)
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    cell = spec.load_cell(args.workload)
    seconds = spec.load_json(spec.ROOT / "BENCHMARK.json")["run_seconds"]
    harness.use_compile_cache()
    try:
        harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    ref = spec.reference(cell.config["family"])
    limits = cell.checks["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        reqs = window_requests(cell, seed, seconds)
        nums = control_numbers(cell, ref, reqs, args.levels, jnp.bfloat16)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "dtype": "bfloat16",
            "requests": len(reqs), "numbers": nums, "limits": limits,
            "fails_check": any(not nums[k] <= limits[k] for k in limits),
            "finite": all(math.isfinite(v) for v in nums.values())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
