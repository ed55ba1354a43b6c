"""Paper Table 7: single vs double precision — time and accuracy.

Paper: fp64 ~2x slower on Fermi, ~100x lower error; fp32 "enough for SA's
purpose".  We reproduce both directions.  Both precisions run in this
process — one process per chip — with the float64 case inside a
``jax.enable_x64`` scope, so the global config is untouched afterwards.
"""
from __future__ import annotations

import time

import jax

from repro.core import SAConfig, sa_minimize
from repro.objectives import functions as F

from .common import Budget, Table


def _run_one(dtype: str, quick: bool) -> dict:
    obj = F.schwefel(16)
    if quick:
        cfg = SAConfig(T0=100.0, T_min=0.05, rho=0.9, N=30, n_chains=1024,
                       dtype=dtype, record_history=False)
    else:
        cfg = SAConfig(T0=1000.0, T_min=0.01, rho=0.99, N=100,
                       n_chains=16384, dtype=dtype, record_history=False)
    sa_minimize(obj, cfg, key=jax.random.PRNGKey(0))  # warm compile
    t0 = time.perf_counter()
    res = sa_minimize(obj, cfg, key=jax.random.PRNGKey(1))
    jax.block_until_ready(res.f_best)
    dt = time.perf_counter() - t0
    df, dx = obj.error_to_opt(res.x_best, res.f_best)
    return {"dtype": dtype, "time_s": dt, "f_err": float(df),
            "x_err": float(dx)}


def run(budget: Budget) -> Table:
    t = Table(f"Table 7 — fp32 vs fp64 ({budget.label})",
              ["precision", "time_s", "|f-f*|", "rel-x err"],
              fmt={"time_s": ".2f", "|f-f*|": ".3e", "rel-x err": ".3e"})
    rows = {}
    for dtype in ("float32", "float64"):
        with jax.enable_x64(dtype == "float64"):
            r = _run_one(dtype, budget.quick)
        rows[dtype] = r
        t.add(precision=dtype, time_s=r["time_s"], **{"|f-f*|": r["f_err"],
                                                      "rel-x err": r["x_err"]})
    t.show()
    f32, f64 = rows["float32"], rows["float64"]
    print(f"[claim] fp64 slower (paper ~2x on GPU): "
          f"{f64['time_s']/max(f32['time_s'],1e-9):.2f}x; "
          f"fp64 more accurate: "
          f"{'OK' if f64['x_err'] <= f32['x_err'] * 2 else 'NOT SEEN'}")
    t.save("table7_precision")
    return t


if __name__ == "__main__":
    run(Budget(quick=True))
