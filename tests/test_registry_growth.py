"""Registry growth: exponential + salomon join `SERVABLE` (PR 5).

Pure kernel-registry growth — both objectives are separable into the
radial sum accumulator S0 = sum(x_i^2), so the delta variant evaluates
single-coordinate moves in O(1), and runtime `kid` dispatch means the
widened registry adds ZERO new compiled programs (compile-count test).

Parity ladder per new objective:

  host suite fn (objectives/functions.py)
    == kernel-side full_eval (objective_math.py)          [values]
    == Pallas kernel, interpret mode (metropolis_sweep)   [vs ref oracle]
  and delta variant == full variant accumulators,
  and engine co-batch == run_standalone (bit-exact champions).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import objective_math as om
from repro.kernels import ref
from repro.kernels.metropolis_sweep import metropolis_sweep_pallas
from repro.objectives import functions as F
from repro.objectives.families import F_OPT_BY_NAME
from repro.service import (
    EngineConfig,
    SARequest,
    SAServeEngine,
    SERVABLE,
    run_standalone,
)

CPS = 8

NEW_KIDS = {om.KID_EXPONENTIAL: F.exponential, om.KID_SALOMON: F.salomon}
NEW_NAMES = ("exponential", "salomon")
ALL_NAMES = ["schwefel", "rastrigin", "ackley", "griewank", "exponential", "salomon"]


def _x0(kid, chains, dim, seed=0):
    lo, hi = om.BOX[kid]
    u = jax.random.uniform(jax.random.PRNGKey(seed), (chains, dim))
    return (lo + u * (hi - lo)).astype(jnp.float32)


def _req(req_id, objective, **kw):
    kw.setdefault("dim", 4)
    kw.setdefault("n_chains", CPS)
    kw.setdefault("T0", 50.0)
    kw.setdefault("T_min", 1.0)
    kw.setdefault("rho", 0.7)
    kw.setdefault("N", 10)
    return SARequest(req_id=req_id, objective=objective, seed=100 + req_id, **kw)


def _cfg(**kw):
    kw.setdefault("n_slots", 4)
    return EngineConfig(chains_per_slot=CPS, use_pallas=False, **kw)


def test_registry_is_widened_consistently():
    """Every registry surface agrees on the two new objectives: names,
    kids, boxes, host-suite kernel_id backlinks and F_OPT_BY_NAME optima."""
    assert set(NEW_NAMES) <= set(SERVABLE)
    assert om.N_KIDS == 6
    assert set(F_OPT_BY_NAME) == set(om.KID_BY_NAME)
    assert set(om.BOX) == set(om.KID_BY_NAME.values())
    for kid, maker in NEW_KIDS.items():
        obj = maker(8)
        assert obj.kernel_id == kid
        name = next(n for n, k in om.KID_BY_NAME.items() if k == kid)
        assert obj.f_opt == F_OPT_BY_NAME[name]
        lo, hi = om.BOX[kid]
        assert (obj.lower[0], obj.upper[0]) == (lo, hi)


@pytest.mark.parametrize("kid", sorted(NEW_KIDS))
def test_full_eval_matches_host_objective(kid):
    obj = NEW_KIDS[kid](16)
    x = _x0(kid, 8, 16, seed=kid)
    f_k = np.asarray(om.full_eval(kid, x, 16)[:, 0])
    np.testing.assert_allclose(f_k, np.asarray(obj(x)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kid", sorted(NEW_KIDS))
def test_accumulator_decomposition_matches_full_eval(kid):
    """init_acc + combine (the delta-variant bookkeeping) reproduces the
    direct evaluation — the separability claim for the new objectives."""
    x = _x0(kid, 8, 12, seed=3 + kid)
    S, logP, sgnP = om.init_acc(kid, x)
    f_acc = np.asarray(om.combine(kid, S, logP, sgnP, 12))
    f_dir = np.asarray(om.full_eval(kid, x, 12))
    np.testing.assert_allclose(f_acc, f_dir, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kid", sorted(NEW_KIDS))
@pytest.mark.parametrize("variant", ["full", "delta"])
def test_kernel_matches_oracle_for_new_objectives(kid, variant):
    """Kernel-vs-oracle parity (the satellite requirement), both
    evaluation variants, interpret mode."""
    chains, dim, n_steps = 16, 8, 12
    x = _x0(kid, chains, dim)
    kw = dict(kid=kid, n_steps=n_steps, variant=variant)
    xk, fk = metropolis_sweep_pallas(x, 3.0, 42, 0, blk=8, interpret=True, **kw)
    xr, fr = ref.metropolis_sweep_ref(x, 3.0, 42, 0, **kw)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kid", sorted(NEW_KIDS))
def test_runtime_dispatch_matches_static_trajectory(kid):
    """Runtime-kid lowering follows the identical state trajectory as the
    static single-branch specialization for the new objectives."""
    x = _x0(kid, 8, 4, seed=7)
    kids = jnp.asarray([kid], jnp.int32)
    kw = dict(n_steps=8, blk=8, variant="delta", interpret=True)
    xa, _ = metropolis_sweep_pallas(x, 2.0, 7, 0, kid=kids, **kw)
    xs, _ = metropolis_sweep_pallas(x, 2.0, 7, 0, kid=kid, **kw)
    np.testing.assert_array_equal(np.asarray(xa), np.asarray(xs))


def test_new_objectives_serve_bit_exact_and_share_one_program():
    """The engine co-batches all six registry objectives in ONE compiled
    sweep program per (dim, N) — widening `SERVABLE` costs zero new
    lowerings — and every champion is bit-exact versus standalone."""
    from repro.service.engine import _group_tick

    cfg = _cfg(n_slots=6)
    engine = SAServeEngine(cfg)
    reqs = [_req(i, obj) for i, obj in enumerate(ALL_NAMES)]
    for r in reqs:
        engine.submit(r)
    has_cc = hasattr(_group_tick, "clear_cache")
    can_count = has_cc and hasattr(_group_tick, "_cache_size")
    if can_count:
        _group_tick.clear_cache()
    results = {r.req_id: r for r in engine.run(max_ticks=200)}
    assert len(results) == 6
    if can_count:
        assert _group_tick._cache_size() == 1
    for r in reqs:
        solo = run_standalone(r, cfg)
        assert results[r.req_id].f_best == solo.f_best
        np.testing.assert_array_equal(results[r.req_id].x_best, solo.x_best)
        assert results[r.req_id].champion_history == solo.champion_history


@pytest.mark.parametrize("dim,n_steps,macro_k", [(4, 10, 4), (8, 10, 4), (4, 10, 2)])
def test_fused_macro_tick_compiles_one_program_per_shape(dim, n_steps, macro_k):
    """Compile stability under macro-tick fusion: co-batching all six
    SERVABLE objectives at one (dim, N, K) traces exactly ONE group
    program — the K-level loop keeps the objective id a runtime input —
    and every champion stays bit-exact vs standalone."""
    from repro.service.engine import _group_tick

    can_count = hasattr(_group_tick, "clear_cache") and hasattr(
        _group_tick, "_cache_size"
    )
    if can_count:
        _group_tick.clear_cache()
    cfg = _cfg(n_slots=6, macro_k=macro_k)
    engine = SAServeEngine(cfg)
    reqs = [_req(i, obj, dim=dim, N=n_steps) for i, obj in enumerate(ALL_NAMES)]
    for r in reqs:
        engine.submit(r)
    results = {r.req_id: r for r in engine.run(max_ticks=200)}
    assert len(results) == 6
    if can_count:
        # One lowering serves the whole registry at this shape.
        assert _group_tick._cache_size() == 1
    for r in reqs:
        solo = run_standalone(r, cfg)
        assert results[r.req_id].f_best == solo.f_best
        assert results[r.req_id].champion_history == solo.champion_history


@pytest.mark.parametrize("name", NEW_NAMES)
def test_new_objectives_anneal_toward_their_optimum(name):
    """Sanity: a short ladder makes real progress toward the registered
    optimum (loose bound — this is an anneal, not a solve)."""
    req = _req(0, name, dim=4, T0=10.0, T_min=0.05, rho=0.6, N=40)
    res = run_standalone(req, _cfg())
    x0_best = float(np.min(om.full_eval(req.kid, _x0(req.kid, CPS, 4), 4)))
    assert res.f_best <= x0_best + 1e-6, "annealing never improved"
    assert res.f_best >= F_OPT_BY_NAME[name] - 1e-5, "beat the global optimum?!"


def test_target_error_supported_on_new_objectives():
    """F_OPT_BY_NAME registration makes accuracy-target stopping legal for the
    new objectives (the submit-time guard must not fire)."""
    engine = SAServeEngine(_cfg())
    engine.submit(_req(0, "exponential", target_error=10.0, T0=10.0, rho=0.5))
    engine.submit(_req(1, "salomon", target_error=50.0, T0=10.0, rho=0.5))
    results = {r.req_id: r for r in engine.run(max_ticks=100)}
    assert results[0].finish_reason == "target"
    assert results[1].finish_reason == "target"
