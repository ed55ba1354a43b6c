"""The harness end to end on the CPU at a tiny size: the plain reference
agrees with the engine's Pallas kernels (interpret mode), and each fault
planted under the timed path makes ``correct`` come out false."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench_cells import load
from repro.service import engine as engine_mod

SEED = 2**31 + 99


def tiny(name, **engine):
    """The cell ``name`` cut to a CPU-sized pool (same traffic, checks)."""
    cell = load(name)
    cfg = dict(cell.config)
    cfg["engine"] = dict(cfg["engine"], n_slots=4, chains_per_slot=8,
                         n_devices=1, **engine)
    if name == "paper-d512":
        # Cooler than the paper's T0=1000, so that a tiny population
        # descends and its champion moves from level to level.
        cfg["problems"] = [["schwefel", 16]]
        cfg["schedules"] = [dict(cfg["schedules"][0], T0=10.0)]
        cfg["slots_per_request"] = [4]
    else:
        cfg["problems"] = [["rastrigin", 8], ["griewank", 8]]
        cfg["schedules"] = cfg["schedules"][:2]
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, rate=3.0))
    return dataclasses.replace(cell, config=cfg)


def run(cell, seconds=1.5):
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            require_chip=False)


@pytest.fixture
def fresh_programs():
    """Trace the engine's programs anew around a test that patches what
    they call."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", ["paper-d512", "mix-poisson"])
def test_reference_agrees_with_the_kernel(name):
    out = run(tiny(name, use_pallas=True, interpret=True))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    for v in out["checks"].values():     # well inside every limit
        assert v["value"] < v["limit"] / 10


def _unchanged(orig):
    def sweep(x, kids, *a, live=None, **kw):
        return orig(x, kids, *a, live=jnp.zeros(kids.shape, jnp.int32), **kw)
    return sweep


def _unchanged_late(orig):
    """The state returned unchanged from level 10 on: past the leading
    levels the check replays, so only the late stretch can see it."""
    def sweep(x, kids, T, seeds, step0s, *a, live=None, n_steps, **kw):
        keep = (step0s < 10 * n_steps).astype(jnp.int32)
        if live is not None:
            keep = keep * live
        return orig(x, kids, T, seeds, step0s, *a, live=keep,
                    n_steps=n_steps, **kw)
    return sweep


def _half_left_out(orig):
    def sweep(x, kids, *a, live=None, **kw):
        n = kids.shape[0]
        half = (jnp.arange(n) < max(1, n // 2)).astype(jnp.int32)
        return orig(x, kids, *a, live=half, **kw)
    return sweep


def _no_exchange(orig):
    def exchange(x, fx, seg, num_segments, adopt, *a):
        return orig(x, fx, seg, num_segments, jnp.zeros_like(adopt), *a)
    return exchange


def _answer_altered(orig):
    def exchange(*a):
        x, fx, xb, fb = orig(*a)
        return x, fx, xb, fb + jnp.float32(0.5)
    return exchange


FAULTS = {
    "state_unchanged": ("ops", "metropolis_sweep_slots", _unchanged),
    "state_unchanged_after_level_10": ("ops", "metropolis_sweep_slots",
                                       _unchanged_late),
    "half_the_blocks_left_out": ("ops", "metropolis_sweep_slots",
                                 _half_left_out),
    "exchange_left_out": ("exch", "serving_exchange", _no_exchange),
    "answer_altered": ("exch", "serving_exchange", _answer_altered),
}


def test_sound_run_is_correct(fresh_programs):
    out = run(tiny("paper-d512"))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(fault, monkeypatch, fresh_programs):
    module, attr, make = FAULTS[fault]
    target = getattr(engine_mod, module)
    monkeypatch.setattr(target, attr, make(getattr(target, attr)))
    out = run(tiny("paper-d512"))
    assert not out["correct"], (fault, out["checks"])
