"""The control of the correctness check at a test's size: the float32
reference in the program's place passes the cell's limits, the bfloat16
one (the control) fails them, and an altered answer fails them too."""
import dataclasses
import types

import jax.numpy as jnp
import pytest

from bench import check, control, spec
from bench_cells import load

SEED = 2**31 + 5


def tiny(name):
    cell = load(name)
    cfg = dict(cell.config)
    cfg["engine"] = dict(cfg["engine"], n_slots=4, chains_per_slot=8)
    if name == "paper-d512":
        cfg["problems"] = [["schwefel", 64]]
        cfg["slots_per_request"] = [4]
        return dataclasses.replace(cell, config=cfg)
    cfg["problems"] = [["rastrigin", 8], ["griewank", 16], ["salomon", 8]]
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, rate=2.0))


def _numbers(name, dtype, levels):
    cell = tiny(name)
    ref = spec.reference(cell.config["family"])
    reqs = control.window_requests(cell, SEED, 2.0)
    return cell, control.control_numbers(cell, ref, reqs, levels, dtype)


@pytest.mark.parametrize("name,levels", [("paper-d512", 12),
                                         ("mix-poisson", 0)])
def test_reference_in_the_programs_place_passes(name, levels):
    cell, nums = _numbers(name, jnp.float32, levels)
    limits = cell.checks["limits"]
    assert all(nums[k] <= limits[k] / 10 for k in limits), nums


@pytest.mark.parametrize("name,levels", [("paper-d512", 12),
                                         ("mix-poisson", 0)])
def test_bfloat16_control_fails(name, levels):
    cell, nums = _numbers(name, jnp.bfloat16, levels)
    limits = cell.checks["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums


def test_compared_numbers():
    spec_ = {"history_tol": 1e-3}
    ok = {"value_gap": 1e-5, "history_gap": 0.0}
    parted = {"value_gap": 2e-5, "history_gap": 0.5}
    assert check.compared([ok, parted, ok, ok], spec_) == \
        {"value_gap": 2e-5, "departed": 0.25}
    missing = check.compared([ok, None], spec_)
    assert missing == {"value_gap": float("inf"), "departed": 0.5}
    nan = check.compared([dict(ok, value_gap=float("nan"))], spec_)
    assert nan["value_gap"] == float("inf")
    assert check.compared([], spec_)["departed"] == float("inf")


def test_an_answer_out_of_its_box_or_altered_fails():
    cell = tiny("mix-poisson")
    ref = spec.reference("continuous")
    req = control.window_requests(cell, SEED, 2.0)[0]
    ans = control.control_answer(ref, req, req.n_levels, jnp.float32)
    good = ref.judge(req, ans, 6)
    assert good["value_gap"] < 1e-5 and good["history_gap"] < 1e-5
    lo, hi = ref.BOX[req.objective]
    outside = types.SimpleNamespace(**dict(vars(ans), x_best=ans.x_best * 0
                                           + 2 * hi))
    assert ref.judge(req, outside, 6)["value_gap"] == float("inf")
    altered = types.SimpleNamespace(**dict(vars(ans),
                                           f_best=ans.f_best + 0.5))
    assert ref.judge(req, altered, 6)["value_gap"] > 1e-2
