"""``chip_smoke.py`` on the CPU: its phases at a tiny size in interpret
mode, and its refusal to run without a TPU.

The script itself takes no size options: the shapes here are the test's
own, passed straight to the phase functions.
"""
import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke as cs  # noqa: E402
from repro.service.engine import EngineConfig  # noqa: E402

TINY = EngineConfig(n_slots=4, chains_per_slot=8, use_pallas=True,
                    interpret=True)
#: The paper job cut to d=16 and 16 chains, 5 levels of 10 steps.
TINY_PAPER = dataclasses.replace(cs.PAPER_REQ, dim=16, n_chains=16,
                                 T_min=960.0, N=10)


def test_main_refuses_a_cpu_backend(capsys):
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    assert '"ok"' not in out


def _served(f_best):
    req = SimpleNamespace(req_id=0, objective="rastrigin", dim=2)
    res = SimpleNamespace(f_best=f_best, x_best=np.zeros(2, np.float32))
    return cs.Served(None, [req], {0: res}, 0.0, 0.0, [])


@pytest.mark.parametrize("f_kernel, f_ref, ok", [
    (1.0, 1.0 + 1e-3, True),       # inside the relative term
    (1e-3, 1.0e-5, True),          # at an optimum of 0: the absolute term
    (0.0, 3e-3, False),
    (1000.0, 1003.0, False),
])
def test_compare_reference_tolerance(f_kernel, f_ref, ok):
    if ok:
        lines = cs.compare_reference("t", _served(f_kernel), _served(f_ref))
        assert len(lines) == 1 and "rastrigin d=2" in lines[0]
    else:
        with pytest.raises(cs.CheckFailed, match="vs reference"):
            cs.compare_reference("t", _served(f_kernel), _served(f_ref))


def test_paper_phase_tiny():
    assert TINY_PAPER.n_levels == 5
    with cs.capture_programs() as captured:
        kern, lines = cs.paper_phase(TINY, TINY_PAPER)
    assert kern.results[0].completed
    assert len(lines) == 1 and "schwefel d=16" in lines[0]
    assert sorted(captured) == ["_group_tick"]
    # Interpret mode lowers to plain XLA, and the CPU is no TPU: both
    # device checks must refuse this run.
    with pytest.raises(cs.CheckFailed, match="tpu_custom_call"):
        cs.check_kernels_in_programs(captured)
    with pytest.raises(cs.CheckFailed, match="interpret=True"):
        cs.check_device_path(kern, 1)


def test_mix_phase_tiny():
    kern, lines = cs.mix_phase(TINY, 3, seed=0)
    assert len(kern.results) == 3 and len(lines) == 3
    assert {r.objective for r in kern.reqs} == {"rastrigin", "ackley",
                                                "schwefel"}


def test_four_chip_phase_tiny():
    """Four logical shards (round-robin onto the one CPU device), the last
    drained mid-stream; every champion still equals its replay."""
    cfg = dataclasses.replace(TINY, n_slots=2, n_devices=4)
    # Seed 6: the last shard holds a job at tick 6, and no d >= 16 request
    # is served in one slot (XLA:CPU takes minutes to compile that
    # standalone replay at n_slots=1).
    kern, evacuated = cs.four_chip_phase(cfg, 4, seed=6, drain_tick=6)
    assert evacuated >= 1
    assert [i for i, _ in kern.engine.retired_shards] == [3]
    assert len(kern.results) == 4
    # An engine view that reports compiled kernels, so that the check
    # reaches the placement test: one CPU device is not four TPU chips.
    compiled = SimpleNamespace(
        use_pallas=True,
        cfg=dataclasses.replace(kern.engine.cfg, interpret=False))
    with pytest.raises(cs.CheckFailed, match="distinct TPU devices"):
        cs.check_device_path(dataclasses.replace(kern, engine=compiled), 4)
