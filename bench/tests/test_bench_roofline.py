"""The roofline's operation and byte counts against hand counts."""
import pytest

from bench import spec

roof = spec.load_module(spec.BENCH_DIR / "metrics" / "sweep_roofline_pct.py")

# Hand count, schwefel, per chain-step:
#   threefry2x32 x2: (2 key adds + 20 rounds x 5 + 5 injections x 3) x 2 = 234
#   uniforms 3 x 3 = 9, coordinate 1, new value 2, accept 6, selects 2  = 20
#   term twice 2 x 4 = 8, S update 2, S select 1, combine 2             = 13
SCHWEFEL_STEP = 234 + 20 + 13


def test_ops_per_step_hand_count():
    assert roof.ops_per_step("schwefel") == SCHWEFEL_STEP == 267
    # rastrigin: term 5 (x2), update 2, select 1, combine 1
    assert roof.ops_per_step("rastrigin") == 254 + 10 + 2 + 1 + 1


@pytest.mark.parametrize("dim", [8, 512])
def test_bytes_per_level_hand_count(dim):
    chains = 16384
    # state read + written once (float32), one value per chain written
    assert roof.bytes_per_level(chains, dim) == 2 * chains * dim * 4 + chains * 4


@pytest.mark.parametrize("dim,bound", [(8, "vpu"), (512, "vpu")])
def test_roofline_seconds(dim, bound):
    peaks = {"vpu_f32_ops_per_s": {"value": 1e12},
             "hbm_bytes_per_s": {"value": 819e9}}
    levels = [("schwefel", dim, 100, 16384, 3)]
    t, b = roof.roofline_seconds(levels, peaks)
    ops = SCHWEFEL_STEP * 100 * 16384 * 3
    nbytes = 3 * (2 * 16384 * dim * 4 + 16384 * 4)
    assert b == bound
    assert t == pytest.approx(max(ops / 1e12, nbytes / 819e9))
