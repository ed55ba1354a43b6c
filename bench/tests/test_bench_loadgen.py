"""The traffic generator: deterministic per seed, the same work for every
seed, and a warm-up that covers every group shape the traffic can make."""
import collections
import itertools

import pytest

from bench import loadgen
from bench_cells import load

CELLS = ("paper-d512", "mix-poisson")


def _shape(req):
    return (req.objective, req.dim, req.n_chains, req.T0, req.T_min,
            req.rho, req.N)


def _window(cell, seed, seconds=10.0):
    gen = loadgen.Generator(cell.config, cell.traffic, seed)
    if cell.traffic["loop"] == "open":
        return list(itertools.takewhile(
            lambda it: it[0] < seconds, gen.open_schedule(seconds)))
    n = 2 * len(gen.kinds)            # whole cycles of the closed stream
    return [(0.0, r) for r in itertools.islice(gen.closed_stream(), n)]


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_requests(name):
    cell = load(name)
    a, b = _window(cell, 2**31 + 7), _window(cell, 2**31 + 7)
    assert [(t, r) for t, r in a] == [(t, r) for t, r in b]


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_the_same_work(name):
    cell = load(name)
    a, b = _window(cell, 3), _window(cell, 2**33 + 5)
    assert collections.Counter(_shape(r) for _, r in a) == \
        collections.Counter(_shape(r) for _, r in b)
    assert [r.seed for _, r in a] != [r.seed for _, r in b]
    assert all(0 <= r.seed < 2**32 for _, r in a + b)


def test_open_loop_offers_rate_times_window():
    cell = load("mix-poisson")
    due = [t for t, _ in _window(cell, 5, seconds=20.0)]
    assert len(due) == round(cell.traffic["rate"] * 20.0)
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20.0


def test_warm_up_covers_the_group_shapes():
    paper = load("paper-d512")
    warm = loadgen.warmup_requests(paper.config, paper.traffic["clients"])
    assert [(s, r.dim, r.N, r.n_levels) for s, r in warm] == [
        (64, 512, 100, 1)]
    mix = load("mix-poisson")
    warm = loadgen.warmup_requests(mix.config)
    shapes = {(r.dim, r.N) for _, r in warm}
    assert shapes == {(d, s["N"]) for (_, d) in mix.config["problems"]
                      for s in mix.config["schedules"]}
    assert sorted({s for s, _ in warm}) == [1, 2, 4, 8, 16, 32, 64]
    assert len(warm) == len(shapes) * 7
