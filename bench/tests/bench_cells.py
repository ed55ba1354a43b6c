"""Cells for the tests: those ``BENCHMARK.json`` lists, and the ones whose
files are under ``bench/`` but whose entry waits for a measurement on the
chip (``mix-poisson``: its offered rate is 0.8 of a knee not yet swept)."""
from bench import spec

#: Cells with files but no ``BENCHMARK.json`` entry yet.
PENDING = {
    "mix-poisson": {"config": "suite-mix-1chip", "traffic": "poisson",
                    "chips": 1},
}


def load(name: str) -> spec.Cell:
    if name not in PENDING:
        return spec.load_cell(name)
    w, here = PENDING[name], spec.BENCH_DIR
    return spec.Cell(
        name=name, chips=w["chips"],
        config=spec.load_json(here / "configs" / f"{w['config']}.json"),
        traffic=spec.load_json(here / "traffic" / f"{w['traffic']}.json"),
        checks=spec.load_json(here / "checks" / f"{name}.json"),
        end_to_end=[], per_layer=[])
