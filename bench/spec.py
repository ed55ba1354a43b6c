"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout root
names the cell's configuration, traffic and metrics; each lives in a file
of its own under ``bench/``:

* ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives),
* ``traffic/<traffic>.json``,
* ``checks/<workload>.json``: the numbers that decide ``correct`` and
  their limits,
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``,
* ``reference/<family>.py``: the plain reference of a problem family.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: List[dict]      # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with all its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        checks=load_json(here / "checks" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_module(path: Path):
    """Import a Python file by path (metric and reference names may hold
    dots, which a plain import cannot)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def reference(family: str, root: Path = ROOT):
    """The plain reference module of a problem family."""
    return load_module(root / "bench" / "reference" / f"{family}.py")


def peaks(kind: str, root: Path = ROOT) -> Dict[str, dict]:
    """The peaks of a device kind; a kind not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[kind]
