"""A cell's parts are found by name: a configuration, traffic mix, check
or metric dropped into a copy of the benchmark is used with no edit of
any file already there; and the run refuses to start without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT


@pytest.fixture
def copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    return tmp_path


def _add_cell(root, traffic, metric):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/suite-mix-1chip.json").read_text())
    conf["problems"] = [["salomon", 8]]
    (root / "bench/configs/salomon-d8.json").write_text(json.dumps(conf))
    (root / f"bench/traffic/{traffic}.json").write_text(json.dumps(
        {"loop": "open", "rate": 3.0, "at_close": "drain"}))
    (root / "bench/checks/salomon-burst.json").write_text(
        (root / "bench/checks/mix-poisson.json").read_text())
    # latency_p50_s has its reader under bench/metrics already
    (root / f"bench/metrics/{metric}.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "salomon-d8", "source": "x",
                             "file": "bench/configs/salomon-d8.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "salomon-burst", "config": "salomon-d8",
                               "traffic": traffic, "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": metric, "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "host tick",
                               "moves": "latency_p50_s",
                               "workloads": ["salomon-burst"]})
    bench["end_to_end"].append({"name": "latency_p50_s", "unit": "s",
                                "better": "lower", "bound": 0.15,
                                "source": "host_clock",
                                "workloads": ["salomon-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_found_by_name(copy):
    _add_cell(copy, "burst-3", "wait_ms.salomon")
    cell = spec.load_cell("salomon-burst", root=copy)
    assert cell.config["problems"] == [["salomon", 8]]
    assert cell.traffic["rate"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["wait_ms.salomon"]
    assert {m["name"] for m in cell.end_to_end} == {"latency_p50_s",
                                                     "setup_s"}
    assert spec.metric_reader("wait_ms.salomon", root=copy)(None) == 42.0
    assert spec.reference("continuous", root=copy).BOX["salomon"] == (
        -100.0, 100.0)


def test_every_cell_resolves():
    bench = spec.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        assert cell.checks["limits"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"]["value"] == 819e9


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-d512",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_on_cpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_run_exits_nonzero_without_the_program(copy):
    proc = _run(copy, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
