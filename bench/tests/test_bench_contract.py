"""BENCHMARK.json keeps the shape the benchmark's readers rely on: names,
units and one-line texts within their limits, every metric with a reader
file, every cell with its files, and every cell reporting set-up, another
end-to-end metric and a per-layer one."""
import json
import re

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and \
        1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_texts():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert _one_line(c["why"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert _one_line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_enough():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:       # each moves a metric the cell reports
            assert m["moves"] in e2e
        for m in cell.end_to_end + cell.per_layer:
            assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_config_files_state_their_cut():
    for c in BENCH["configs"]:
        conf = spec.load_json(spec.ROOT / c["file"])
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert conf["precision"] == "float32"
