"""Names resolve to files; a file with a key it may not hold is refused;
`BENCHMARK.json` keeps to the characters and keys of the contract."""

import json
import pathlib
import re
import shutil

import pytest

from benchmarks.lib import loader

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def benchmark():
    return loader.load_benchmark(ROOT)


def test_every_cell_resolves_to_its_files(benchmark):
    for entry in benchmark["workloads"]:
        cell = loader.load_cell(entry["name"], benchmark)
        assert cell["workload"]["config"] == entry["config"]
        assert cell["driver"].__name__.endswith(cell["workload"]["kind"])
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"], "a cell reports at least one per-layer metric"
        for metric in cell["per_layer"]:
            reader = loader.load_metric(metric["name"])
            assert reader.LAYER == metric["layer"]
            assert reader.UNIT == metric["unit"]
            assert reader.MOVES == metric["moves"]
            assert reader.SOURCE == metric["source"]


def test_the_tiny_cell_resolves_from_its_own_directory():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    cell = loader.load_cell("tiny-lm.train", bench, base=DATA, root=DATA)
    assert cell["config"]["hidden_size"] == 64
    assert [m["name"] for m in cell["per_layer"]] == ["input_wait_ms.train"]


@pytest.mark.parametrize("where", ["workloads/tiny-lm.train.json",
                                   "configs/tiny-lm.json"])
def test_an_unknown_key_is_refused(tmp_path, where):
    shutil.copytree(DATA, tmp_path / "data")
    path = tmp_path / "data" / where
    data = json.loads(path.read_text())
    data["bacth"] = 4
    path.write_text(json.dumps(data))
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    with pytest.raises(loader.BenchmarkFileError, match="unknown key.*bacth"):
        loader.load_cell(
            "tiny-lm.train", bench, base=tmp_path / "data", root=tmp_path / "data"
        )


def test_unknown_names_are_refused(benchmark):
    with pytest.raises(loader.BenchmarkFileError, match="no cell"):
        loader.load_cell("no-such.cell", benchmark)
    with pytest.raises(loader.BenchmarkFileError, match="no such file"):
        loader.load_metric("no_such_metric")
    with pytest.raises(loader.BenchmarkFileError, match="bad metric name"):
        loader.load_metric("../run")


def test_benchmark_json_keeps_to_the_contract(benchmark):
    assert set(benchmark) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert benchmark["paths"] == ["benchmarks"]
    assert 1 <= benchmark["run_seconds"] <= 51
    cells = {w["name"] for w in benchmark["workloads"]}
    configs = {c["name"] for c in benchmark["configs"]}
    names = (
        [c["name"] for c in benchmark["configs"]]
        + [w["name"] for w in benchmark["workloads"]]
        + [w["traffic"] for w in benchmark["workloads"]]
        + [m["name"] for m in benchmark["end_to_end"] + benchmark["per_layer"]]
        + [k for c in benchmark["configs"] for k in c["reduced"]]
    )
    assert all(NAME.match(n) for n in names), names
    assert len(cells) == len(benchmark["workloads"])
    assert {w["config"] for w in benchmark["workloads"]} == configs
    end = {m["name"]: m for m in benchmark["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    for m in benchmark["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in benchmark["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in end and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in benchmark["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and (ROOT / c["file"]).is_file()
        published = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(published["reduced"])
    for w in benchmark["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in benchmark["workloads"])
    assert four <= max(1, len(cells) // 4)
    assert len(json.dumps(benchmark)) < 64 * 1024


def test_files_under_paths_are_named_from_a_names_characters():
    bad = [
        str(p) for p in (ROOT / "benchmarks").rglob("*")
        if "__pycache__" not in p.parts
        and not re.match(r"^[A-Za-z0-9_.\-]+$", p.name)
    ]
    assert not bad
