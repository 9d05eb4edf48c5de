"""Finds a cell's files by the names in `BENCHMARK.json`.

- a cell `<name>` of `workloads`  -> `workloads/<name>.json` (its traffic mix)
- its `config`                    -> the `file` its entry of `configs` names
- a per-layer metric `<name>`     -> `metrics/<name>.py` (its reader)
- the workload file's `kind`      -> `drivers/<kind>.py`

Nothing here knows a cell, a configuration or a metric by name: a later
PR adds files and entries and edits nothing. A key a file may not hold is
refused, so a typo cannot pass for a setting.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent.parent  # benchmarks/
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# Keys every workload file may hold; a driver adds its own (`WORKLOAD_KEYS`).
WORKLOAD_COMMON = {"kind", "config", "chips", "why"}
# Keys every configuration file may hold besides its family's published
# ones (`CONFIG_KEYS` of the driver).
CONFIG_COMMON = {
    "source", "family", "reduced", "assumed", "deployment", "precision",
}


class BenchmarkFileError(ValueError):
    pass


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchmarkFileError(f"no such file: {path}")
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise BenchmarkFileError(f"{path}: not a JSON object")
    return data


def load_benchmark(root: pathlib.Path | None = None) -> dict:
    root = pathlib.Path(root) if root else HERE.parent
    return _read_json(root / "BENCHMARK.json")


def _check_keys(what: str, data: dict, allowed: set[str], required: set[str]):
    unknown = sorted(set(data) - allowed)
    if unknown:
        raise BenchmarkFileError(
            f"{what}: unknown key(s) {unknown}; allowed: {sorted(allowed)}"
        )
    missing = sorted(required - set(data))
    if missing:
        raise BenchmarkFileError(f"{what}: missing key(s) {missing}")


def load_driver(kind: str):
    if not NAME.match(kind):
        raise BenchmarkFileError(f"bad driver kind {kind!r}")
    return _load_module(HERE / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def _load_module(path: pathlib.Path, modname: str):
    if not path.is_file():
        raise BenchmarkFileError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        re.sub(r"[^A-Za-z0-9_]", "_", modname), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_metric(name: str):
    """The reader of one per-layer metric: a module with `read(trace,
    spans, cell)`, and `LAYER`, `UNIT`, `MOVES`, `SOURCE` for the reader."""
    if not NAME.match(name):
        raise BenchmarkFileError(f"bad metric name {name!r}")
    module = _load_module(HERE / "metrics" / f"{name}.py", f"bench_metric_{name}")
    for attr in ("read", "LAYER", "UNIT", "MOVES", "SOURCE"):
        if not hasattr(module, attr):
            raise BenchmarkFileError(f"metrics/{name}.py lacks `{attr}`")
    return module


def load_cell(
    name: str, benchmark: dict, base: pathlib.Path = HERE,
    root: pathlib.Path | None = None,
) -> dict:
    """Everything one run needs to know about the cell `name`:
    `{"name", "workload", "config", "driver", "end_to_end", "per_layer"}`.
    Data files are looked for under `base` (and the configuration's `file`
    under `root`); drivers and metric readers are always this directory's."""
    root = pathlib.Path(root) if root else base.parent
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise BenchmarkFileError(
            f"no cell {name!r} in BENCHMARK.json (has: {sorted(entries)})"
        )
    entry = entries[name]
    workload = _read_json(base / "workloads" / f"{name}.json")
    configs = {c["name"]: c for c in benchmark["configs"]}
    if entry["config"] not in configs:
        raise BenchmarkFileError(f"cell {name}: no config {entry['config']!r}")
    config = _read_json(root / configs[entry["config"]]["file"])
    for key in ("config", "chips"):
        if workload.get(key) != entry[key]:
            raise BenchmarkFileError(
                f"workloads/{name}.json says {key}={workload.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}"
            )
    driver = load_driver(workload.get("kind", ""))
    _check_keys(
        f"workloads/{name}.json", workload,
        WORKLOAD_COMMON | set(driver.WORKLOAD_KEYS),
        WORKLOAD_COMMON | set(driver.WORKLOAD_REQUIRED),
    )
    _check_keys(
        configs[entry["config"]]["file"], config,
        CONFIG_COMMON | set(driver.CONFIG_KEYS),
        {"source"} | set(driver.CONFIG_REQUIRED),
    )

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    end_to_end = [m for m in benchmark["end_to_end"] if reported(m)]
    names = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in benchmark["per_layer"]
        if reported(m) and m["moves"] in names
    ]
    return {
        "name": name, "chips": entry["chips"], "workload": workload,
        "config": config, "driver": driver, "end_to_end": end_to_end,
        "per_layer": per_layer,
    }
