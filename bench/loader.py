"""Finds the benchmark's parts by name.

Each part lives in a file of its own, so a later change adds a cell, a
traffic mix, a generator, a metric or a reference by adding files:

    BENCHMARK.json                      cells, metrics, bounds (repo root)
    bench/configs/<config>.json         a configuration (``file`` in it)
    bench/traffic/<traffic>.json        a traffic mix's parameters
    bench/traffic/families/<family>.py  a traffic family's generator
    bench/traffic/arrivals.py           the arrival schedules
    bench/metrics/<metric>.py           a per-layer metric's reader
    bench/reference/<name>.py           a plain reference
    bench/adapters/<family>.py          the program's config for a model family
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

_MODULES: dict = {}


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def module(path: Path):
    """Import one file of the benchmark as a module (once per process)."""
    path = Path(path).resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"benchmark part not found: {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def cell(spec: dict, name: str, root: Path = ROOT) -> tuple:
    """The cell ``name`` with its configuration and traffic mix."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(Path(root) / configs[w["config"]]["file"])
    traffic = load_json(Path(root) / "bench" / "traffic"
                        / f"{w['traffic']}.json")
    return w, config, traffic


def family(name: str):
    return module(BENCH / "traffic" / "families" / f"{name}.py")


def arrivals():
    return module(BENCH / "traffic" / "arrivals.py")


def metric(name: str):
    return module(BENCH / "metrics" / f"{name}.py")


def reference(name: str):
    return module(BENCH / "reference" / f"{name}.py")


def adapter(family: str):
    return module(BENCH / "adapters" / f"{family}.py")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
