"""Find a cell's pieces by name: configuration, traffic mix, driver, readers.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
sits in a file of its own (``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``). A mix names its driver
(``bench/drivers/<driver>.py``), a configuration its plain reference
(``bench/references/<reference>.py``), and each per-layer metric of
``BENCHMARK.json`` has a reader (``bench/metrics/<metric>.py``). Adding any
of them is adding a file and an entry, never editing one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> dict:
    """The ``workloads`` entry called ``name``."""
    for w in load_benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str) -> ModuleType:
    return _module(BENCH / "drivers" / f"{name}.py")


def reference(name: str) -> ModuleType:
    return _module(BENCH / "references" / f"{name}.py")


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py")


def metrics_of(cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in load_benchmark()[kind]
            if cell_name in m.get("workloads", [cell_name])]
