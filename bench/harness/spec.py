"""Find the benchmark's pieces by name, from files alone.

``BENCHMARK.json`` at the checkout root names cells, configurations and
metrics; each is a file of its own under ``bench/``:

  bench/cells/<cell>.json        configuration, driver, traffic, chips, why
  bench/configs/<config>.json    sizes as run, source, reduced, assumed
  bench/drivers/<driver>.py      ``run(ctx) -> record``
  bench/metrics/<metric>.py      UNIT, BETTER, MOVES, ``read(record, trace)``
  bench/counts/<config>.py       operation and byte counts of the config

A later change adds a cell, configuration or metric by adding a file and
an entry; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (names may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod_name = "bench_dyn_" + "".join(
        c if c.isalnum() else "_" for c in (name or path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark as the files under ``bench_dir`` describe it."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.dir = bench_dir or os.path.join(root, "bench")
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    # -- lookups by name ---------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        entry = next((w for w in self.spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{[w['name'] for w in self.spec['workloads']]}")
        cell = load_json(os.path.join(self.dir, "cells", f"{name}.json"))
        if cell["config"] != entry["config"]:
            raise ValueError(f"cell {name}: file names config "
                             f"{cell['config']!r}, BENCHMARK.json "
                             f"{entry['config']!r}")
        return {**cell, "name": name, "chips": entry["chips"]}

    def config(self, name: str) -> Dict[str, Any]:
        entry = next((c for c in self.spec["configs"] if c["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no config {name!r} in BENCHMARK.json")
        return {**load_json(os.path.join(self.root, entry["file"])),
                "name": name}

    def driver(self, name: str) -> ModuleType:
        return load_module(os.path.join(self.dir, "drivers", f"{name}.py"),
                           f"driver_{name}")

    def counts(self, config: str) -> ModuleType:
        return load_module(os.path.join(self.dir, "counts", f"{config}.py"),
                           f"counts_{config}")

    def metric(self, name: str) -> ModuleType:
        return load_module(os.path.join(self.dir, "metrics", f"{name}.py"),
                           f"metric_{name}")

    # -- which metrics a cell reports --------------------------------------
    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell])
                and m["moves"] in reported]
