"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Every piece lives in a file of its own under ``bench/``:

- configuration: the ``file`` of its ``configs`` entry (published keys
  under ``config``, the engine's under ``model_config``, and the name of
  its plain reference in ``bench/references/<reference>.py``);
- traffic mix: ``bench/traffic/<traffic>.json``, whose ``driver`` names
  ``bench/drivers/<driver>.py``;
- metric: ``bench/metrics/<metric name>.py`` with ``read(run)``; for a
  name such as ``idle_share.chat``, a quantity split by the cells that
  report it, ``bench/metrics/idle_share.py`` serves every split that has
  no file of its own.

So a later cell, mix, driver or metric is new files plus new entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = "bench"


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: Dict            # the configuration file's contents
    traffic: Dict           # the traffic file's contents
    end_to_end: List[Dict]  # BENCHMARK.json metric entries of this cell
    per_layer: List[Dict]

    @property
    def bench(self) -> Path:
        return self.root / BENCH_DIR

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.bench / kind / f"{name}.py")

    def driver(self) -> ModuleType:
        return self.module("drivers", self.traffic["driver"])

    def reference(self) -> ModuleType:
        return self.module("references", self.config["reference"])

    def metric(self, name: str) -> ModuleType:
        if (self.bench / "metrics" / f"{name}.py").is_file():
            return self.module("metrics", name)
        return self.module("metrics", name.split(".")[0])


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(root=root, name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])
