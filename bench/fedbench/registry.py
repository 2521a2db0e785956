"""Finds what a cell is made of by the names in BENCHMARK.json:

  bench/configs/<config>.json     the configuration as it is run
  bench/configs/<config>.py       its plain reference model
  bench/generators/<name>.py      the data generator the config names
  bench/traffic/<traffic>.json    the traffic mix
  bench/limits/<workload>.json    the limits of the correctness check
  bench/metrics/<metric>.py       one per-layer metric: its reader, and
                                  the spans it installs

A later cell or metric is added by adding such files and entries; no
file here needs an edit."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, workload: str, root: Path = ROOT):
        self.bm = _json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bm["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bm["configs"]}
        self.config = _json(root / configs[self.entry["config"]]["file"])
        self.reference = load_module(
            BENCH / self.config["reference"],
            f"bench_ref_{self.config['name']}")
        self.generator = load_module(
            BENCH / "generators" / f"{self.config['data']['generator']}.py",
            f"bench_gen_{self.config['data']['generator']}")
        self.traffic = _json(BENCH / "traffic" /
                             f"{self.entry['traffic']}.json")
        self.limits = _json(BENCH / "limits" / f"{workload}.json")
        self.per_layer = [m for m in self.bm["per_layer"]
                          if workload in m.get("workloads", [workload])]

    def metric_module(self, name: str):
        return load_module(BENCH / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")
