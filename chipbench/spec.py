"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a configuration, a traffic mix or a metric by name: a
later PR adds a cell by adding an entry to ``BENCHMARK.json`` and data
files beside the ones that are there, and edits none of them.

- ``configs[].file``: the configuration as it is run;
- ``chipbench/traffic/<traffic>.json``: the mix's parameters, all of them
  (a mix at another interval is another file);
- ``chipbench/metrics/<metric>.json``: ``{"reader": <module under
  chipbench/readers>, "args": {...}}`` for every metric of either list.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass


class SpecError(Exception):
    pass


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as err:
        raise SpecError(f"{path}: {err}") from err


@dataclass
class Cell:
    root: str
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    def metrics(self, group: str) -> list[dict]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        name = self.workload["name"]
        return [m for m in self.bench[group]
                if "workloads" not in m or name in m["workloads"]]

    def reader(self, metric: str):
        """→ (read function, args) of one metric, from its file."""
        spec = _load(os.path.join(self.root, "chipbench", "metrics",
                                  f"{metric}.json"))
        module = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        return module.read, spec.get("args", {})


def load_cell(root: str, workload: str) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(it has {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load(os.path.join(root, "chipbench", "traffic",
                                 f"{cell['traffic']}.json"))
    return Cell(root, bench, cell, config, traffic)
