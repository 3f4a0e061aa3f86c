"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here, and nothing else in ``chipbench/``, knows a configuration, a
traffic mix, a metric or an estimator by name: a later PR adds a cell by
adding an entry to ``BENCHMARK.json`` and files beside the ones that are
there, and edits none of them. The four kinds of file a cell may bring:

- ``configs[].file``: the configuration as it is run. Its ``estimator``
  names the fourth kind; its optional ``check`` says how much of a run is
  compared (``sampled_nodes``, default 6: nodes whose answers are kept from
  every window; ``final_model_nodes``, default ``"all"``: a number = that
  many model nodes of the final window, drawn from the seed, go through
  the reference — every node must still be there and well-formed, every
  ratio node is still compared);
- ``chipbench/traffic/<traffic>.json``: the mix's parameters, all of them
  (a mix at another interval is another file);
- ``chipbench/metrics/<metric>.json``: ``{"reader": <module under
  chipbench/readers>, "args": {...}}`` for every metric of either list. A
  roofline of a new estimator is a new metric file over the readers that
  are there (``roofline``, ``roofline_chips``, ``step_mfu``...): they read
  ``run.work``, which is the estimator's own count;
- ``chipbench/estimators/<estimator>.py``: what the yardstick knows of one
  estimator (``ESTIMATOR_NAMES``). It states, and has to obey:

  - ``make_params(seed, config)`` → the flat ``.npz`` arrays the aggregator
    loads for that ``model``, from the seed alone;
  - ``watts(params, hist [B,T,F], t_valid [B,T], config, quantize=None)`` →
    float32 ``[B,Z]``: the plain reference of the trunk. It imports nothing
    of ``kepler_tpu`` and takes nothing the program has made. Where it
    computes is its own business (NumPy on the host, or float32
    ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` on the
    chip: the child is dead and the chip free when the reference starts),
    as is ``block_rows(config)``, the rows one call of it may be given;
  - ``work(config, model_pods)`` → (FLOPs, bytes) of one window, of the
    ALGORITHM and not of a program: no padding, no discarded rows, nothing
    a program recomputes;
  - ``PROGRAM``: the prefix of the window's program's name in the trace's
    ``XLA Modules`` line;
  - ``CONTROL``: a name of ``precision.QUANTIZERS``, the precision BELOW
    the one the configuration states, which ``watts`` takes as ``quantize``
    when ``control.py`` puts it in the program's place;
  - ``WIDTHS``: {key: value} that every configuration of the estimator
    holds, as published (``tests/chipbench`` holds each file to them);
  - ``small(config)`` → the configuration at a size a CPU test holds: the
    same estimator and code, cut in widths and depth as far as a test
    needs, with ``limits`` of its own, each set between a sound reading and
    the control's, both written down. The tier-1 tests draw parameters and
    run the reference only on it, never at published widths.

A configuration brings one more file, its tier-1 goldens:
``tests/chipbench/goldens/<config>.json`` (``params_sha256`` by seed, of
``small(config)``; ``work`` by model pods, of the configuration itself).
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass


ESTIMATOR_NAMES = ("make_params", "watts", "block_rows", "work", "PROGRAM",
                   "CONTROL", "WIDTHS", "small")


class SpecError(Exception):
    pass


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as err:
        raise SpecError(f"{path}: {err}") from err


def estimator_of(config: dict):
    """The module that states ``config``'s estimator. One that is not
    there, or states less than ``ESTIMATOR_NAMES``, is an error that names
    the file, never a guess: a configuration that brings an estimator
    brings its reference and its count."""
    name = config.get("estimator")
    path = f"chipbench/estimators/{name}.py"
    module = f"chipbench.estimators.{name}"
    try:
        found = importlib.import_module(module)
    except ModuleNotFoundError as err:
        if err.name != module:
            raise
        raise SpecError(
            f"the configuration {config.get('name')!r} names the estimator "
            f"{name!r}, and there is no {path}") from err
    missing = [n for n in ESTIMATOR_NAMES if not hasattr(found, n)]
    if missing:
        raise SpecError(f"{path} does not state {', '.join(missing)}")
    return found


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

    def estimator(self):
        """The module of the configuration's estimator."""
        return estimator_of(self.config)


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
    estimator_of(config)  # an estimator nobody has stated: now
    return Cell(root, bench, cell, config, traffic)
