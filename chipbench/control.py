#!/usr/bin/env python3
"""The control of ``correct``: the plain reference, computed in the
precision below the one the configuration states, put in the PROGRAM's
place — and the comparison has to call it not correct.

    python3 chipbench/control.py --workload <cell> --seed <n> [<n> ...]

Which precision that is, the cell's estimator states (``CONTROL`` of
``estimators/<name>.py``: where the configurations state bf16 matmul
operands, fp8 e4m3); the ratio path's float32 product is computed with
bf16 operands. What the lowered reference gives is laid out as the
aggregator would have published it, at the cell's own size — a few sampled
windows and the whole fleet's final window — and goes through the same
``check.compare`` and ``check.verdict`` as a run's publications do, against
the float32 reference and the configuration's limits. No aggregator runs
and no chip is needed; the numbers are the upper readings of ``PERF.md``
section 2. Exit 0 where every seed came out not correct.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, spec  # noqa: E402
from chipbench.drive import Drive, Round, Window, sample_nodes  # noqa: E402
from chipbench.fleetgen import BATCH, Fleet  # noqa: E402
from chipbench.precision import QUANTIZERS  # noqa: E402
from chipbench.reference import Reference  # noqa: E402


def published(ref: Reference, nodes: list[int], r: int,
              stamp: float) -> dict:
    """What ``ref`` gives for ``nodes`` after round ``r``, as the
    ``nodes`` object of ``GET /v1/results``."""
    fleet = ref.fleet
    idx = np.asarray(nodes, np.intp)
    gen = ref.state(r)[0].gen
    is_model = fleet.mode[idx] == 1
    pods = np.zeros((len(idx), fleet.w, len(fleet.zones)))
    node = np.zeros((len(idx), len(fleet.zones)))
    if is_model.any():
        watts = ref.model_nodes(idx[is_model], r)
        pods[is_model] = watts
        node[is_model] = watts.sum(axis=1, dtype=np.float32)
    if (~is_model).any():
        pods[~is_model], node[~is_model] = ref.ratio_nodes(idx[~is_model], r)
    out = {}
    for k, i in enumerate(nodes):
        out[fleet.names[i]] = {
            "timestamp": stamp, "zones": list(fleet.zones),
            "mode": int(fleet.mode[i]),
            "node_power_uw": (node[k] * 1e6).tolist(),
            "workloads": [{"id": wid, "power_uw": (pods[k, j] * 1e6).tolist()}
                          for j, wid in enumerate(fleet.ids(i, gen[i]))]}
    return out


def stand_in(cell: spec.Cell, fleet: Fleet, low: Reference,
             windows: int = 3) -> Drive:
    """What ``low`` gives, laid out as a run's observations of the
    aggregator: ``windows`` sampled windows and the whole fleet's final
    one, after the history fill and the warm-up, at the cell's own size."""
    t = int(cell.config["history_window"])
    n_batches = -(-fleet.n // BATCH)
    last = t + int(cell.traffic.get("warmup_rounds", 3)) + windows - 1
    drive = Drive(fleet=fleet, seconds=float(windows))
    sample = sample_nodes(fleet)
    for r in range(last + 1):
        # a round a second: its POSTs in the first tenth, its window's
        # assembly in the middle, so no answer's round is in doubt
        rnd = Round(r=r, due=float(r), batches=[
            (None, float(r), r + 0.1)] * n_batches)
        drive.all_rounds.append(rnd)
        if r > last - windows:
            drive.rounds.append(rnd)
            answers = published(low, sample, r, r + 0.5)
            drive.windows.append(Window(
                stamp=r + 0.5, seen=r + 0.9,
                gauges={"last_assembly_ms": 200.0},
                answers={i: answers[fleet.names[i]] for i in sample}))
    drive.t_open, drive.t_close = last - windows + 1.0, last + 1.0
    drive.final = {"nodes": published(low, list(range(fleet.n)), last,
                                      last + 0.5)}
    drive.final_round = last
    drive.debug = {"first": {"rung": 0}, "last": {"rung": 0}}
    return drive


def control_run(cell: spec.Cell, seed: int, quantize: str | None = None,
                windows: int = 3) -> tuple[bool, dict]:
    """→ (correct, {name: {"value", "limit"}}) of the reference at
    ``quantize`` (the estimator's ``CONTROL`` unless another is named) in
    the program's place, at the cell's own size."""
    estimator = cell.estimator()
    quantize = quantize or estimator.CONTROL
    t = int(cell.config["history_window"])
    fleet = Fleet(cell.config, cell.traffic, seed)
    params = estimator.make_params(seed, cell.config)
    drive = stand_in(cell, fleet, Reference(fleet, params, t, quantize),
                     windows)
    errors = check.compare(drive, Reference(fleet, params, t), {})
    return check.verdict(errors, cell.config["limits"])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--quantize", default=None,
                    choices=sorted(q for q in QUANTIZERS if q),
                    help="the estimator's CONTROL unless given")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    quantize = args.quantize or cell.estimator().CONTROL
    passed = 0
    for seed in args.seed:
        correct, compared = control_run(cell, seed, quantize)
        passed += bool(correct)
        row = " ".join(f"{k}={v['value']:.6g}/{v['limit']:.6g}"
                       for k, v in compared.items() if v["limit"] > 0)
        print(f"CONTROL {args.workload} seed {seed} {quantize}: "
              f"correct = {correct} {row}", flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
