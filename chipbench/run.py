#!/usr/bin/env python3
"""chipbench: one cell of BENCHMARK.json, on the chip this machine holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

Starts the aggregator (``kepler_tpu.cmd.aggregator.main`` through
``launch.py``, ``tpu.platform: tpu``, ``fallbackEnabled: false``) and speaks
HTTP to it: seeded wire-v2 reports in, published windows out. Set-up is
start, compile, history fill and warm-up; then ``--seconds`` are measured;
then the child is stopped and what it published is compared with the plain
reference. The last stdout line is one JSON object: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
Without a TPU it exits non-zero and prints no result. This process never
touches JAX.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, records, spec, trace, work  # noqa: E402
from chipbench.child import (LAUNCHER, AggregatorChild,  # noqa: E402
                             BenchFailure, free_port)
from chipbench.drive import Drive, run_window  # noqa: E402
from chipbench.fleetgen import Fleet  # noqa: E402
from chipbench.reference import Reference  # noqa: E402
from chipbench.stats import window_latencies  # noqa: E402


def aggregator_config(cell: spec.Cell, params_path: str,
                      platform: str) -> dict:
    """The YAML the aggregator starts with: shipped defaults, the
    configuration's own settings, the traffic's interval."""
    cfg = cell.config
    out: dict = {
        "log": {"level": "info"},
        "tpu": {"platform": platform},
        "aggregator": {
            "listenAddress": f"127.0.0.1:{free_port()}",
            "interval": float(cell.traffic["interval_s"]),
            "model": cfg["estimator"], "paramsPath": params_path,
            "historyWindow": int(cfg["history_window"]),
            "fallbackEnabled": False,
        },
    }
    for section, values in cfg.get("aggregator_config", {}).items():
        out.setdefault(section, {}).update(values)
    return out


def child_env(cell: spec.Cell, env: dict | None) -> dict:
    """The aggregator's environment: ``env`` (the tests') or this
    process's, with the configuration's ``runtime_env`` over it: settings
    of the runtime under the program, as a deployment gives them."""
    out = dict(os.environ if env is None else env)
    out.update((key, str(value)) for key, value
               in cell.config.get("runtime_env", {}).items())
    return out


class Run:
    """One run's observations, as the metric readers see them."""

    def __init__(self, cell: spec.Cell, drive: Drive, launch: dict) -> None:
        self.cell, self.drive, self.launch = cell, drive, launch
        d = drive
        self.windows_in = [w for w in d.windows
                           if d.t_open <= w.seen <= d.t_close]
        self.latencies_ms, self.attempted_windows, self.failed_windows = \
            window_latencies(d.windows, d.t_open, d.t_close,
                             d.published_close - d.published_open,
                             d.count_from, d.count_to)
        self.work = work.of_config(cell.config, d.fleet.model_pods)
        self.program = cell.estimator().PROGRAM  # the window's, by its name
        kind = launch.get("device_kind", "")
        try:
            self.peak = work.peaks(kind)
        except KeyError:
            self.peak = None  # CPU rehearsal: no device metric is printed
        self.planes = launch.get("planes") or []
        self.busy_s = self.window_s = self.program_ms = None
        marks = launch.get("marks", {})
        if self.planes and "start" in marks and "stop_asked" in marks:
            self.window_s = marks["stop_asked"] - marks["start"]
            busy = trace.busy_seconds(self.planes)
            self.busy_s = busy if busy > 0 else None
            self.program_ms = trace.program_ms(self.planes)

    def metrics(self, group: str) -> dict:
        out = {}
        for m in self.cell.metrics(group):
            read, args = self.cell.reader(m["name"])
            value = read(self, **args)
            # a reader that finds nothing to read returns nothing, and the
            # metric is left out of the line
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def breakdown(self) -> dict:
        """The device ops that took most time, and the longest idle gaps by
        what the aggregator's own clocks say the host was doing: the trace
        lies on the host's clock by its own start time, and without one no
        gap is named."""
        gaps = []
        zero_ns = self.launch.get("profile_start_time")
        for start, end in trace.idle_gaps(self.planes) if zero_ns else ():
            mid = (zero_ns + (start + end) / 2) / 1e9
            gaps.append([self._host_state(mid), (end - start) / 1e9])
        return {"device_ops": trace.top_ops(self.planes),
                "idle_gaps": gaps}

    def _host_state(self, t: float) -> str:
        for w in self.drive.windows:
            g = w.gauges
            t1 = w.stamp + g["last_assembly_ms"] / 1e3
            if w.stamp <= t < t1:
                return "assembly"
            if t1 <= t < t1 + g["last_dispatch_ms"] / 1e3:
                return "dispatch"
            pub = (g["last_wait_ms"] + g["last_fetch_ms"]
                   + g["last_scatter_ms"]) / 1e3
            if w.seen - pub <= t <= w.seen:
                return "publish"
        for rnd in self.drive.all_rounds:
            if rnd.batches and rnd.start <= t <= rnd.end:
                return "ingest"
        return "idle_tick"


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             root: str = ROOT, platform: str = "tpu",
             env: dict | None = None,
             launcher: str = LAUNCHER) -> tuple[int, dict | None]:
    """One run → (exit code, the result line's object or None).
    ``platform``, ``env`` and ``launcher`` are for the tests: a CPU child,
    a child whose timed path is broken on purpose."""
    cell = spec.load_cell(root, workload)
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    child = drive = None
    try:
        params = cell.estimator().make_params(seed, cell.config)
        params_path = os.path.join(workdir, "params.npz")
        np.savez(params_path, **params)
        # set-up starts where a deployment's does, with its checkpoint on
        # disk: the seeded stand-in is the harness's, its load the program's
        t_start = time.time()
        config = aggregator_config(cell, params_path, platform)
        child = AggregatorChild(config, workdir, traced,
                                child_env(cell, env), launcher)
        fleet = Fleet(cell.config, cell.traffic, seed)
        child.wait_ready(600.0)
        drive = run_window(child, fleet, cell.traffic,
                           int(cell.config["history_window"]), seconds,
                           traced, t_start)
        rc = child.stop()
        log = child.log_text()
        launch = child.launch_report()
        for needle in ("Traceback", "fleet aggregation failed"):
            if needle in log:
                line = next(x for x in log.splitlines() if needle in x)
                raise BenchFailure(f"aggregator log has {needle!r}: "
                                   f"{line[:200]}")
        if rc != 0 or "Graceful shutdown completed" not in log:
            raise BenchFailure(f"the aggregator did not shut down cleanly "
                               f"(exit {rc}): {child.log_tail()}")
        if launch.get("platform") != platform:
            raise BenchFailure(f"the aggregator ran on "
                               f"{launch.get('platform')!r}, not {platform!r}")
        if launch.get("count", 0) < int(cell.workload["chips"]):
            raise BenchFailure(f"{launch.get('count')} device(s), the cell "
                               f"asks for {cell.workload['chips']}")
    except BenchFailure as err:
        phase = err.phase or ("ready" if drive is None else "close")
        print(f"chipbench: FAIL: in {phase}: {err}", file=sys.stderr)
        return 1, None
    finally:
        if child is not None:
            child.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    # the program's state is freed and its peak read: now the reference
    run = Run(cell, drive, launch)
    t_ref = time.time()
    ref = Reference(fleet, params, int(cell.config["history_window"]))
    errors = check.compare(drive, ref, launch)
    correct, compared = check.verdict(errors, cell.config["limits"])
    ref_s = time.time() - t_ref

    group = "per_layer" if traced else "end_to_end"
    device = {"platform": launch["platform"], "kind": launch["device_kind"],
              "count": launch["count"], **launch["memory"]}
    result: dict = {
        "correct": bool(correct),
        "attempted": (run.attempted_windows
                      if cell.traffic["loop"] == "open"
                      else len(drive.rounds)),
        "failed": (run.failed_windows if cell.traffic["loop"] == "open"
                   else errors.numbers["rounds_uncovered"]),
        "metrics": run.metrics(group),
        "device": device,
    }
    if traced:
        device["busy_s"] = run.busy_s
        device["window_s"] = run.window_s
        result["breakdown"] = run.breakdown()
    result["notes"] = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "rounds": len(drive.rounds), "windows": len(run.windows_in),
        "reference_s": ref_s, "setup_parts": drive.setup_parts,
        "reports_throttled": sum(r.throttled for r in drive.all_rounds),
        "throttle_wait_s": sum(r.throttle_wait_s for r in drive.all_rounds),
        **errors.counts}
    if cell.traffic["loop"] == "open":
        # every window's own latency, in the windows' order: the next
        # question about the tail costs no chip time
        result["notes"]["latencies_ms"] = [
            None if math.isinf(x) else x for x in run.latencies_ms]
    if traced:
        # how many records the trace's zero was held against, and how many
        # contradicted it (``records.align``); null where there is no zero
        result["notes"]["trace_zero"] = records.align(
            drive.debug.get("last"), run.planes, launch, run.program)
    result["compared"] = compared
    for name, row in compared.items():
        print(f"chipbench: {name} = {row['value']:.6g} (limit "
              f"{row['limit']:.6g})", file=sys.stderr)
    print(f"chipbench: correct = {correct}", file=sys.stderr)
    return 0, result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        rc, result = run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    except spec.SpecError as err:
        print(f"chipbench: FAIL: {err}", file=sys.stderr)
        return 2
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
