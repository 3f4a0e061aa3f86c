"""Closed-loop validation against REAL host counters.

Every other accuracy artifact is synthetic-vs-synthetic; this harness runs
the real meter + informer stack for N windows and asserts the TPU
attribution agrees with an INDEPENDENT float64 host computation to within
the 0.5% north-star budget (reference credibility anchor:
``internal/device/rapl_sysfs_power_meter.go:76-231`` reads live sysfs).

Modes (auto-selected, strongest available first):
  live    — real RAPL sysfs zones + real /proc. Only on bare-metal hosts
            exposing /sys/class/powercap (the hardware-CI configuration).
  proc    — real /proc dynamics + the fake meter's synthetic-but-wrapping
            counters. Containers (like the bench host) have no powercap;
            the informer leg and the whole attribution loop still verify
            against live process churn. Labelled meter="fake".
  replay  — a checked-in capture (benchmarks/artifacts/host_capture.json)
            replayed through replay meter/reader doubles: deterministic
            regression coverage of the closed loop with no host deps.

The f64 reference shares NO code with the device path: it recomputes the
active/idle split and per-workload shares from each window's raw inputs
(zone deltas, usage ratio, cpu deltas) with numpy float64, the same
re-derivation as ``benchmarks.accuracy.reference_attribution_f64``.

CLI: ``python -m benchmarks.real_host [--windows N] [--interval S]
[--capture PATH] [--replay [PATH]]`` — prints one JSON line, exits
nonzero when validation ran and missed the budget.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

RAPL_SYSFS = "/sys/class/powercap"
TOL = 0.005  # the 0.5% budget
DEFAULT_CAPTURE = os.path.join(os.path.dirname(__file__), "artifacts",
                               "host_capture.json")


# -- replay doubles ---------------------------------------------------------


class ReplayZone:
    """EnergyZone replaying recorded counter values."""

    def __init__(self, name: str, readings: list[int], max_uj: int,
                 index: int = 0) -> None:
        from kepler_tpu.device.energy import Energy

        self._energy = Energy
        self._name = name
        self._readings = list(readings)
        self._i = 0
        self._max = max_uj
        self._index = index

    def name(self) -> str:
        return self._name

    def index(self) -> int:
        return self._index

    def path(self) -> str:
        return f"replay://{self._name}"

    def energy(self):
        v = self._readings[min(self._i, len(self._readings) - 1)]
        self._i += 1
        return self._energy(v)

    def max_energy(self):
        return self._energy(self._max)


class ReplayMeter:
    def __init__(self, zones: list[ReplayZone]) -> None:
        self._zones = zones

    def name(self) -> str:
        return "replay-meter"

    def zones(self):
        return self._zones

    def primary_energy_zone(self):
        return self._zones[0]


class ReplayProc:
    def __init__(self, pid: int, comm: str, cpu: float) -> None:
        self._pid, self._comm, self.cpu = pid, comm, cpu

    def pid(self):
        return self._pid

    def comm(self):
        return self._comm

    def executable(self):
        return f"/bin/{self._comm}"

    def cgroups(self):
        return ["0::/replay.scope"]

    def environ(self):
        return {}

    def cmdline(self):
        return [f"/bin/{self._comm}"]

    def cpu_time(self):
        return self.cpu


class ReplayReader:
    """ProcReader replaying recorded (pid → cpu_seconds) window samples."""

    def __init__(self, windows: list[dict], ratios: list[float]) -> None:
        self._windows = windows
        self._ratios = ratios
        self._i = 0

    def all_procs(self):
        w = self._windows[min(self._i, len(self._windows) - 1)]
        return [ReplayProc(int(pid), f"proc-{pid}", cpu)
                for pid, cpu in w.items()]

    def cpu_usage_ratio(self):
        r = self._ratios[min(self._i, len(self._ratios) - 1)]
        self._i += 1  # one refresh consumes one window
        return r


# -- the closed loop --------------------------------------------------------


def _f64_window(sample) -> dict:
    """Independent f64 recomputation of one window's attribution."""
    deltas = np.where(sample.zone_valid, sample.zone_deltas_uj, 0.0).astype(
        np.float64)
    ratio = float(np.clip(sample.usage_ratio, 0.0, 1.0))
    active = deltas * ratio
    dt = float(sample.dt_s)
    power = deltas / dt if dt > 0 else np.zeros_like(deltas)
    active_power = active / dt if dt > 0 else np.zeros_like(deltas)
    cpu = sample.batch.cpu_deltas.astype(np.float64)
    denom = float(sample.batch.node_cpu_delta)
    shares = cpu / denom if denom > 0 else np.zeros_like(cpu)
    return {
        "node_power_uw": power,
        "node_active_power_uw": active_power,
        "node_active_uj": active,
        "workload_power_uw": shares[:, None] * active_power[None, :],
        "ids": list(sample.batch.ids),
    }


def _max_rel_err(got: np.ndarray, want: np.ndarray, floor: float) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    sig = np.abs(want) > floor
    if not sig.any():
        return 0.0
    return float(np.max(np.abs(got[sig] - want[sig]) / np.abs(want[sig])))


def validate(meter, reader, windows: int, interval: float,
             mode: str) -> dict:
    """Run the real monitor for N windows; compare device attribution per
    window to the f64 recomputation. → result dict (the artifact row)."""
    from kepler_tpu.monitor.monitor import PowerMonitor
    from kepler_tpu.resource.informer import ResourceInformer

    if windows < 1:
        return {"mode": mode, "skipped": True, "ok": False,
                "reason": f"need >= 1 window, got {windows} (a capture "
                          "holds windows+1 samples)"}
    informer = ResourceInformer(reader=reader)
    monitor = PowerMonitor(meter, informer, interval=0, staleness=1e9)
    monitor.init()
    samples = []
    monitor.add_window_listener(samples.append)

    errs_node, errs_active, errs_wl = [], [], []
    monitor.refresh()  # seed counters (firstNodeRead semantics)
    for _ in range(windows):
        if interval > 0:
            time.sleep(interval)
        monitor.refresh()
        snap = monitor.snapshot()
        sample = samples[-1]
        ref = _f64_window(sample)
        errs_node.append(_max_rel_err(snap.node.power_uw,
                                      ref["node_power_uw"], floor=1e3))
        errs_active.append(_max_rel_err(snap.node.window_active_uj,
                                        ref["node_active_uj"], floor=1e3))
        # union the four kind tables back into id → power rows
        got = {}
        for table in (snap.processes, snap.containers,
                      snap.virtual_machines, snap.pods):
            for i, wid in enumerate(table.ids):
                got[wid] = table.power_uw[i]
        want_rows, got_rows = [], []
        for i, wid in enumerate(ref["ids"]):
            if wid in got:
                want_rows.append(ref["workload_power_uw"][i])
                got_rows.append(got[wid])
        if want_rows:
            errs_wl.append(_max_rel_err(np.asarray(got_rows),
                                        np.asarray(want_rows), floor=1e3))
    worst = max(errs_node + errs_active + (errs_wl or [0.0]))
    return {
        "mode": mode,
        "windows": windows,
        "interval_s": interval,
        "zones": list(monitor.zone_names()),
        "procs_last_window": len(samples[-1].batch.ids) if samples else 0,
        "node_power_max_rel_err": round(max(errs_node), 9),
        "node_active_energy_max_rel_err": round(max(errs_active), 9),
        "workload_power_max_rel_err": round(max(errs_wl or [0.0]), 9),
        "max_rel_err": round(worst, 9),
        "tolerance": TOL,
        "ok": bool(worst <= TOL),
    }


def run_live(windows: int, interval: float) -> dict:
    """Real RAPL + real /proc — bare-metal hosts only.

    /sys/class/powercap existing is NOT sufficient (cloud VMs ship the
    powercap class with no intel-rapl zones; hardened kernels make
    energy_uj root-only since PLATYPUS) — any meter init/read failure
    degrades to a skip so CI callers can fall back to proc mode.
    """
    if not os.path.isdir(RAPL_SYSFS):
        return {"mode": "live", "skipped": True,
                "reason": f"{RAPL_SYSFS} absent (not bare-metal)"}
    from kepler_tpu.device.rapl import RaplPowerMeter
    from kepler_tpu.resource.fast_procfs import make_proc_reader

    try:
        return validate(RaplPowerMeter(), make_proc_reader("/proc"),
                        windows, interval, "live")
    except (OSError, RuntimeError, ValueError) as err:
        return {"mode": "live", "skipped": True,
                "reason": f"RAPL unusable: {err!r}"[:200]}


def run_proc_live(windows: int, interval: float) -> dict:
    """Real /proc + fake meter (containers: no powercap)."""
    from kepler_tpu.device.fake import FakeCPUMeter
    from kepler_tpu.resource.fast_procfs import make_proc_reader

    out = validate(FakeCPUMeter(), make_proc_reader("/proc"),
                   windows, interval, "proc")
    out["meter"] = "fake"
    return out


def run_replay(path: str = DEFAULT_CAPTURE) -> dict:
    """Replay a checked-in capture through the closed loop."""
    with open(path, encoding="utf-8") as f:
        cap = json.load(f)
    zones = [ReplayZone(z["name"], z["readings"], z["max_uj"], i)
             for i, z in enumerate(cap["zones"])]
    reader = ReplayReader(cap["proc_windows"], cap["usage_ratios"])
    out = validate(ReplayMeter(zones), reader,
                   windows=len(cap["proc_windows"]) - 1, interval=0.0,
                   mode="replay")
    out["capture"] = os.path.basename(path)
    out["captured_on"] = cap.get("captured_on", "")
    return out


def capture(out_path: str, windows: int, interval: float) -> dict:
    """Record real host counters into a replayable capture file.

    Zone readings come from real RAPL when present, else from the fake
    meter (recorded in the file so replays are honestly labelled).
    """
    from kepler_tpu.resource.fast_procfs import make_proc_reader

    if os.path.isdir(RAPL_SYSFS):
        from kepler_tpu.device.rapl import RaplPowerMeter

        meter, source = RaplPowerMeter(), "rapl"
        meter.init()
    else:
        from kepler_tpu.device.fake import FakeCPUMeter

        meter, source = FakeCPUMeter(), "fake"
        if hasattr(meter, "init"):
            meter.init()
    reader = make_proc_reader("/proc")
    zones = list(meter.zones())
    readings: list[list[int]] = [[] for _ in zones]
    proc_windows, ratios = [], []
    for _ in range(windows + 1):
        for i, z in enumerate(zones):
            readings[i].append(int(z.energy()))
        procs = {str(p.pid()): p.cpu_time() for p in reader.all_procs()}
        proc_windows.append(procs)
        ratios.append(reader.cpu_usage_ratio())
        time.sleep(interval)
    cap = {
        "captured_on": time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime()),
        "meter_source": source,
        "interval_s": interval,
        "zones": [{"name": z.name(), "max_uj": int(z.max_energy()),
                   "readings": r} for z, r in zip(zones, readings)],
        "proc_windows": proc_windows,
        "usage_ratios": ratios,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(cap, f)
    return {"captured": out_path, "windows": windows,
            "meter_source": source,
            "procs": len(proc_windows[0])}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, default=5)
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--capture", help="record a capture to PATH and exit")
    ap.add_argument("--replay", nargs="?", const=DEFAULT_CAPTURE,
                    help="validate a capture instead of the live host")
    args = ap.parse_args()
    from kepler_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()

    if args.capture:
        print(json.dumps(capture(args.capture, args.windows,
                                 args.interval)))
        return
    if args.replay:
        out = run_replay(args.replay)
    else:
        out = run_live(args.windows, args.interval)
        if out.get("skipped"):
            live_skip = out
            out = run_proc_live(args.windows, args.interval)
            out["live"] = live_skip
    print(json.dumps(out))
    if not out.get("ok", False):
        sys.exit(1)


if __name__ == "__main__":
    main()
