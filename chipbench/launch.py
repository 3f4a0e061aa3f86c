"""The aggregator child: ``kepler_tpu.cmd.aggregator.main`` in-process.

The launcher adds only what the process that holds the chip alone can give
a benchmark, and changes nothing of how the aggregator serves:

- SIGUSR1 / SIGUSR2 start and stop ``jax.profiler`` around the measured
  window (traced runs only); each writes a marker file with the host time,
  so the parent knows the trace runs before it opens the window;
- every backend compile is noted with its host time (``jax.monitoring``),
  so the parent can refuse a run that compiled inside its window;
- the fullest device's memory, as ``memory_stats()`` gives it: the two
  peaks the runtime keeps (``peak_bytes_in_use`` counts buffers,
  ``peak_bytes_reserved`` the scratch of the loaded programs), each under
  its own name, and ``memory_peak_bytes``, their sum; beside them what a
  thread that reads ``bytes_in_use`` + ``bytes_reserved`` four times a
  second saw at most at ONE instant (``memory_held_max_bytes``), which
  needs no adding of two peaks that may not have coincided;
- on exit one JSON file: platform, device kind and count, that memory, the
  compiles, and — traced runs — the device
  planes of the profiler's trace as plain lists of ``[name, start_ns,
  duration_ns]`` (``trace.py`` reduces them; the parent never loads JAX)
  with the wall time of the trace's zero (``profile_start_time``, Unix ns),
  which lays them on the aggregator's window records (``records.py``).

    python chipbench/launch.py --config.file <yaml> --out <json>
                               [--trace-dir <dir>]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CLOCK_STATS = ("profile_start_time", "profile_stop_time")


def _device_planes(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced
    (``_reduce``); no planes where there is none."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"planes": []}
    return _reduce(ProfileData.from_file(paths[-1]).planes)


def _reduce(planes) -> dict:
    """Every device plane → {"planes": [{"plane", "lines": [{"line",
    "events": [[name, start_ns, dur_ns]]}]}]}, and beside them the stats
    ``CLOCK_STATS`` of the ``Task Environment`` plane: the Unix time in ns
    at which the profiler started, which is the zero every event's
    ``start_ns`` counts from, and at which it stopped. Host planes are left
    out: they are large, and what the host was doing comes from the
    aggregator's own records."""
    out: dict = {"planes": []}
    for plane in planes:
        if plane.name == "Task Environment":
            out.update((key, int(value)) for key, value in plane.stats
                       if key in CLOCK_STATS)
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"line": line.name, "events": events})
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config.file", dest="config_file", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args(argv)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)

    import jax
    from jax import monitoring

    compiles: list[list[float]] = []

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            compiles.append([time.time(), float(duration)])

    monitoring.register_event_duration_secs_listener(on_duration)

    marks: dict[str, float] = {}
    wanted: list[str] = []
    wake = threading.Event()

    def tracer() -> None:
        # a thread of its own: start_trace takes a while, and a signal
        # handler runs between two bytecodes of the main thread
        while True:
            wake.wait()
            wake.clear()
            while wanted:
                what = wanted.pop(0)
                if what == "start" and "start" not in marks:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    options.host_tracer_level = 0
                    jax.profiler.start_trace(args.trace_dir,
                                             profiler_options=options)
                    marks["start"] = time.time()
                elif what == "stop" and "start" in marks \
                        and "stop" not in marks:
                    marks["stop_asked"] = time.time()
                    jax.profiler.stop_trace()
                    marks["stop"] = time.time()
                else:
                    continue
                with open(os.path.join(args.trace_dir, f"{what}.mark"),
                          "w", encoding="utf-8") as f:
                    f.write(repr(marks[what]))

    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        threading.Thread(target=tracer, daemon=True,
                         name="chipbench-tracer").start()

        def ask(what: str):
            def handler(_sig, _frame) -> None:
                wanted.append(what)
                wake.set()
            return handler

        signal.signal(signal.SIGUSR1, ask("start"))
        signal.signal(signal.SIGUSR2, ask("stop"))

    held_max = [0]
    done = threading.Event()

    def watch_memory() -> None:
        # reads once the aggregator has brought the backend up; asking
        # earlier would bring it up here, before main chose the platform
        from jax._src import xla_bridge

        while not done.wait(0.25):
            if not xla_bridge.backends_are_initialized():
                continue
            for dev in jax.local_devices():
                stats = dev.memory_stats() or {}
                held_max[0] = max(held_max[0], int(
                    stats.get("bytes_in_use", 0)) + int(
                    stats.get("bytes_reserved", 0)))

    threading.Thread(target=watch_memory, daemon=True,
                     name="chipbench-memory").start()

    from kepler_tpu.cmd import aggregator

    rc = aggregator.main(["--config.file", args.config_file])
    done.set()

    out: dict = {"rc": rc, "compiles": compiles, "marks": marks}
    try:
        devices = jax.local_devices()
        out.update(platform=devices[0].platform,
                   device_kind=devices[0].device_kind, count=len(devices))
        rows = [dev.memory_stats() or {} for dev in devices]
        fullest = max(rows, key=lambda st: int(st.get(
            "peak_bytes_in_use", 0)) + int(st.get("peak_bytes_reserved", 0)))
        in_use = int(fullest.get("peak_bytes_in_use", 0))
        reserved = int(fullest.get("peak_bytes_reserved", 0))
        out["memory"] = {"memory_peak_bytes": in_use + reserved,
                         "peak_bytes_in_use": in_use,
                         "peak_bytes_reserved": reserved,
                         "memory_held_max_bytes": held_max[0]}
    except RuntimeError as err:  # no backend came up: main said why
        out["device_error"] = str(err)
    if args.trace_dir and "stop" in marks:
        out.update(_device_planes(args.trace_dir))
    tmp = args.out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(out, f)
    os.replace(tmp, args.out)
    return rc


if __name__ == "__main__":
    sys.exit(main())
