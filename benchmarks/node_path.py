"""On-node scrape-to-export benchmark: the half of the headline metric the
device can't answer.

BASELINE.json's headline is "pods/sec attributed + p99 scrape-to-export
latency"; the reference's entire per-node hot path is /proc scan →
attribute → render (`docs/developer/design/architecture/data-flow.md:
487-494` in the reference tree). This module measures that path at fleet
realism — 10k processes — through the REAL stack: a fake procfs + RAPL
sysfs tree on tmpfs, `PowerMonitor.snapshot()` (staleness 0, so every
scrape refreshes: zone reads, full proc scan, delta cache, classification,
jitted attribution) and the Prometheus collector's text render, end to end
per scrape.

Two configurations quantify the native scanner's win:
  * python — pure-Python ProcFSReader (one open/read/parse per PID)
  * native — the C batched scanner (one C call per tick), when buildable

Node agents don't own TPU chips (the aggregator does); the architecturally
honest configuration runs attribution on the host CPU — invoke this module
with JAX_PLATFORMS=cpu (bench.py does) or accept the ambient platform.

Run directly: ``python -m benchmarks.node_path --procs 10000`` → one JSON
line.
"""

from __future__ import annotations

# keplint: monotonic-only — scrape/render timings use perf_counter only

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

_ZONES = (("intel-rapl:0", "package-0"), ("intel-rapl:0:0", "dram"))
_RUNTIME_CGROUPS = (
    "0::/system.slice/docker-{cid}.scope\n",
    "0::/kubepods.slice/kubepods-burstable.slice/"
    "kubepods-burstable-pod{pod}.slice/cri-containerd-{cid}.scope\n",
)


def build_fake_host(root: str, n_procs: int, pct_container: float = 0.5,
                    seed: int = 0):
    """Fake /proc + /sys trees (the reference's tempdir-fixture strategy,
    ``rapl_sysfs_power_meter_test.go``) at bench scale. Returns
    (proc_dir, sysfs_dir, pids)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    proc = os.path.join(root, "proc")
    sysfs = os.path.join(root, "sys")
    os.makedirs(proc)
    pids = list(range(100, 100 + n_procs))
    for i, pid in enumerate(pids):
        d = os.path.join(proc, str(pid))
        os.makedirs(d)
        utime = int(rng.integers(100, 100000))
        write_stat_line(d, pid, f"proc-{pid}", utime, utime // 3)
        if rng.random() < pct_container:
            cid = f"{pid:064x}"[-64:]
            tmpl = _RUNTIME_CGROUPS[i % len(_RUNTIME_CGROUPS)]
            cgroup = tmpl.format(cid=cid, pod=f"pod{pid % 997}")
        else:
            cgroup = "0::/system.slice/ssh.service\n"
        with open(os.path.join(d, "cgroup"), "w") as f:
            f.write(cgroup)
        with open(os.path.join(d, "comm"), "w") as f:
            f.write(f"proc-{pid}\n")
        with open(os.path.join(d, "cmdline"), "wb") as f:
            f.write(f"/bin/proc-{pid}".encode() + b"\0")
        with open(os.path.join(d, "environ"), "wb") as f:
            f.write(b"")
    write_proc_stat(proc, tick=0)
    for dirname, name in _ZONES:
        zd = os.path.join(sysfs, "class", "powercap", dirname)
        os.makedirs(zd)
        for fname, val in (("name", name), ("energy_uj", 10_000_000),
                           ("max_energy_range_uj", 2**40)):
            with open(os.path.join(zd, fname), "w") as f:
                f.write(f"{val}\n")
    return proc, sysfs, pids


def write_stat_line(d: str, pid: int, comm: str, utime: int,
                    stime: int) -> None:
    head = f"{pid} ({comm}) S 1 1 1 0 -1 4194560 100 0 0 0"
    tail = (f"{utime} {stime} 0 0 20 0 1 0 100 0 0 "
            + " ".join(["0"] * 29))
    with open(os.path.join(d, "stat"), "w") as f:
        f.write(head + " " + tail)


def write_proc_stat(proc: str, tick: int) -> None:
    base = 1_000_000 + tick * 5_000
    idle = 4_000_000 + tick * 2_000
    with open(os.path.join(proc, "stat"), "w") as f:
        f.write(f"cpu  {base} {base // 10} {base // 2} {idle} "
                f"{idle // 8} 0 0 0 0 0\n")


def advance_host(proc: str, sysfs: str, pids, tick: int,
                 churn_frac: float = 0.1) -> None:
    """One synthetic interval: a rotating ``churn_frac`` slice of processes
    burns CPU, /proc/stat advances, RAPL counters accrete. Untimed."""
    n = len(pids)
    span = max(1, int(n * churn_frac))
    lo = (tick * span) % n
    for pid in (pids + pids)[lo:lo + span]:
        d = os.path.join(proc, str(pid))
        utime = 100_000 + tick * 150 + pid % 97
        write_stat_line(d, pid, f"proc-{pid}", utime, utime // 3)
    write_proc_stat(proc, tick)
    for i, (dirname, _) in enumerate(_ZONES):
        path = os.path.join(sysfs, "class", "powercap", dirname,
                            "energy_uj")
        with open(path, "w") as f:
            f.write(f"{10_000_000 + tick * (40_000_000 + i * 7_000_000)}\n")


def _percentile(sorted_vals, q: float) -> float:
    import math

    return sorted_vals[min(len(sorted_vals) - 1,
                           math.ceil(q * len(sorted_vals)) - 1)]


def spawn_burst(proc: str, start_pid: int, n: int) -> list[int]:
    """A mass pod reschedule: n NEW processes appear in one tick."""
    import numpy as np

    rng = np.random.default_rng(start_pid)
    new_pids = list(range(start_pid, start_pid + n))
    for i, pid in enumerate(new_pids):
        d = os.path.join(proc, str(pid))
        os.makedirs(d)
        utime = int(rng.integers(100, 100000))
        write_stat_line(d, pid, f"burst-{pid}", utime, utime // 3)
        cid = f"{pid:064x}"[-64:]
        tmpl = _RUNTIME_CGROUPS[i % len(_RUNTIME_CGROUPS)]
        with open(os.path.join(d, "cgroup"), "w") as f:
            f.write(tmpl.format(cid=cid, pod=f"pod{pid % 997}"))
        with open(os.path.join(d, "comm"), "w") as f:
            f.write(f"burst-{pid}\n")
        with open(os.path.join(d, "cmdline"), "wb") as f:
            f.write(f"/bin/burst-{pid}".encode() + b"\0")
        with open(os.path.join(d, "environ"), "wb") as f:
            f.write(b"CONTAINER_NAME=burst\0")
    return new_pids


def measure_reader(proc: str, sysfs: str, pids, use_native: bool,
                   iters: int) -> dict | None:
    """p50/p99 scrape→export ms through monitor+collector with one reader
    configuration. None when the native scanner isn't buildable."""
    from prometheus_client import CollectorRegistry

    from kepler_tpu.config.level import Level
    from kepler_tpu.device.rapl import RaplPowerMeter
    from kepler_tpu.exporter.prometheus.collector import PowerCollector
    from kepler_tpu.monitor.monitor import PowerMonitor
    from kepler_tpu.resource.fast_procfs import make_proc_reader
    from kepler_tpu.resource.informer import ResourceInformer

    if use_native:
        from kepler_tpu import native

        if native.scanner() is None:
            return None
    reader = make_proc_reader(proc, use_native=use_native)
    informer = ResourceInformer(reader=reader)
    meter = RaplPowerMeter(sysfs_path=sysfs)
    monitor = PowerMonitor(meter, informer, interval=0, staleness=0.0)
    monitor.init()
    collector = PowerCollector(monitor, node_name="bench-node",
                               metrics_level=Level.all(),
                               ready_timeout=0.0)
    registry = CollectorRegistry()
    registry.register(collector)
    advance_host(proc, sysfs, pids, 0)
    monitor.refresh()  # seed counters + caches + jit compile (untimed)
    collector.render_text()  # warm the label-block cache (untimed)
    monitor.join_prewarm()  # next-bucket compile stays out of timed iters

    scrape_ms, refresh_ms, render_ms, om_render_ms = [], [], [], []
    for it in range(1, iters + 1):
        advance_host(proc, sysfs, pids, it)
        t0 = time.perf_counter()
        # alternate negotiated formats so the p99 (and its budget gate)
        # covers BOTH: default Prometheus scrapes OpenMetrics
        out = collector.render_text(openmetrics=bool(it % 2))
        scrape_ms.append((time.perf_counter() - t0) * 1e3)
        assert len(out) > 1000, "empty scrape"
        # split legs (separate interval; staleness lifted so the render
        # leg measures rendering alone, not a second refresh)
        advance_host(proc, sysfs, pids, it + iters)
        t0 = time.perf_counter()
        monitor.refresh()
        t1 = time.perf_counter()
        monitor._staleness = 1e9
        collector.render_text()
        t2 = time.perf_counter()
        # OpenMetrics render (what default Prometheus negotiates) — same
        # caches, different counter headers; must stay as fast
        collector.render_text(openmetrics=True)
        t3 = time.perf_counter()
        monitor._staleness = 0.0
        refresh_ms.append((t1 - t0) * 1e3)
        render_ms.append((t2 - t1) * 1e3)
        om_render_ms.append((t3 - t2) * 1e3)
    # one STOCK prometheus_client render (staleness lifted so it times
    # rendering alone) — the baseline the direct render_text path replaced
    from prometheus_client.exposition import generate_latest

    monitor._staleness = 1e9
    t0 = time.perf_counter()
    generate_latest(registry)
    stock_render_ms = (time.perf_counter() - t0) * 1e3
    monitor._staleness = 0.0
    # churn burst (VERDICT r3 weak #5: first-sight classification latency
    # under a mass pod reschedule): 20% of the fleet appears in ONE tick;
    # time the refresh that absorbs it (batch classification in C on the
    # native reader vs per-file Python). The post-burst bucket's program
    # is warmed UNTIMED first so the number isolates the HOST cost the
    # readers differ on — a default-configured node crossing this many
    # buckets at once would ADDITIONALLY pay a one-time XLA compile
    # (~165 ms on CPU) for the new shape: once ever per shape, avoidable
    # via tpu.compilationCacheDir (enabled in the shipped deploy
    # configs); the monitor's background prewarm only covers gradual
    # single-bucket growth. The compile would otherwise also bill
    # whichever reader ran first (the jit cache is process-wide),
    # corrupting the native-vs-python comparison.
    import jax.numpy as jnp

    from kepler_tpu.ops.attribution import attribute, pad_to_bucket

    burst = spawn_burst(proc, 10_000_000, max(1, len(pids) // 5))
    # W counts ALL workload rows; each burst pid adds a proc AND a
    # (unique-id) container row
    cur_w = len(informer.feature_batch().ids)
    warm_w = pad_to_bucket(cur_w + 2 * len(burst), monitor._bucket)
    z = len(monitor.zone_names())
    attribute(jnp.zeros(z, jnp.float32), jnp.ones(z, bool),
              jnp.float32(0.5), jnp.zeros(warm_w, jnp.float32),
              jnp.zeros(warm_w, bool), jnp.float32(1.0), jnp.float32(1.0))
    t0 = time.perf_counter()
    monitor.refresh()
    burst_ms = (time.perf_counter() - t0) * 1e3
    snap = monitor.snapshot(clone=False)
    burst_set = {str(pid) for pid in burst}
    classified = sum(
        1 for i, wid in enumerate(snap.processes.ids)
        if wid in burst_set
        and snap.processes.meta[i].get("type") == "container")
    if classified != len(burst):  # not assert: -O must still validate
        raise RuntimeError(
            f"burst: {classified}/{len(burst)} classified as containers")
    scrape_ms.sort(), refresh_ms.sort(), render_ms.sort()
    om_render_ms.sort()
    return {
        "stock_render_ms": round(stock_render_ms, 3),
        "p99_ms": round(_percentile(scrape_ms, 0.99), 3),
        "p50_ms": round(_percentile(scrape_ms, 0.50), 3),
        "refresh_p50_ms": round(_percentile(refresh_ms, 0.50), 3),
        "render_p50_ms": round(_percentile(render_ms, 0.50), 3),
        "om_render_p50_ms": round(_percentile(om_render_ms, 0.50), 3),
        "burst_new_procs": len(burst),
        "burst_refresh_ms": round(burst_ms, 3),
    }


# churn-burst absorption budget at the reference burst size (2000 new
# procs = 20% of a 10k-proc node). Round-5 measured 175 ms on the native
# reader; the budget is measured + ~3× margin so it trips on regressions
# (per-burst-proc Python creeping back in), not on host noise.
NODE_BURST_BUDGET_MS = float(os.environ.get(
    "KEPLER_NODE_BURST_BUDGET_MS", "600.0"))


def run(n_procs: int = 10_000, iters: int = 11, root: str | None = None
        ) -> dict:
    """→ flat dict of node_scrape_* fields (bench.py merges them)."""
    tmp = root or tempfile.mkdtemp(prefix="kepler-nodepath-")
    try:
        # a FRESH tree per reader configuration: reusing one would rewind
        # the synthetic counters for the second reader (zero deltas, RAPL
        # wrap storms) and corrupt the native-vs-python comparison
        proc_n, sysfs_n, pids_n = build_fake_host(
            os.path.join(tmp, "native"), n_procs)
        native = measure_reader(proc_n, sysfs_n, pids_n, use_native=True,
                                iters=iters)
        proc_p, sysfs_p, pids_p = build_fake_host(
            os.path.join(tmp, "python"), n_procs)
        python = measure_reader(proc_p, sysfs_p, pids_p, use_native=False,
                                iters=iters)
    finally:
        if root is None:
            shutil.rmtree(tmp, ignore_errors=True)
    assert python is not None
    best = native or python
    out = {
        "node_scrape_to_export_p99_ms": best["p99_ms"],
        "node_scrape_to_export_p50_ms": best["p50_ms"],
        "node_scrape_refresh_p50_ms": best["refresh_p50_ms"],
        "node_scrape_render_p50_ms": best["render_p50_ms"],
        "node_scrape_om_render_p50_ms": best["om_render_p50_ms"],
        "node_scrape_procs": n_procs,
        "node_scrape_reader": "native" if native else "python",
        "node_scrape_py_p99_ms": python["p99_ms"],
        "node_scrape_py_p50_ms": python["p50_ms"],
        # budget gate: the whole on-node hot path (refresh + render) at
        # 10k procs must beat 100 ms p99 — "matching a Go exporter"
        # territory (VERDICT r3 item 2). Informational on the pure-Python
        # fallback; the native reader is the shipped configuration.
        "node_scrape_budget_ms": 100.0,
        "node_scrape_budget_ok": bool(best["p99_ms"] < 100.0),
    }
    out["node_churn_burst_procs"] = best["burst_new_procs"]
    out["node_churn_burst_ms"] = best["burst_refresh_ms"]
    out["node_churn_burst_py_ms"] = python["burst_refresh_ms"]
    # churn-burst absorption gate (ISSUE 5): one refresh that absorbs a
    # 20%-of-fleet pod reschedule must stay within an explicit budget —
    # the monitor's staging reuses its padded buffers across refreshes
    # (the node-side delta-slice analog of the aggregator's resident
    # batch), so the burst pays only scan+classify+the new tail, never a
    # fresh full-fleet allocation. Scaled linearly with the burst size;
    # like the scrape budget, informational on the pure-Python fallback
    # (the native reader is the shipped configuration).
    burst_budget = NODE_BURST_BUDGET_MS * (best["burst_new_procs"] / 2000)
    out["node_churn_burst_budget_ms"] = round(burst_budget, 1)
    out["node_churn_burst_ok"] = bool(
        best["burst_refresh_ms"] < burst_budget)
    if native:
        out["native_scan_speedup"] = round(
            python["refresh_p50_ms"] / max(native["refresh_p50_ms"], 1e-9),
            2)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=10_000)
    ap.add_argument("--iters", type=int, default=11)
    args = ap.parse_args()
    from kepler_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    print(json.dumps(run(args.procs, args.iters)))


if __name__ == "__main__":
    sys.exit(main())
