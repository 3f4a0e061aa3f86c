"""Bounded-growth guard for the node agent's caches.

This round added several cross-tick caches to the hot path — the scan
handle's fd cache, the informer's array state and object meta caches,
the monitor's RowStore accumulators and meta-row cache, the collector's
per-row label and whole-blob caches. Each has an eviction story; this
test runs a long churn workload (processes born and killed every tick)
and asserts every structure tracks the LIVE population instead of the
cumulative history — the node-agent analog of the aggregator's RSS soak
(`benchmarks/soak.py`).
"""

import os
import shutil

import pytest

from kepler_tpu.config.level import Level
from kepler_tpu.device.fake import FakeCPUMeter
from kepler_tpu.exporter.prometheus.collector import PowerCollector
from kepler_tpu.monitor.monitor import PowerMonitor
from kepler_tpu.native import load as _native_load
from kepler_tpu.resource.fast_procfs import make_proc_reader
from kepler_tpu.resource.informer import ResourceInformer

# gate on the scanner actually LOADING, not on g++ existing: a present
# but incompatible toolchain (the named environmental flake) must skip,
# not fail at make_proc_reader(use_native=True)
pytestmark = pytest.mark.skipif(
    _native_load() is None, reason="native scanner unavailable")


def write_proc(proc, pid, utime, container=False):
    # stat-line layout comes from the benchmarks' canonical fixture
    # writer — one definition of the fake stat format repo-wide
    from benchmarks.node_path import write_stat_line

    d = os.path.join(proc, str(pid))
    os.makedirs(d, exist_ok=True)
    write_stat_line(d, pid, f"churn-{pid}", utime, utime // 2)
    with open(os.path.join(d, "comm"), "w") as f:
        f.write(f"churn-{pid}\n")
    cg = (f"0::/system.slice/docker-{pid:064x}.scope\n" if container
          else "0::/system.slice/init.scope\n")
    with open(os.path.join(d, "cgroup"), "w") as f:
        f.write(cg)
    with open(os.path.join(d, "cmdline"), "wb") as f:
        f.write(b"/bin/churn\0")
    with open(os.path.join(d, "environ"), "wb") as f:
        f.write(b"")


def open_fd_count() -> int:
    return len(os.listdir("/proc/self/fd"))


def test_long_churn_keeps_every_cache_bounded(tmp_path):
    proc = str(tmp_path / "proc")
    os.makedirs(proc)
    with open(os.path.join(proc, "stat"), "w") as f:
        f.write("cpu  100 20 300 4000 500 60 70 0 0 0\n")
    base = list(range(100, 200))  # 100 long-lived procs
    for pid in base:
        write_proc(proc, pid, 1000)

    informer = ResourceInformer(reader=make_proc_reader(proc,
                                                        use_native=True))
    meter = FakeCPUMeter(seed=1)
    # staleness frozen HIGH from the start: every tick is exactly one
    # explicit refresh() — on a loaded host a wall-clock-coupled
    # staleness (0.0) makes each render_text() refresh AGAIN, so cache
    # contents raced the clock instead of tracking the tick count
    monitor = PowerMonitor(meter, informer, interval=0, staleness=1e9,
                           max_terminated=10, workload_bucket=32,
                           min_terminated_energy_uj=0.0)
    monitor.init()
    collector = PowerCollector(monitor, node_name="n0",
                               metrics_level=Level.all(),
                               ready_timeout=0.0)

    churn_pid = 10_000
    live_churn: list[int] = []
    fd_counts = []
    for tick in range(120):
        # two new container procs appear, the two oldest die
        for _ in range(2):
            churn_pid += 1
            write_proc(proc, churn_pid, 500 + tick, container=True)
            live_churn.append(churn_pid)
        while len(live_churn) > 10:
            dead = live_churn.pop(0)
            shutil.rmtree(os.path.join(proc, str(dead)),
                          ignore_errors=True)
        for pid in base:  # long-lived procs burn CPU
            write_proc(proc, pid, 1000 + tick * 7)
        with open(os.path.join(proc, "stat"), "w") as f:
            f.write(f"cpu  {100 + tick * 50} 20 300 {4000 + tick * 20} "
                    "500 60 70 0 0 0\n")
        monitor.refresh()
        out = collector.render_text()
        assert out
        if tick >= 60:
            # count fds only with the bucket-prewarm thread quiesced —
            # a concurrently compiling prewarm opens transient fds, and
            # sampling mid-flight made the flatness bound load-dependent
            monitor.join_prewarm()
            fd_counts.append(open_fd_count())

    live = len(base) + len(live_churn)
    # informer: caches track the live set, not history
    assert len(informer._proc_cache) == live
    st = informer._arr
    assert st is not None and len(st.procs) == live
    # container slots: only live churn containers (plus none from base)
    assert len(st.cont_slots) == len(live_churn)
    # monitor: cumulative rows are popped on termination
    proc_store = monitor._cumulative["processes"]
    assert len(proc_store.rows) == live
    cont_store = monitor._cumulative["containers"]
    assert len(cont_store.rows) == len(live_churn)
    # collector: label cache covers live + currently-tracked terminated
    # rows only (the tracker is capped at 10). Staleness has been frozen
    # since construction, so this render and the comparison below read
    # the SAME snapshot by count-based construction, not clock luck.
    collector.render_text()
    snap = monitor._snapshot
    rendered_rows = sum(
        len(getattr(snap, a).ids)
        for a in ("processes", "containers", "virtual_machines", "pods",
                  "terminated_processes", "terminated_containers",
                  "terminated_virtual_machines", "terminated_pods"))
    assert len(collector._label_cache) <= rendered_rows
    assert len(collector._blob_cache) <= 8  # (kind, state) pairs
    # native scan handle: fds track live pids (sweep on vanish); the
    # process-wide fd count must be flat across the back half of the run
    assert max(fd_counts) - min(fd_counts) <= 4, fd_counts
