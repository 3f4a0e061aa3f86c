"""Concurrency contracts under hammering — the reference's strongest suite
(SURVEY §4: monitor_concurrency_test.go runs 2×NumCPU goroutines under the
race detector; clone_test.go proves snapshot deep-copy isolation;
power_collector_concurrency_test.go hammers concurrent scrapes).

The contracts under test (docs/developer/power-attribution-guide.md in the
reference, mirrored here): monitor public API thread-safe via
single-writer + singleflight; snapshots immutable and isolated; the
exporter path safe against concurrent scrapes; fleet ingest safe against
concurrent POSTs racing aggregation.
"""

import os
import threading
import time

import numpy as np
import pytest

from kepler_tpu.device.fake import FakeCPUMeter
from kepler_tpu.monitor.monitor import PowerMonitor
from kepler_tpu.resource import ResourceInformer

from tests.test_resource import MockProc, MockReader

N_THREADS = 2 * (os.cpu_count() or 4)


class AdvancingReader(MockReader):
    """Every scan advances each proc's CPU time — so every refresh sees a
    nonzero per-proc delta and the conservation invariant is live."""

    def all_procs(self):
        for proc in self.procs:
            proc.cpu += 0.5 * proc.pid()
        return list(self.procs)


def make_monitor(**kw):
    procs = [MockProc(1, cpu=10.0), MockProc(2, cpu=20.0),
             MockProc(3, cpu=20.0)]
    reader = AdvancingReader(procs, usage_ratio=0.5)
    informer = ResourceInformer(reader=reader)
    meter = FakeCPUMeter(seed=42)
    kw.setdefault("staleness", 0.0)
    m = PowerMonitor(meter, informer, interval=0, workload_bucket=8, **kw)
    m.init()
    return m


def hammer(fn, n_threads=N_THREADS, per_thread=20):
    """Run fn concurrently from many threads; re-raise the first error."""
    errors = []
    barrier = threading.Barrier(n_threads)

    def worker():
        try:
            barrier.wait(timeout=10)
            for _ in range(per_thread):
                fn()
        except Exception as err:  # noqa: BLE001 — surfaced below
            errors.append(err)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]


class TestMonitorHammer:
    def test_concurrent_snapshots_stay_consistent(self):
        m = make_monitor()
        m.refresh()
        time.sleep(0.01)
        m.refresh()  # second refresh → power populated

        def read():
            snap = m.snapshot()
            # internal consistency of whatever snapshot we got: every
            # workload table has the same zone axis as the node
            z = snap.node.energy_uj.shape[0]
            for table in (snap.processes, snap.containers, snap.pods):
                assert table.energy_uj.shape[1] == z
                assert np.isfinite(table.power_uw).all()
            # conservation: Σ process power == node active power (within f32)
            np.testing.assert_allclose(
                snap.processes.power_uw.sum(axis=0),
                snap.node.active_power_uw, rtol=1e-3, atol=1e-3)

        hammer(read)

    def test_staleness_zero_triggers_refresh_per_reader_safely(self):
        """staleness=0 makes every snapshot() refresh — max contention on
        the singleflight path."""
        m = make_monitor()
        m.refresh()
        hammer(lambda: m.snapshot(), per_thread=5)

    def test_refresh_races_snapshot(self):
        m = make_monitor(staleness=1000.0)  # readers never trigger refresh
        m.refresh()
        stop = threading.Event()

        def refresher():
            while not stop.is_set():
                m.refresh()

        t = threading.Thread(target=refresher)
        t.start()
        try:
            hammer(lambda: m.snapshot(), n_threads=8, per_thread=25)
        finally:
            stop.set()
            t.join(timeout=30)


class TestSnapshotIsolation:
    def test_clone_mutation_does_not_leak(self):
        m = make_monitor(staleness=1000.0)
        m.refresh()
        a = m.snapshot()
        a.processes.energy_uj[:] = -1.0  # vandalise the clone's arrays
        a.node.energy_uj[:] = -1.0
        b = m.snapshot()
        assert (np.asarray(b.processes.energy_uj) >= 0).all()
        assert (np.asarray(b.node.energy_uj) >= 0).all()

    def test_two_readers_get_independent_arrays(self):
        m = make_monitor(staleness=1000.0)
        m.refresh()
        a, b = m.snapshot(), m.snapshot()
        assert a.processes.energy_uj is not b.processes.energy_uj
        a.processes.energy_uj[:] = 123.0
        assert not np.array_equal(a.processes.energy_uj,
                                  b.processes.energy_uj)


class TestCollectorConcurrency:
    def test_concurrent_scrapes(self):
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest

        from kepler_tpu.config.level import Level
        from kepler_tpu.exporter.prometheus.collector import PowerCollector

        m = make_monitor(staleness=1000.0)
        m.refresh()
        time.sleep(0.01)
        m.refresh()
        registry = CollectorRegistry()
        registry.register(PowerCollector(m, "node0", Level.all()))

        def scrape():
            text = generate_latest(registry).decode()
            assert "kepler_node_cpu_joules_total" in text
            assert "kepler_process_cpu_watts" in text

        hammer(scrape, n_threads=8, per_thread=10)

    def test_concurrent_render_text_with_refreshes_and_churn(self):
        """The direct text renderer keeps per-row label and whole-blob
        caches across scrapes; concurrent scrapes racing refreshes THAT
        CHURN MEMBERSHIP (procs appear and vanish, so the meta_gen
        invalidation and cache rebuilds fire mid-hammer, like a pod
        reschedule under ThreadingHTTPServer) must see consistent
        output. When no refresh interleaves a scrape, its bytes must
        equal a cold fresh-collector render of the same published
        snapshot — a torn cached-labels/new-values mix cannot pass that.
        """
        from kepler_tpu.config.level import Level
        from kepler_tpu.exporter.prometheus.collector import PowerCollector

        m = make_monitor(staleness=1000.0)
        reader = m._resources._fs
        m.refresh()
        time.sleep(0.01)
        m.refresh()
        collector = PowerCollector(m, "node0", Level.all())
        baseline = collector.render_text()
        assert b"kepler_process_cpu_watts" in baseline
        stop = threading.Event()
        refresh_errors: list[Exception] = []

        def refresher():
            pid = 100
            while not stop.is_set():
                try:
                    # membership churn: one proc appears, an earlier
                    # synthetic one vanishes (keeps the set bounded)
                    reader.procs.append(MockProc(pid, cpu=1.0))
                    if len(reader.procs) > 6:
                        reader.procs.pop(3)
                    pid += 1
                    m.refresh()
                except Exception as err:  # pragma: no cover
                    refresh_errors.append(err)
                    return
                time.sleep(0.001)

        t = threading.Thread(target=refresher, daemon=True)
        t.start()
        try:
            def scrape():
                snap_before = m._snapshot
                out = collector.render_text()
                fresh = PowerCollector(m, "node0", Level.all())
                out_cold = fresh.render_text()
                if m._snapshot is snap_before:
                    # the published snapshot was stable across BOTH
                    # renders: warm caches must reproduce the cold
                    # render byte-for-byte (a torn mix cannot)
                    assert out == out_cold
                else:
                    # a refresh interleaved: still structurally whole
                    assert out.count(
                        b"# TYPE kepler_process_cpu_watts") == 1
                    for line in out.splitlines():
                        if line.startswith(b"kepler_process_cpu_watts{"):
                            assert (line.count(b"{") == 1
                                    and b"} " in line)
                            labels = line[line.index(b"{") + 1:
                                          line.index(b"} ")]
                            assert b'zone="' in labels
                            assert labels.count(b"pid=") == 1

            hammer(scrape, n_threads=8, per_thread=20)
        finally:
            stop.set()
            t.join(timeout=30)
        assert not t.is_alive(), "refresher deadlocked against scrapes"
        assert not refresh_errors


class TestAggregatorIngestRaces:
    def test_reports_race_aggregation(self):
        from kepler_tpu.fleet import Aggregator
        from kepler_tpu.fleet.wire import encode_report
        from kepler_tpu.parallel.fleet import MODE_RATIO, NodeReport
        from kepler_tpu.parallel.mesh import make_mesh
        from kepler_tpu.server.http import APIServer

        agg = Aggregator(APIServer(), model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.windows.mesh = make_mesh()
        rng = np.random.default_rng(0)
        seqs = {i: 0 for i in range(N_THREADS)}
        lock = threading.Lock()

        class Req:
            command = "POST"

        def post(i):
            with lock:
                seqs[i] += 1
                seq = seqs[i]
            cpu = rng.uniform(0.1, 5.0, 4).astype(np.float32)
            rep = NodeReport(
                node_name=f"node-{i}",
                zone_deltas_uj=np.asarray([1e7, 2e7], np.float32),
                zone_valid=np.ones(2, bool), usage_ratio=0.6,
                cpu_deltas=cpu, workload_ids=[f"w{j}" for j in range(4)],
                node_cpu_delta=float(cpu.sum()), dt_s=5.0, mode=MODE_RATIO)
            r = Req()
            r.body = encode_report(rep, ["package", "dram"], seq=seq)
            status, _, _ = agg._handle_report(r)
            assert status == 204

        idx = iter(range(10_000))
        stop = threading.Event()
        agg_errors = []

        def aggregate_loop():
            try:
                while not stop.is_set():
                    agg.aggregate_once()
            except Exception as err:  # noqa: BLE001
                agg_errors.append(err)

        t = threading.Thread(target=aggregate_loop)
        t.start()
        try:
            hammer(lambda: post(next(idx) % N_THREADS),
                   n_threads=N_THREADS, per_thread=10)
        finally:
            stop.set()
            t.join(timeout=60)
        assert not agg_errors, agg_errors[:2]
        result = agg.aggregate_once()
        assert result is not None
        assert np.isfinite(np.asarray(result.wl_power_uw)).all()
