"""Device-plane fault tolerance: the window degradation ladder (ISSUE 6).

Deterministic (seeded, count-scoped) chaos for the four device fault
sites consulted inside the window engine and the aggregator's
dispatch/publish pipeline:

* ``device.dispatch_error`` mid-pipeline at depth 2 — the aggregator
  abandons the in-flight window, re-seeds the donated ring, demotes ONE
  rung, and recomputes the interval at the new rung: every interval
  still publishes, node rows stay complete and unique, and the
  published windows are BIT-consistent with a fault-free serial packed
  reference;
* ``device.compile_error`` on a bucket-growth rung — the failed compile
  leaves no poisoned cache entry, the ladder absorbs it;
* ``device.stall`` — a hung fetch trips the dispatch-timeout watchdog
  and demotes instead of wedging the aggregation loop;
* the full ladder walk: with the device permanently failed the
  aggregator reaches the pure-NumPy rung and keeps publishing correct
  ratio attribution indefinitely; clearing the fault re-promotes back
  to packed-pipelined after ``repromote_after`` clean windows per rung.

All tests run under the ``chaos`` marker (``make chaos``).
"""

from __future__ import annotations

import numpy as np
import pytest

from kepler_tpu import fault
from kepler_tpu.fault import FaultPlan, FaultSpec
from kepler_tpu.fleet.aggregator import Aggregator, _Stored
from kepler_tpu.fleet.scheduler import (RUNG_EINSUM, RUNG_NUMPY,
                                        RUNG_PACKED_SERIAL, RUNG_PIPELINED)
from kepler_tpu.fleet.window import DeviceWindowError  # noqa: F401 (API)
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
from kepler_tpu.parallel.mesh import make_mesh
from kepler_tpu.server.http import APIServer

pytestmark = pytest.mark.chaos

ZONES = ("package", "dram")


def make_report(name: str, seed: int, w: int = 4,
                mode: int = MODE_RATIO) -> NodeReport:
    rng = np.random.default_rng(abs(hash((name, seed))) % (2 ** 32))
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    return NodeReport(
        node_name=name,
        zone_deltas_uj=rng.uniform(1e7, 5e8, len(ZONES)).astype(np.float32),
        zone_valid=np.ones(len(ZONES), bool),
        usage_ratio=float(rng.uniform(0.2, 0.9)),
        cpu_deltas=cpu,
        workload_ids=[f"{name}-w{k}" for k in range(w)],
        node_cpu_delta=float(cpu.sum()),
        dt_s=5.0,
        mode=mode,
        workload_kinds=np.ones(w, np.int8),
    )


def make_agg(depth: int = 2, **kw) -> Aggregator:
    kw.setdefault("model_mode", "mlp")
    kw.setdefault("node_bucket", 8)
    kw.setdefault("workload_bucket", 8)
    kw.setdefault("stale_after", 1e9)
    kw.setdefault("repromote_after", 2)
    kw.setdefault("dispatch_timeout", 10.0)
    ticks = [1e9]
    agg = Aggregator(APIServer(), pipeline_depth=depth,
                     clock=lambda: ticks[0], **kw)
    agg.test_clock = ticks
    agg.windows.mesh = make_mesh()
    return agg


def seed_window(agg: Aggregator, win: int, n_nodes: int = 5,
                w: int = 4) -> None:
    agg.test_clock[0] += 5.0
    now = agg.test_clock[0]
    for i in range(n_nodes):
        mode = MODE_MODEL if i % 2 else MODE_RATIO
        rep = make_report(f"n{i:02d}", win * 100 + i, w=w, mode=mode)
        agg._reports[rep.node_name] = _Stored(
            report=rep, zone_names=ZONES, received=now, seq=win + 1,
            run="r1")


def run_windows(agg: Aggregator, n: int, start: int = 0,
                n_nodes: int = 5, w: int = 4) -> list:
    published = []
    for win in range(start, start + n):
        seed_window(agg, win, n_nodes=n_nodes, w=w)
        result = agg.aggregate_once()
        published.append(result)
    return published


def assert_windows_equal(a, b) -> None:
    """Bit-level comparison of two published windows (same schedule
    seed): identical node sets, node power/energy, and per-workload
    watts row by row."""
    assert set(a.names) == set(b.names)
    assert list(a.zones) == list(b.zones)
    for name in a.names:
        i, j = a.rows[name], b.rows[name]
        np.testing.assert_array_equal(a.node_power_uw[i],
                                      b.node_power_uw[j])
        np.testing.assert_array_equal(a.node_energy_uj[i],
                                      b.node_energy_uj[j])
        wl_a = a.wl_power_uw[i, :a.counts[i]]
        wl_b = b.wl_power_uw[j, :b.counts[j]]
        np.testing.assert_array_equal(wl_a, wl_b)


class TestDispatchErrorMidPipeline:
    def test_demotes_within_one_window_and_recovers_bit_exact(self):
        """Acceptance: dispatch error armed mid-pipeline at depth 2 →
        every interval publishes (no gap beyond pipeline fill, no
        duplicate node rows), demotion within ≤1 window, re-promotion
        after ``repromote_after`` clean windows, all published windows
        bit-consistent with a fault-free serial packed run."""
        n_win = 10
        fail_at = 4  # 0-based window index that hits the armed fault

        # fault-free serial packed reference: depth 1 publishes window k
        # at call k, so reference[k] is window k's ground truth
        ref_agg = make_agg(depth=1)
        reference = run_windows(ref_agg, n_win)
        ref_agg.shutdown()
        assert all(r is not None for r in reference)

        agg = make_agg(depth=2)
        # skip: one check per window dispatch → windows 0..3 pass, the
        # 5th dispatch (window index 4) fails once
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    skip=fail_at, count=1)])
        with fault.installed(plan):
            published = run_windows(agg, n_win)
            tail = agg.windows.drain()
        assert plan.fired("device.dispatch_error") == 1

        # demotion within ≤1 window: the failing call itself demoted and
        # still published (serial recompute at the demoted rung)
        assert published[fail_at] is not None
        assert agg.windows._stats["window_demotions_total"] == 1
        assert agg.windows._demotions_by_reason == {"dispatch_error": 1}
        # re-promotion landed after repromote_after clean windows
        assert agg.windows._stats["window_repromotions_total"] == 1
        assert agg.windows._rung == RUNG_PIPELINED

        # no gap: every call after the initial pipeline fill publishes,
        # except the single re-fill slot right after re-promotion
        # (identical to process start — the documented staleness bound)
        # (the recovery window itself counts clean, so the re-promotion
        # lands repromote_after−1 windows later and the fill slot is the
        # call after that)
        gaps = [i for i, r in enumerate(published) if r is None]
        assert gaps == [0, fail_at + agg.windows._repromote_after]
        # no duplicates, monotone publication order
        seen = [r.timestamp for r in published if r is not None]
        if tail is not None:
            seen.append(tail.timestamp)
        assert seen == sorted(seen)
        assert len(set(seen)) == len(seen)

        # bit-consistency: every published window matches the fault-free
        # serial reference for the SAME schedule window (timestamps map
        # publications back to schedule indices; 5 s per window)
        base = 1e9
        all_published = [r for r in published if r is not None]
        if tail is not None:
            all_published.append(tail)
        for result in all_published:
            win = int(round((result.timestamp - base) / 5.0)) - 1
            assert_windows_equal(result, reference[win])

    def test_node_rows_complete_after_recovery(self):
        agg = make_agg(depth=2)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    skip=2, count=1)])
        with fault.installed(plan):
            published = run_windows(agg, 6)
            agg.shutdown()
        for result in [p for p in published if p is not None]:
            assert sorted(result.names) == [f"n{i:02d}" for i in range(5)]
            assert len(set(result.rows[n] for n in result.names)) == 5


class TestCompileErrorOnGrowth:
    def test_growth_compile_failure_demotes_and_recovers(self):
        """Window 3 doubles the workload count → bucket growth → the
        armed compile fault fires on the growth rung. The ladder absorbs
        it (no poisoned cache entry) and the fleet keeps publishing."""
        agg = make_agg(depth=2)
        plan = FaultPlan([FaultSpec(site="device.oom_on_grow", count=1)])
        with fault.installed(plan):
            run_windows(agg, 3, n_nodes=5, w=4)
            # workload growth: w 4 → 12 crosses the bucket (8)
            published = run_windows(agg, 4, start=3, n_nodes=5, w=12)
            agg.shutdown()
        assert plan.fired("device.oom_on_grow") == 1
        assert agg.windows._demotions_by_reason == {"oom_on_grow": 1}
        # the growth window itself still published, at the demoted rung
        assert published[0] is not None
        assert published[0].timestamp == 1e9 + 4 * 5.0
        assert sorted(published[0].names) == [f"n{i:02d}" for i in range(5)]

    def test_cold_compile_failure_is_absorbed(self):
        """compile_error on the very first packed program: the ladder
        falls to the serial packed rung (whose compile is NOT faulted —
        count=1) and the first window still publishes."""
        agg = make_agg(depth=1)
        plan = FaultPlan([FaultSpec(site="device.compile_error", count=1)])
        with fault.installed(plan):
            published = run_windows(agg, 2)
            agg.shutdown()
        assert plan.fired("device.compile_error") == 1
        assert all(p is not None for p in published)
        assert agg.windows._stats["window_demotions_total"] == 1


class TestStallWatchdog:
    def test_hung_fetch_demotes_instead_of_wedging(self):
        """device.stall injects a 1.5 s hang ahead of the fetch; the
        0.2 s dispatch timeout trips, the loop demotes and recomputes —
        the interval still publishes and the loop never wedges."""
        agg = make_agg(depth=1, dispatch_timeout=0.2)
        plan = FaultPlan([FaultSpec(site="device.stall", count=1,
                                    arg=1.5)])
        with fault.installed(plan):
            published = run_windows(agg, 3)
            agg.shutdown()
        assert plan.fired("device.stall") == 1
        assert agg.windows._demotions_by_reason == {"stall": 1}
        assert all(p is not None for p in published)

    def test_timeout_zero_disables_watchdog(self):
        agg = make_agg(depth=1, dispatch_timeout=0.0)
        plan = FaultPlan([FaultSpec(site="device.stall", count=1,
                                    arg=0.05)])
        with fault.installed(plan):
            published = run_windows(agg, 2)
            agg.shutdown()
        # the injected sleep ran inline (no worker thread, no timeout):
        # slow, but never a demotion
        assert agg.windows._stats["window_demotions_total"] == 0
        assert all(p is not None for p in published)


class TestFullLadderWalk:
    def test_dead_device_reaches_numpy_and_keeps_publishing(self):
        """Acceptance: with every dispatch failing, the aggregator walks
        packed-pipelined → packed-serial → einsum-serial → numpy-host
        INSIDE the first window (each retry demotes one rung) and keeps
        publishing correct ratio attribution indefinitely; /healthz
        reports fleet-window degraded with the rung named."""
        agg = make_agg(depth=2, repromote_after=3)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error")])
        with fault.installed(plan):
            published = run_windows(agg, 4)
            # every interval published (the NumPy rung is depth 1)
            assert all(p is not None for p in published)
            # rung probing: after repromote_after clean numpy windows the
            # einsum rung is retried, fails, and demotes right back —
            # the rung must never climb past einsum while the fault holds
            assert agg.windows._rung in (RUNG_NUMPY, RUNG_EINSUM)

            health = agg.window_health()
            assert health["ok"] is False
            assert health["rung_name"] in ("numpy-host", "einsum-serial")
            assert health["demotions_total"] >= 3

            # the literal /healthz surface: the registered probe turns
            # the endpoint degraded and names the rung
            from kepler_tpu.server.health import HealthRegistry
            registry = HealthRegistry()
            registry.register_probe("fleet-window", agg.window_health)
            status, _headers, body = registry.handle_healthz(None)
            assert status == 503
            import json
            payload = json.loads(body)
            assert payload["status"] == "degraded"
            probe = payload["components"]["fleet-window"]
            assert probe["ok"] is False
            assert probe["rung_name"] == health["rung_name"]

            # ratio-node attribution at the numpy rung is exact
            result = published[-1]
            for name in result.names:
                stored = agg._reports[name]
                if stored.report.mode != MODE_RATIO:
                    continue
                i = result.rows[name]
                zd = np.where(stored.report.zone_valid,
                              stored.report.zone_deltas_uj, 0.0)
                order = np.argsort(np.asarray(ZONES))  # canonical zones
                np.testing.assert_allclose(
                    result.node_power_uw[i],
                    (zd / stored.report.dt_s)[order], rtol=1e-6)

    def test_walks_back_up_after_fault_clears(self):
        """The fault window closes → the ladder re-promotes one rung per
        ``repromote_after`` clean windows all the way back to
        packed-pipelined, and the healthy-path windows published after
        full recovery are bit-consistent with a fault-free serial run."""
        n_fail, repromote = 2, 2
        agg = make_agg(depth=2, repromote_after=repromote)
        # every dispatch in the first n_fail windows fails; packed +
        # legacy dispatches each consult the site, so budget generously
        # and bound by a duration window instead of a count: windows are
        # 5 s apart on the test clock but the plan clock is monotonic —
        # use count to scope precisely (3 retries in window 0 walks to
        # numpy; window 1 probes nothing new = 0 fires)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    count=3)])
        with fault.installed(plan):
            walk = run_windows(agg, 1)
        assert agg.windows._rung == RUNG_NUMPY
        assert walk[0] is not None

        # fault cleared: 2 clean → einsum, 2 → packed serial, 2 → full
        recovered = run_windows(agg, 3 * repromote + 2, start=1)
        assert agg.windows._rung == RUNG_PIPELINED
        assert agg.windows._stats["window_repromotions_total"] == 3

        # compare the last windows (fully recovered, pipeline refilled)
        # against a fault-free depth-1 reference of the same schedule
        ref = make_agg(depth=1)
        ref_published = run_windows(ref, 3 * repromote + 3)
        ref_agg_map = {round(r.timestamp, 3): r
                       for r in ref_published if r is not None}
        tail = agg.windows.drain()
        final = [r for r in recovered if r is not None][-2:]
        if tail is not None:
            final.append(tail)
        ref.shutdown()
        for result in final:
            assert_windows_equal(result,
                                 ref_agg_map[round(result.timestamp, 3)])

    def test_failed_probes_back_off_exponentially(self):
        """A permanently failed device: each re-promotion probe that
        dies before proving itself DOUBLES the clean-window threshold
        for the next probe (capped), so probing decays instead of
        leaking a fetch worker at a constant rate. Walk-down demotions
        (no promotion preceding them) must NOT inflate the penalty."""
        agg = make_agg(depth=1, repromote_after=1)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error")])
        with fault.installed(plan):
            run_windows(agg, 1)
            # the initial walk to numpy is 3 demotions, none a probe
            assert agg.windows._probe_penalty == 1
            # window 1: promote → window 2: probe dies → penalty 2;
            # then 2 clean needed → probe at window 5 dies → penalty 4
            run_windows(agg, 10, start=1)
            assert agg.windows._probe_penalty >= 4
            probes_before = agg.windows._stats["window_repromotions_total"]
            run_windows(agg, 10, start=11)
            # the decaying cadence: the second batch of 10 windows fires
            # strictly fewer probes than an un-backed-off ladder would
            # (threshold is ≥ 4 clean windows per probe by now)
            assert (agg.windows._stats["window_repromotions_total"]
                    - probes_before) <= 3
        # recovery resets the penalty only on reaching full health
        # (penalty ≤ 16 by now → at most 48 clean windows to climb the
        # three rungs back to packed-pipelined)
        assert agg.windows._probe_penalty <= 16
        recovered = run_windows(agg, 52, start=21)
        assert agg.windows._rung == RUNG_PIPELINED
        assert agg.windows._probe_penalty == 1
        assert recovered[-1] is not None
        agg.shutdown()

    def test_fallback_disabled_raises(self):
        agg = make_agg(depth=1, fallback_enabled=False)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    count=1)])
        with fault.installed(plan):
            seed_window(agg, 0)
            with pytest.raises(DeviceWindowError):
                agg.aggregate_once()
        assert agg.windows._stats["window_demotions_total"] == 0


class TestLadderMetrics:
    def test_prometheus_families_expose_ladder_state(self):
        agg = make_agg(depth=1)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    count=1)])
        with fault.installed(plan):
            run_windows(agg, 1 + agg.windows._repromote_after)
            agg.shutdown()
        families = {f.name: f for f in agg.collect()}
        # prometheus_client strips the _total suffix into family names
        demote = families["kepler_fleet_window_demotions"]
        samples = {tuple(s.labels.values()): s.value
                   for s in demote.samples if s.name.endswith("_total")}
        assert samples == {("dispatch_error",): 1.0}
        rung = families["kepler_fleet_window_degraded"]
        assert rung.samples[0].value == 0.0  # re-promoted by now
        repromote = families["kepler_fleet_window_repromotions"]
        totals = [s.value for s in repromote.samples
                  if s.name.endswith("_total")]
        assert totals == [1.0]


class TestShardedChaos:
    """ISSUE 7: the sharded-window rung composes with the ladder — one
    shard's device failure demotes to the existing SINGLE-device rungs
    (the demoted window drops the mesh-wide dispatch), `reset()`
    re-seeds every shard ring, and recovery re-promotes back to the
    sharded rung bit-equal."""

    def test_rung0_is_sharded_and_demotes_to_single_device(self):
        import jax

        from kepler_tpu.fleet.window import (PackedWindowEngine,
                                             ShardedWindowEngine)

        n_dev = len(jax.devices())
        assert n_dev >= 4  # conftest forces 8 simulated devices
        agg = make_agg(depth=2)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    skip=2, count=1)])
        with fault.installed(plan):
            published = run_windows(agg, 2)
            assert isinstance(agg.windows._engine, ShardedWindowEngine)
            assert agg.window_health()["rung_name"] == \
                "packed-sharded-pipelined"
            assert agg.windows._stats["window_shards"] == n_dev
            # window 2 hits the armed fault: the shard failure demotes to
            # the packed-serial rung on ONE device and still publishes
            published += run_windows(agg, 1, start=2)
            assert published[-1] is not None
            assert agg.windows._rung == RUNG_PACKED_SERIAL
            serial_engine = agg.windows._engine_serial
            assert type(serial_engine) is PackedWindowEngine
            assert serial_engine._mesh.devices.size == 1
            assert agg.windows._stats["window_shards"] == 1
            health = agg.window_health()
            assert health["rung_name"] == "packed-serial"
            assert health["shards"] == 1
            # sharded ring was re-seeded wholesale
            assert agg.windows._engine._buffers == []
            assert agg.windows._engine._shard_of == {}
        agg.shutdown()

    def test_shard_failure_demotes_and_repromotes_bit_equal(self):
        """Acceptance: dispatch error on the sharded rung mid-pipeline →
        demote through the ladder, re-promote back to the SHARDED rung,
        and every published window stays bit-consistent with a fault-free
        single-device serial packed reference."""
        import jax

        n_win, fail_at = 10, 4
        ref = make_agg(depth=1)
        ref.windows.mesh = make_mesh([1], devices=jax.devices()[:1])
        reference = run_windows(ref, n_win)
        ref.shutdown()
        assert all(r is not None for r in reference)

        agg = make_agg(depth=2)
        plan = FaultPlan([FaultSpec(site="device.dispatch_error",
                                    skip=fail_at, count=1)])
        with fault.installed(plan):
            published = run_windows(agg, n_win)
            tail = agg.windows.drain()
        assert plan.fired("device.dispatch_error") == 1
        assert agg.windows._stats["window_demotions_total"] == 1
        assert agg.windows._stats["window_repromotions_total"] == 1
        # back on the sharded rung, pipeline refilled
        assert agg.windows._rung == RUNG_PIPELINED
        assert agg.window_health()["rung_name"] == \
            "packed-sharded-pipelined"
        assert agg.windows._stats["window_shards"] == len(jax.devices())

        base = 1e9
        all_published = [r for r in published if r is not None]
        if tail is not None:
            all_published.append(tail)
        for result in all_published:
            win = int(round((result.timestamp - base) / 5.0)) - 1
            assert_windows_equal(result, reference[win])
        agg.shutdown()

    def test_shard_oom_on_grow_demotes_then_sharded_regrows(self):
        """Bucket growth on the sharded rung hits device.oom_on_grow:
        the ladder absorbs it at a single-device rung, the interval
        publishes, and the re-promoted sharded engine re-packs the grown
        fleet bit-equal to a clean single-device reference."""
        import jax

        agg = make_agg(depth=2, repromote_after=2)
        plan = FaultPlan([FaultSpec(site="device.oom_on_grow", count=1)])
        with fault.installed(plan):
            run_windows(agg, 3, n_nodes=5, w=4)
            published = run_windows(agg, 6, start=3, n_nodes=5, w=12)
            tail = agg.windows.drain()
        assert plan.fired("device.oom_on_grow") == 1
        assert agg.windows._demotions_by_reason == {"oom_on_grow": 1}
        assert published[0] is not None  # the growth window published
        assert agg.windows._rung == RUNG_PIPELINED  # recovered to sharded

        ref = make_agg(depth=1)
        ref.windows.mesh = make_mesh([1], devices=jax.devices()[:1])
        ref_published = run_windows(ref, 3, n_nodes=5, w=4)
        ref_published += run_windows(ref, 6, start=3, n_nodes=5, w=12)
        ref.shutdown()
        ref_by_ts = {r.timestamp: r for r in ref_published if r is not None}
        final = [r for r in published if r is not None][-2:]
        if tail is not None:
            final.append(tail)
        for result in final:
            assert_windows_equal(result, ref_by_ts[result.timestamp])
        agg.shutdown()


class TestMultiHostChaos:
    """Host death on the multi-host tier (ISSUE 15): a 2-host virtual
    dryrun — one host's fabric presence is killed mid-run (the
    in-process stand-in for SIGKILLing a worker; the real two-process
    leg lives in ``make multihost`` and skips where jax lacks the Gloo
    CPU backend) — and the survivor must

    * demote to the "mesh minus one host" rung within ONE window and
      keep publishing every interval,
    * bump the ring membership epoch so displaced agents follow 421s,
    * absorb the displaced agents' replay with ZERO windows counted
      lost (the acked_through watermark seeds their seq trackers), and
    * publish windows bit-equal to a fault-free single-host reference
      after recovery.
    """

    PEERS = ["127.0.0.1:28291", "127.0.0.1:28292"]

    @staticmethod
    def _topology():
        import jax

        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs >= 4 simulated devices")
        per = len(devs) // 2
        mesh_devs = devs[:2 * per]
        proc_of = {d: (0 if k < per else 1)
                   for k, d in enumerate(mesh_devs)}
        return mesh_devs, proc_of.get

    def _make_agg(self, process_index: int, fabric, device_process,
                  **kw):
        ticks = [1e9]
        agg = Aggregator(
            APIServer(), model_mode="mlp", node_bucket=8,
            workload_bucket=8, stale_after=1e9, pipeline_depth=1,
            multihost_enabled=True,
            multihost_topology={"process_index": process_index,
                                "device_process": device_process,
                                "fabric": fabric},
            peers=list(self.PEERS),
            self_peer=self.PEERS[process_index],
            clock=lambda: ticks[0], **kw)
        agg.test_clock = ticks
        agg.init()
        return agg

    @staticmethod
    def _seed(agg, names, win):
        now = agg.test_clock[0]
        for i, name in enumerate(names):
            rep = make_report(name, win * 100 + i, w=4,
                              mode=MODE_MODEL if i % 2 else MODE_RATIO)
            agg._reports[name] = _Stored(report=rep, zone_names=ZONES,
                                         received=now, seq=win + 1,
                                         run="r1")

    def test_host_death_demotes_within_one_window_zero_loss(self):
        import threading

        from kepler_tpu.fleet import wire
        from kepler_tpu.fleet.scheduler import (RUNG_NAME_MESH_DEGRADED,
                                                RUNG_NAME_MULTIHOST)
        from kepler_tpu.fleet.ring import MeshRing
        from kepler_tpu.fleet.window import HostLocalFabric

        mesh_devs, device_process = self._topology()
        fabric = HostLocalFabric(2, timeout=60)
        aggs = [self._make_agg(p, fabric, device_process)
                for p in (0, 1)]
        assert isinstance(aggs[0]._ring, MeshRing)
        ring = aggs[0]._ring
        all_names = [f"n{i:02d}" for i in range(10)]
        owned = {p: [n for n in all_names
                     if ring.owner(n) == self.PEERS[p]] for p in (0, 1)}
        assert owned[0] and owned[1], owned  # both hosts host agents

        # -- healthy multi-host windows on both virtual hosts ----------
        def window_on_both(win):
            published = [None, None]
            errs = [None, None]

            def run(p):
                try:
                    aggs[p].test_clock[0] += 5.0
                    self._seed(aggs[p], owned[p], win)
                    published[p] = aggs[p].aggregate_once()
                except BaseException as e:
                    errs[p] = e

            ts = [threading.Thread(target=run, args=(p,))
                  for p in (0, 1)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
            for e in errs:
                if e is not None:
                    raise e
            return published

        for win in range(3):
            published = window_on_both(win)
            for p in (0, 1):
                assert published[p] is not None
                assert sorted(published[p].names) == sorted(owned[p])
        assert aggs[0].windows._rung_display(RUNG_PIPELINED) == \
            RUNG_NAME_MULTIHOST
        epoch_before = aggs[0]._ring.epoch

        # -- SIGKILL host 1 (fabric presence dies mid-run) -------------
        fabric.kill()
        survivor = aggs[0]
        survivor.test_clock[0] += 5.0
        self._seed(survivor, owned[0], 3)
        result = survivor.aggregate_once()

        # demoted to "mesh minus one host" within ONE window — the
        # interval still published, on the survivor's own devices
        assert result is not None
        assert sorted(result.names) == sorted(owned[0])
        assert survivor.windows._mesh_degraded is True
        assert survivor.windows._rung == RUNG_PIPELINED
        assert survivor.windows._stats["window_demotions_total"] == 1
        assert survivor.windows._rung_display(RUNG_PIPELINED) == \
            RUNG_NAME_MESH_DEGRADED
        # ring epoch bumped: displaced agents follow 421s to the
        # survivor (takeover ring owns everything here)
        assert survivor._ring.epoch == epoch_before + 1
        assert survivor._ring.owner(owned[1][0]) == self.PEERS[0]

        # -- displaced agents replay to the new owner ------------------
        # each displaced node re-delivers its next window with the
        # acked_through watermark covering everything the dead owner
        # 2xx'd — the fresh seq tracker seeds from it: ZERO loss
        now = survivor.test_clock[0]
        for i, name in enumerate(owned[1]):
            rep = make_report(name, 3 * 100 + 50 + i, w=4,
                              mode=MODE_MODEL if i % 2 else MODE_RATIO)
            data = wire.encode_report(rep, list(ZONES), seq=4, run="r1",
                                      sent_at=now)
            data = wire.restamp_transmit(data, sent_at=now,
                                         acked_through=3)
            status, _, body = survivor._ingest_payload(data)
            assert status == 204, (status, body)
        assert survivor._stats["windows_lost_total"] == 0
        assert survivor._stats["reports_total"] >= len(owned[1])

        # -- recovered window: full fleet on the survivor, bit-equal
        # to a fault-free single-host reference --------------------------
        survivor.test_clock[0] += 5.0
        self._seed(survivor, owned[0], 4)
        for i, name in enumerate(owned[1]):
            rep = make_report(name, 4 * 100 + 50 + i, w=4,
                              mode=MODE_MODEL if i % 2 else MODE_RATIO)
            survivor._reports[name] = _Stored(
                report=rep, zone_names=ZONES,
                received=survivor.test_clock[0], seq=5, run="r1")
        recovered = survivor.aggregate_once()
        assert recovered is not None
        assert sorted(recovered.names) == sorted(all_names)
        assert survivor._stats["windows_lost_total"] == 0

        ref = make_agg(depth=1)
        ref.test_clock[0] = survivor.test_clock[0] - 5.0
        self._seed(ref, owned[0], 4)
        for i, name in enumerate(owned[1]):
            rep = make_report(name, 4 * 100 + 50 + i, w=4,
                              mode=MODE_MODEL if i % 2 else MODE_RATIO)
            ref._reports[name] = _Stored(
                report=rep, zone_names=ZONES,
                received=ref.test_clock[0], seq=5, run="r1")
        ref.test_clock[0] += 5.0
        reference = ref.aggregate_once()
        assert_windows_equal(recovered, reference)
        ref.shutdown()
        survivor.shutdown()
        aggs[1].shutdown()

    def test_dead_host_rejoins_takes_shards_back_bit_equal(self):
        """The elastic rejoin leg (ISSUE 16): after a host death and
        succession, the dead host COMES BACK — a fresh process under a
        NEW fabric incarnation registers with the lease holder over
        ``/v1/membership``. It re-elects no one (the incumbent lease
        survives), the multi-host tier is restored, the rejoiner owns
        ring shards again, and the recovered multi-host window is
        bit-equal to a fault-free single-host reference. Zero windows
        lost across the whole death/rejoin cycle."""
        import json as _json
        import threading

        from kepler_tpu.fleet.scheduler import (
            RUNG_NAME_MESH_DEGRADED, RUNG_NAME_MULTIHOST)
        from kepler_tpu.fleet.ring import MeshRing
        from kepler_tpu.fleet.window import HostLocalFabric

        mesh_devs, device_process = self._topology()
        alive = set(self.PEERS)
        aggs: dict[str, Aggregator] = {}

        class Req:
            command = "POST"

            def __init__(self, body):
                self.body = body

        def make(p, fabric):
            def deliver(target, payload):
                if target not in alive:
                    raise OSError("connection refused")
                status, _, body = aggs[target]._handle_membership(
                    Req(_json.dumps(payload).encode()))
                return _json.loads(body)

            return self._make_agg(
                p, fabric, device_process,
                membership_topology={
                    "peer_alive": lambda q: q in alive,
                    "deliver": deliver})

        fabric1 = HostLocalFabric(2, timeout=60)
        aggs[self.PEERS[0]] = make(0, fabric1)
        aggs[self.PEERS[1]] = make(1, fabric1)
        all_names = [f"n{i:02d}" for i in range(10)]

        def owned_by(ring):
            return {p: [n for n in all_names
                        if ring.owner(n) == self.PEERS[p]]
                    for p in (0, 1)}

        def window_on_both(win, owned):
            published = {0: None, 1: None}
            errs = {0: None, 1: None}

            def run(p):
                try:
                    agg = aggs[self.PEERS[p]]
                    agg.test_clock[0] += 5.0
                    self._seed(agg, owned[p], win)
                    published[p] = agg.aggregate_once()
                except BaseException as e:
                    errs[p] = e

            ts = [threading.Thread(target=run, args=(p,))
                  for p in (0, 1)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
            for e in errs.values():
                if e is not None:
                    raise e
            return published

        # -- one healthy multi-host window ------------------------------
        owned = owned_by(aggs[self.PEERS[0]]._ring)
        assert owned[0] and owned[1], owned
        published = window_on_both(0, owned)
        for p in (0, 1):
            assert sorted(published[p].names) == sorted(owned[p])

        # -- host 1 dies: succession heals the ring ---------------------
        alive.discard(self.PEERS[1])
        fabric1.kill()
        dead = aggs.pop(self.PEERS[1])
        dead.shutdown()
        survivor = aggs[self.PEERS[0]]
        survivor.test_clock[0] += 5.0
        self._seed(survivor, owned[0], 1)
        result = survivor.aggregate_once()
        assert result is not None
        assert survivor._ring.epoch == 2
        assert survivor._membership_applied.get("succession") == 1
        assert survivor._lease.holder == self.PEERS[0]
        assert survivor.windows._rung_display(RUNG_PIPELINED) == \
            RUNG_NAME_MESH_DEGRADED
        assert survivor._ring.owner(owned[1][0]) == self.PEERS[0]

        # -- host 1 REJOINS under a fresh fabric incarnation ------------
        fabric2 = HostLocalFabric(2, timeout=60)
        survivor.arm_mesh(fabric2)
        alive.add(self.PEERS[1])
        rejoined = make(1, fabric2)
        aggs[self.PEERS[1]] = rejoined
        reply = rejoined.request_join(mesh=True)
        assert reply["ok"] is True

        # re-elects NO ONE: the incumbent lease survives the rejoin
        for agg in aggs.values():
            assert agg._lease.holder == self.PEERS[0]
            assert agg._ring.epoch == 3  # death bump + join bump
            assert isinstance(agg._ring, MeshRing)
            assert agg.windows._mesh_degraded is False
        assert "succession" not in rejoined._membership_applied

        # the rejoiner owns shards again, and both rings agree
        owned_after = owned_by(survivor._ring)
        assert owned_after[1], owned_after
        for name in all_names:
            assert survivor._ring.owner(name) == \
                rejoined._ring.owner(name)

        # -- recovered multi-host window on the restored tier -----------
        published = window_on_both(2, owned_after)
        for p in (0, 1):
            assert published[p] is not None
            assert sorted(published[p].names) == sorted(owned_after[p])
            assert aggs[self.PEERS[p]].windows._rung_display(RUNG_PIPELINED) \
                == RUNG_NAME_MULTIHOST
        assert survivor._stats["windows_lost_total"] == 0

        # bit-equal to a fault-free single-host reference over the
        # same fleet (window 2 reports for every node)
        ref = make_agg(depth=1)
        ref.test_clock[0] = survivor.test_clock[0] - 5.0
        for p in (0, 1):
            self._seed(ref, owned_after[p], 2)
        ref.test_clock[0] += 5.0
        reference = ref.aggregate_once()
        assert sorted(reference.names) == sorted(all_names)
        for p in (0, 1):
            win = published[p]
            for name in win.names:
                i, j = win.rows[name], reference.rows[name]
                np.testing.assert_array_equal(
                    win.node_power_uw[i], reference.node_power_uw[j])
                np.testing.assert_array_equal(
                    win.node_energy_uj[i], reference.node_energy_uj[j])
                np.testing.assert_array_equal(
                    win.wl_power_uw[i, :win.counts[i]],
                    reference.wl_power_uw[j, :reference.counts[j]])
        ref.shutdown()
        for agg in aggs.values():
            agg.shutdown()
