"""The seam between ``Aggregator`` and ``WindowScheduler``.

The scheduler (``fleet/scheduler.py``) is the window path below the
report store; the aggregator owns it as ``agg.windows`` and only calls
down. These tests hold the seam: the scheduler runs a window with no
aggregator, server or ring; it imports nothing from above; the two
arrows that cross it (``on_mesh_lost`` up, ``rebuild_engines`` down) fire
once each; and the surfaces that join the two halves' numbers keep every
key and family they had when one class printed them.
"""

from __future__ import annotations

import ast
import json
import os
import threading

import numpy as np
import pytest

from kepler_tpu.fleet import scheduler as scheduler_mod
from kepler_tpu.fleet.aggregator import Aggregator, _Stored
from kepler_tpu.fleet.scheduler import (RUNG_NAME_MESH_DEGRADED,
                                        RUNG_PIPELINED, WindowScheduler)
from kepler_tpu.fleet.window import DeviceWindowError
from kepler_tpu.monitor.history import HistoryBuffer
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO
from kepler_tpu.parallel.mesh import make_mesh
from kepler_tpu.server.http import APIServer
from tests.test_multihost_engine import PEERS, make_mh_aggregator
from tests.test_window_pipeline import ZONES, make_agg, make_report

# every key of the one `_stats` dict `Aggregator` had before the split,
# in its order (`/v1/results`' "stats" prints them so): the ingest half
# stays the aggregator's, the window half is the scheduler's
INGEST_STATS = [
    "reports_total", "rejected_total", "quarantined_total",
    "malformed_total", "clock_skew_total", "reports_redirected_total",
    "keyframe_requests_total", "duplicates_total", "windows_lost_total"]
WINDOW_STATS = [
    "attributions_total", "published_early_total", "last_batch_nodes",
    "last_batch_workloads", "last_attribution_ms", "last_assembly_ms",
    "last_device_ms", "last_scatter_ms", "last_dispatch_ms",
    "last_wait_ms", "last_fetch_ms", "last_sync_per_window_ms",
    "last_h2d_rows", "last_h2d_device_bytes", "window_shards",
    "last_h2d_shards", "shard_skew", "window_compiles_total",
    "window_rung", "window_demotions_total", "window_repromotions_total"]
# `/debug/window`'s "stats" and top-level keys, as the parent tree's
DEBUG_STATS = [
    "last_assembly_ms", "last_dispatch_ms", "last_wait_ms",
    "last_fetch_ms", "last_sync_per_window_ms", "last_scatter_ms",
    "last_attribution_ms", "last_h2d_rows", "last_h2d_device_bytes",
    "last_h2d_shards", "window_shards", "shard_skew",
    "window_compiles_total", "window_rung", "window_demotions_total",
    "window_repromotions_total", "last_batch_nodes",
    "last_batch_workloads"]
DEBUG_KEYS = [
    "platform", "device_kind", "devices", "rung", "rung_name", "shards",
    "windows_at_rung", "windows_since_last_failure", "fallback_enabled",
    "probe_backoff", "timeline", "demotions_by_reason", "engines",
    "stats", "counts", "ingest", "records"]
# every family of the parent tree's `collect()`, in scrape order (ring
# and autoscale on, so that the conditional ones are there)
FAMILIES = [
    "kepler_fleet_journal_events", "kepler_fleet_hlc_drift_seconds",
    "kepler_fleet_hlc_clamped", "kepler_fleet_nodes",
    "kepler_fleet_workloads", "kepler_fleet_attribution_latency_ms",
    "kepler_fleet_window_leg_ms", "kepler_fleet_window_h2d_rows",
    "kepler_fleet_window_h2d_device_bytes", "kepler_fleet_window_fetch_ms",
    "kepler_fleet_window_sync_per_window_ms", "kepler_fleet_window_shards",
    "kepler_fleet_window_shard_skew_ratio",
    "kepler_fleet_window_shard_rows", "kepler_fleet_window_shard_h2d_rows",
    "kepler_fleet_window_buffer_staleness_windows",
    "kepler_fleet_window_program_flops",
    "kepler_fleet_window_program_bytes",
    "kepler_fleet_window_program_device_memory_bytes",
    "kepler_fleet_window_compiles", "kepler_fleet_window_degraded",
    "kepler_fleet_window_demotions", "kepler_fleet_window_repromotions",
    "kepler_fleet_attributions", "kepler_fleet_windows_published_early",
    "kepler_fleet_reports", "kepler_fleet_reports_rejected",
    "kepler_fleet_reports_quarantined", "kepler_fleet_reports_duplicate",
    "kepler_fleet_reports_redirected",
    "kepler_fleet_reports_keyframe_requests", "kepler_fleet_ingest_bytes",
    "kepler_fleet_wire_version", "kepler_fleet_reports_shed",
    "kepler_fleet_ingest_inflight", "kepler_fleet_ingest_latency_seconds",
    "kepler_fleet_ring_epoch", "kepler_fleet_ring_ownership_ratio",
    "kepler_fleet_ring_peers", "kepler_fleet_membership_rejected",
    "kepler_fleet_membership_applied",
    "kepler_fleet_membership_awaiting_state",
    "kepler_fleet_autoscale_recommended_replicas",
    "kepler_fleet_autoscale_decisions",
    "kepler_fleet_delivery_latency_seconds", "kepler_fleet_windows_lost",
    "kepler_fleet_degraded_nodes", "kepler_fleet_node_state",
    "kepler_fleet_scoreboard_nodes", "kepler_fleet_node_cpu_watts",
    "kepler_fleet_node_cpu_joules"]

# what the scheduler may not import: everything above it
ABOVE = ("aggregator", "membership", "ring", "admission", "scoreboard",
         "delivery")


class _Request:
    command = "GET"

    def __init__(self, path: str) -> None:
        self.path = path
        self.body = b""


def _stored(n_nodes: int, mode_of, now: float) -> list[_Stored]:
    return [_Stored(report=make_report(f"n{i:02d}", 100 + i,
                                       mode=mode_of(i)),
                    zone_names=tuple(ZONES), received=now, seq=1, run="r1")
            for i in range(n_nodes)]


def _planes(results) -> dict:
    """Every array and list a published window holds."""
    return {
        "timestamp": results.timestamp, "zones": list(results.zones),
        "names": list(results.names), "rows": dict(results.rows),
        "counts": [int(c) for c in results.counts],
        "ids": [list(w) for w in results.workload_ids],
        "mode": np.asarray(results.mode),
        "node_power_uw": np.asarray(results.node_power_uw),
        "node_energy_uj": np.asarray(results.node_energy_uj),
        "node_joules_total": np.asarray(results.node_joules_total),
        "wl_power_uw": np.asarray(results.wl_power_uw),
        "wl_energy_uj": np.asarray(results.wl_energy_uj),
    }


def _assert_bit_equal(a, b) -> None:
    pa, pb = _planes(a), _planes(b)
    assert pa.keys() == pb.keys()
    for key, va in pa.items():
        if isinstance(va, np.ndarray):
            assert va.dtype == pb[key].dtype, key
            assert va.tobytes() == pb[key].tobytes(), key
        else:
            assert va == pb[key], key


class TestSchedulerAlone:
    """(a) one window with no aggregator, no server, no ring."""

    @pytest.mark.parametrize("model_mode", [None, "temporal"],
                             ids=["ratio", "temporal"])
    def test_publishes_what_the_aggregator_publishes(self, model_mode):
        now = 1e9
        mode_of = ((lambda i: MODE_RATIO) if model_mode is None
                   else (lambda i: MODE_MODEL if i % 2 else MODE_RATIO))
        stored = _stored(5, mode_of, now)
        kw = dict(model_mode=model_mode, node_bucket=8, workload_bucket=8,
                  history_window=4)

        agg = make_agg(1, clock=lambda: now, **kw)
        agg.init()
        for s in stored:
            agg._reports[s.report.node_name] = s
            if model_mode == "temporal" and s.report.mode == MODE_MODEL:
                for _ in range(3):
                    agg._push_history(s.report)
        via_aggregator = agg.aggregate_once()
        agg.shutdown()

        # the store's half of the temporal seam, without the store: the
        # same pushes into the same buffers, read the same way
        buffers = {}

        def history_windows(batch):
            from kepler_tpu.models.features import NUM_FEATURES
            from kepler_tpu.resource.informer import FeatureBatch

            n, w = batch.cpu_deltas.shape
            hist = np.zeros((n, w, 4, NUM_FEATURES), np.float32)
            valid = np.zeros((n, w, 4), bool)
            for i in range(batch.n_nodes):
                rep = by_name[batch.node_names[i]]
                if rep.mode != MODE_MODEL:
                    continue
                buf = buffers.setdefault(rep.node_name,
                                         HistoryBuffer(window=4))
                if not buf.window_arrays(rep.workload_ids)[1].any():
                    for _ in range(3):
                        buf.push(FeatureBatch(
                            kinds=rep.workload_kinds,
                            ids=list(rep.workload_ids),
                            cpu_deltas=np.asarray(rep.cpu_deltas,
                                                  np.float32),
                            node_cpu_delta=float(rep.node_cpu_delta),
                            usage_ratio=float(rep.usage_ratio)),
                            dt_s=float(rep.dt_s))
                k = len(rep.workload_ids)
                buf.window_arrays(rep.workload_ids,
                                  out=(hist[i, :k], valid[i, :k]))
            return hist, valid

        by_name = {s.report.node_name: s.report for s in stored}
        windows = WindowScheduler(history_windows=history_windows,
                                  clock=lambda: now, mesh=make_mesh(),
                                  **kw)
        windows.init()
        rec = windows.new_record(now, 0.0)
        alone = windows.step(
            sorted(stored, key=lambda s: s.report.node_name),
            sorted(ZONES), now, rec)
        windows.shutdown()

        assert via_aggregator is not None and alone is not None
        _assert_bit_equal(alone, via_aggregator)
        assert windows.stats()["attributions_total"] == 1
        assert windows.results() is alone
        assert rec.seq == 0 and rec.published is not None

    def test_empty_scheduler_reads_clean(self):
        windows = WindowScheduler(model_mode=None)
        assert windows.results() is None
        assert windows.last_window_at() is None
        assert windows.drain() is None
        assert windows.health()["ok"] is True
        payload, records = windows.debug()
        assert records == [] and payload["engines"] == {}
        assert windows.rung_timeline() == (RUNG_PIPELINED, [])
        windows.shutdown()


class TestArrowsPointOneWay:
    def test_scheduler_imports_nothing_from_above(self):
        """(b) no import of the aggregator, membership, ring, admission,
        scoreboard, delivery or the server, at any depth of the file."""
        path = os.path.abspath(scheduler_mod.__file__)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        imported: list[str] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                imported += [f"{node.module}.{a.name}" for a in node.names]
        assert imported, "the walk found no import at all"
        for name in imported:
            parts = name.split(".")
            assert "server" not in parts, name
            if "fleet" in parts:
                assert not set(parts) & set(ABOVE), name

    def test_aggregator_forwards_nothing(self):
        """No ``__getattr__`` and no property on ``Aggregator``: window
        state is reached through ``agg.windows`` alone."""
        assert "__getattr__" not in vars(Aggregator)
        assert not [k for k, v in vars(Aggregator).items()
                    if isinstance(v, property)]
        agg = Aggregator(APIServer(), model_mode=None)
        assert isinstance(agg.windows, WindowScheduler)
        for moved in ("_rung", "_engine", "_inflight", "_results",
                      "_pipeline_lock", "_results_lock"):
            assert not hasattr(agg, moved), moved


class TestSeamCalls:
    """(c) the two arrows across the seam, once each."""

    def test_mesh_demotion_tells_membership_once_after_the_drop(self):
        agg = make_mh_aggregator(0)
        try:
            windows = agg.windows
            windows._packed_engine(RUNG_PIPELINED)
            assert windows._engine is not None
            seen = []

            def on_mesh_lost(reason):
                # the scheduler's own side is done by now
                seen.append((reason, windows._engine,
                             windows._engine_serial,
                             windows._mesh_elastic,
                             windows._mesh_degraded))

            windows._on_mesh_lost = on_mesh_lost
            windows._handle_device_failure(
                DeviceWindowError("host_dead", "peer lost"))
            assert seen == [("host_dead", None, None, None, True)]
            assert windows._rung == RUNG_PIPELINED
            assert windows._rung_display(RUNG_PIPELINED) == \
                RUNG_NAME_MESH_DEGRADED
            # the next failure is a local device's: the ordinary ladder,
            # and no second word to membership
            windows._handle_device_failure(
                DeviceWindowError("dispatch_error", "boom"))
            assert len(seen) == 1
            assert windows._rung == RUNG_PIPELINED + 1
        finally:
            agg.shutdown()

    def test_membership_apply_rebuilds_engines_once(self):
        agg = make_mh_aggregator(0)
        try:
            windows = agg.windows
            windows._packed_engine(RUNG_PIPELINED)
            calls = []
            real = windows.rebuild_engines

            def rebuild_engines(**kw):
                calls.append(kw)
                real(**kw)

            windows.rebuild_engines = rebuild_engines
            # a plain (non-mesh) membership: the mesh no longer describes
            # ownership
            agg.apply_membership(PEERS[:1], agg._ring.epoch + 1)
            assert calls == [{}]
            assert windows._engine is None
            assert windows._engine_serial is None
            assert windows._mesh_degraded is True
            # the mesh-path restore over both processes
            agg.apply_membership(PEERS, agg._ring.epoch + 1, mesh=True)
            assert len(calls) == 2
            assert calls[1]["mesh"] is windows._mesh_elastic
            assert calls[1]["mesh"] is not None
            assert windows._mesh_degraded is False
            # a replay of the same membership changes nothing
            assert agg.apply_membership(PEERS, agg._ring.epoch) == 0
            assert len(calls) == 2
        finally:
            agg.shutdown()

    def test_single_host_membership_leaves_engines_alone(self):
        agg = Aggregator(APIServer(), model_mode=None,
                         peers=["a:1", "b:2"], self_peer="a:1")
        agg.windows.rebuild_engines = lambda **kw: pytest.fail(
            "no multi-host tier: nothing to rebuild")
        agg.apply_membership(["a:1"], 2)
        assert agg._ring.epoch == 2


class TestJoinedSurfaces:
    """(d) every key and family of the one-class days, in their order."""

    @pytest.fixture()
    def agg(self):
        agg = make_agg(1, model_mode=None, peers=["a:1", "b:2"],
                       self_peer="a:1", membership_autoscale=True)
        agg.init()
        now = agg.test_clock[0]
        for s in _stored(3, lambda i: MODE_RATIO, now):
            agg._reports[s.report.node_name] = s
        assert agg.aggregate_once() is not None
        yield agg
        agg.shutdown()

    def test_stats_split_and_join(self, agg):
        assert list(agg._stats) == INGEST_STATS
        assert list(agg.windows.stats()) == WINDOW_STATS
        _, _, body = agg._handle_results(_Request("/v1/results"))
        assert list(json.loads(body)["stats"]) == INGEST_STATS + WINDOW_STATS
        scalar = [k for k in INGEST_STATS + WINDOW_STATS
                  if k != "last_h2d_shards"]
        assert list(agg.bundle()["stats"]) == sorted(scalar)

    def test_debug_window_body(self, agg):
        _, _, body = agg._handle_window_debug(_Request("/debug/window"))
        payload = json.loads(body)
        assert list(payload) == DEBUG_KEYS
        assert list(payload["stats"]) == DEBUG_STATS
        assert payload["counts"]["windows"] == 1
        assert len(payload["records"]["rows"]) == 1
        assert set(payload["ingest"]) == {
            "reports", "decode_s", "lock_wait_s", "merge_s",
            "history_push_s"}

    def test_collect_families_in_scrape_order(self, agg):
        assert [f.name for f in agg.collect()] == FAMILIES
        window = [f.name for f in agg.windows.collect()]
        nodes = [f.name for f in agg.windows.collect_nodes()]
        assert window == FAMILIES[3:25]
        assert nodes == FAMILIES[-2:]

    def test_probe_joins_membership_lines(self):
        agg = make_mh_aggregator(1)
        try:
            with agg._lock:
                agg._awaiting_membership = True
            probe = agg.window_health()
            assert list(probe["multihost"]) == [
                "active", "mesh_degraded", "init_joined", "init_reason",
                "awaiting_membership", "lease_holder", "lease_epoch",
                "detail"]
            assert probe["ok"] is False
            assert probe["multihost"]["lease_holder"] == PEERS[0]
            assert probe["multihost"]["detail"] == \
                "degraded, awaiting membership"
            assert "lease_holder" not in agg.windows.health()["multihost"]
        finally:
            agg.shutdown()


def test_publisher_thread_keeps_its_name():
    """The served loop's publisher is still ``kepler-window-publish``,
    started and stopped by the scheduler."""
    windows = WindowScheduler(model_mode=None)
    windows.start()
    try:
        assert "kepler-window-publish" in [
            t.name for t in threading.enumerate()]
    finally:
        windows.stop()
    assert "kepler-window-publish" not in [
        t.name for t in threading.enumerate()]
