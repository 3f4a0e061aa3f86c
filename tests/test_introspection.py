"""Fleet flight recorder (ISSUE 8): device-plane cost introspection,
shard-skew metrics, rung timeline, and the per-node fleet scoreboard.

Contracts:

* `/debug/window` and `/debug/fleet` serve schema-valid JSON on a LIVE
  aggregator (over real HTTP), and cost gauges appear after the first
  cold compile;
* stage-label cardinality is independent of mesh size (per-shard span
  names observe one shared histogram stage);
* the rung timeline records demotions and re-promotions, bounded;
* the scoreboard state machine walks healthy → stale / lossy /
  anomalous / quarantined and back, LRU-capped;
* telemetry + fleet families render byte-identically on both
  exposition fast paths under the ShardedWindowEngine, and a Chrome
  trace from a sharded pipelined run still validates.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

from kepler_tpu import telemetry
from kepler_tpu.fleet.aggregator import Aggregator
from kepler_tpu.fleet.scheduler import RUNG_NUMPY, RUNG_PIPELINED
from kepler_tpu.fleet.scoreboard import (STATE_ANOMALOUS, STATE_HEALTHY,
                                         STATE_LOSSY, STATE_NAMES,
                                         STATE_QUARANTINED, STATE_STALE,
                                         FleetScoreboard)
from kepler_tpu.fleet.window import DeviceWindowError, PackedWindowEngine
from kepler_tpu.fleet.wire import encode_report
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext
from tests.test_window_pipeline import (churn_schedule, make_agg,
                                        run_schedule)

WINDOW_REQUIRED = {"rung", "rung_name", "shards", "timeline",
                   "windows_at_rung", "windows_since_last_failure",
                   "demotions_by_reason", "engines", "stats"}
ENGINE_REQUIRED = {"engine", "n_shards", "window_seq", "buckets",
                   "resident", "shards", "programs", "updates",
                   "compile_count"}
FLEET_REQUIRED = {"cap", "anomaly_z", "flag_ttl_s", "stale_after_s",
                  "states", "nodes"}


class _Req:
    def __init__(self, path="/", command="GET", body=b""):
        self.path = path
        self.command = command
        self.body = body


def window_payload(agg) -> dict:
    status, headers, body = agg._handle_window_debug(_Req("/debug/window"))
    assert status == 200
    assert headers["Content-Type"] == "application/json"
    return json.loads(body)


def fleet_payload(agg) -> dict:
    status, headers, body = agg._handle_fleet_debug(_Req("/debug/fleet"))
    assert status == 200
    return json.loads(body)


def families(agg) -> dict:
    return {f.name: f for f in agg.collect()}


class TestDebugWindow:
    def test_schema_and_cost_after_cold_compile(self):
        import jax

        agg = make_agg(2)
        run_schedule(agg, churn_schedule(3))
        payload = window_payload(agg)
        assert WINDOW_REQUIRED <= set(payload)
        assert payload["rung"] == RUNG_PIPELINED
        engines = payload["engines"]
        assert "pipelined" in engines
        for engine in engines.values():
            assert ENGINE_REQUIRED <= set(engine)
        eng = engines["pipelined"]
        assert eng["n_shards"] == len(jax.devices())
        assert len(eng["shards"]) == eng["n_shards"]
        assert sum(s["rows"] for s in eng["shards"]) == \
            eng["resident"]["rows"]
        assert len(payload["stats"]["last_h2d_shards"]) == eng["n_shards"]
        # cost stats captured on the cold compile: the attribution
        # program reports non-zero FLOPs, updates report cost too
        progs = {p["key"]: p for p in eng["programs"]}
        assert progs, "no cached programs after three windows"
        costed = [p for p in progs.values() if p["cost"]]
        assert costed, "cost stats missing from every compile-cache entry"
        assert any(p["cost"].get("flops", 0) > 0 for p in costed)
        # staleness: one entry per ring slot (depth+1), current slot 0
        staleness = eng["resident"]["staleness_windows"]
        assert len(staleness) == 3  # pipeline_depth 2 → 3 ring slots
        assert min(staleness) == 0
        # json round-trips (the endpoint contract — no numpy leaks)
        json.dumps(payload)
        agg.shutdown()

    def test_names_the_device_it_serves_from(self, caplog):
        """platform / device_kind / devices: read once from the engine's
        mesh at init, served on /debug/window and written to the start-up
        log — what chip_smoke.py asserts on, and what tells an aggregator
        on the chip from one quietly serving off the CPU."""
        import logging

        import jax

        first = jax.devices()[0]
        agg = make_agg(2)
        with caplog.at_level(logging.INFO, "kepler.fleet.aggregator"):
            agg.init()
        payload = window_payload(agg)  # before any window: already there
        assert payload["platform"] == first.platform == "cpu"
        assert payload["device_kind"] == first.device_kind
        assert payload["devices"] == len(jax.devices())
        line = next(r.getMessage() for r in caplog.records
                    if r.getMessage().startswith("aggregator:"))
        assert f"platform={first.platform}" in line
        assert f"device_kind={first.device_kind}" in line
        assert f"devices={len(jax.devices())}" in line
        agg.shutdown()

    def test_endpoints_valid_before_first_window(self):
        agg = Aggregator(APIServer(), model_mode=None)
        payload = window_payload(agg)
        assert WINDOW_REQUIRED <= set(payload)
        assert payload["engines"] == {}
        fleet = fleet_payload(agg)
        assert FLEET_REQUIRED <= set(fleet)
        assert fleet["nodes"] == {}
        assert set(fleet["states"]) == set(STATE_NAMES)

    def test_collect_families_cost_skew_staleness(self):
        import jax

        n_dev = len(jax.devices())
        agg = make_agg(2)
        run_schedule(agg, churn_schedule(3))
        fams = families(agg)
        flops = fams["kepler_fleet_window_program_flops"]
        assert flops.samples, "cost gauges absent after cold compiles"
        assert all(s.value >= 0 for s in flops.samples)
        assert {s.labels["program"] for s in flops.samples} == \
            {s.labels["program"]
             for s in fams["kepler_fleet_window_program_bytes"].samples}
        skew = fams["kepler_fleet_window_shard_skew_ratio"].samples
        assert len(skew) == 1 and skew[0].value >= 1.0
        rows = fams["kepler_fleet_window_shard_rows"].samples
        # exactly 2 series per shard (ratio/model split): bounded by the
        # mesh, not the fleet
        assert len(rows) == 2 * n_dev
        h2d = fams["kepler_fleet_window_shard_h2d_rows"].samples
        assert len(h2d) == n_dev
        staleness = fams[
            "kepler_fleet_window_buffer_staleness_windows"].samples
        assert len(staleness) == 3
        assert {s.labels["slot"] for s in staleness} == {"0", "1", "2"}
        agg.shutdown()

    def test_served_over_live_http(self):
        """Acceptance pin: both endpoints schema-valid on a live
        aggregator reached over real HTTP, after real wire ingest."""
        from tests.test_fleet import make_report

        server = APIServer(listen_addresses=["127.0.0.1:0"])
        agg = Aggregator(server, model_mode="mlp", node_bucket=8,
                         workload_bucket=16, stale_after=1e9)
        agg.init()
        server.init()
        ctx = CancelContext()
        threading.Thread(target=server.run, args=(ctx,),
                         daemon=True).start()
        host, port = server.addresses[0]
        base = f"http://{host}:{port}"
        try:
            for seed, name in enumerate(("node-a", "node-b")):
                req = urllib.request.Request(
                    f"{base}/v1/report",
                    data=encode_report(make_report(name, seed=seed),
                                       ["package", "dram"], seq=1,
                                       run="r1"),
                    method="POST")
                with urllib.request.urlopen(req, timeout=5) as resp:
                    assert resp.status == 204
            assert agg.aggregate_once() is not None
            with urllib.request.urlopen(f"{base}/debug/window",
                                        timeout=5) as resp:
                window = json.loads(resp.read())
            assert WINDOW_REQUIRED <= set(window)
            assert window["engines"]
            programs = next(iter(window["engines"].values()))["programs"]
            assert any(p.get("cost") for p in programs)
            with urllib.request.urlopen(f"{base}/debug/fleet",
                                        timeout=5) as resp:
                fleet = json.loads(resp.read())
            assert FLEET_REQUIRED <= set(fleet)
            assert set(fleet["nodes"]) == {"node-a", "node-b"}
            assert all(row["state"] == "healthy"
                       for row in fleet["nodes"].values())
        finally:
            ctx.cancel()
            agg.shutdown()
            server.shutdown()

    def test_debug_index_links_introspection_surfaces(self):
        from kepler_tpu.server.debug import DebugService

        svc = DebugService(APIServer(listen_addresses=["127.0.0.1:0"]))
        status, _, body = svc._handle(_Req("/debug/pprof/"))
        assert status == 200
        for link in (b"/debug/traces", b"/debug/window", b"/debug/fleet"):
            assert link in body


class TestProgramLabels:
    def test_sharded_labels_distinct_from_serial(self):
        """After a demotion both engines hold cost stats; on a
        multi-device mesh the sharded rung-0 program and the serial
        demotion program can reach the same bucket key for different
        executables — the shard suffix keeps their labels (and so the
        cost gauges) distinct."""
        eng = PackedWindowEngine.__new__(PackedWindowEngine)
        key = (8, 256, 2, "", None)
        assert eng._program_label(key) == "prog_n8_w256_z2_ratio"
        assert eng._update_label((4, 264, 8)) == "upd_n4_x264_d8"
        eng.n_shards = 8
        assert eng._program_label(key) == "prog_n8_w256_z2_ratio_s8"
        assert eng._update_label((4, 264, 8)) == "upd_n4_x264_d8_s8"


class TestRungTimeline:
    def test_demotion_records_transition(self):
        agg = make_agg(1)
        run_schedule(agg, churn_schedule(1))
        agg.windows._handle_device_failure(
            DeviceWindowError("dispatch_error", "injected"))
        probe = agg.window_health()
        assert probe["timeline_len"] == 1
        entry = probe["timeline"][-1]
        assert entry["rung"] == 1
        assert entry["from_rung"] == 0
        assert entry["reason"] == "dispatch_error"
        assert entry["windows_at_prev_rung"] == 1  # one published window
        assert entry["wall_time"] > 0 and entry["monotonic_s"] > 0
        payload = window_payload(agg)
        assert payload["timeline"] == probe["timeline"]
        assert payload["windows_at_rung"] == 0  # reset at the transition
        agg.shutdown()

    def test_repromotion_records_transition(self):
        agg = make_agg(1, repromote_after=2)
        schedules = churn_schedule(4)
        run_schedule(agg, schedules[:1])
        agg.windows._handle_device_failure(
            DeviceWindowError("compile_error", "injected"))
        published = run_schedule(agg, schedules[1:])
        assert published  # demoted rung still publishes
        probe = agg.window_health()
        assert probe["rung"] == RUNG_PIPELINED  # walked back up
        reasons = [e["reason"] for e in probe["timeline"]]
        assert reasons == ["compile_error", "repromoted"]
        promo = probe["timeline"][-1]
        assert promo["rung"] == 0 and promo["from_rung"] == 1
        assert promo["windows_at_prev_rung"] >= 2
        agg.shutdown()

    def test_demoted_rung_introspection_reads_active_engine(self):
        """At a demoted rung the shard/skew/staleness families must
        read the engine actually serving windows (the serial demotion
        engine), not the reset — empty — rung-0 sharded engine: the
        flight recorder must not go blank exactly while degraded."""
        agg = make_agg(1, repromote_after=100)  # stay demoted
        schedules = churn_schedule(3)
        run_schedule(agg, schedules[:1])
        agg.windows._handle_device_failure(
            DeviceWindowError("dispatch_error", "injected"))
        run_schedule(agg, schedules[1:])
        assert agg.window_health()["rung"] == 1  # packed serial
        fams = families(agg)
        rows = fams["kepler_fleet_window_shard_rows"].samples
        assert sum(s.value for s in rows) > 0, \
            "shard occupancy blank at the demoted rung"
        skew = fams["kepler_fleet_window_shard_skew_ratio"].samples[0]
        assert skew.value >= 1.0
        staleness = fams[
            "kepler_fleet_window_buffer_staleness_windows"].samples
        assert staleness, "buffer staleness blank at the demoted rung"
        agg.shutdown()

    def test_timeline_ring_is_bounded(self):
        agg = make_agg(1)
        for _ in range(80):
            agg.windows._handle_device_failure(
                DeviceWindowError("stall", "injected"))
        assert agg.window_health()["timeline_len"] == 64
        assert agg.windows._rung == RUNG_NUMPY  # pinned at the bottom rung
        agg.shutdown()


class TestStageCardinality:
    """Satellite: `window.h2d_delta.s<k>` span names must not mint one
    stage series per shard — the histogram key is the shared stage."""

    def make_recorder(self):
        from kepler_tpu.telemetry.spans import SpanRecorder

        return SpanRecorder(enabled=True)

    def test_stage_key_overrides_histogram_series(self):
        rec = self.make_recorder()
        with rec.span("aggregator.window"):
            for k in range(8):
                with rec.span(f"window.h2d_delta.s{k}",
                              stage="window.h2d_delta.shard"):
                    pass
        stages = rec.stats()["stages"]
        assert "window.h2d_delta.shard" in stages
        assert not [s for s in stages if s.startswith("window.h2d_delta.s")
                    and s != "window.h2d_delta.shard"]
        # all eight spans observed into the ONE stage histogram
        with rec._lock:
            assert rec._hist["window.h2d_delta.shard"].count == 8
        # the trace keeps the per-shard names for readability
        trace = rec.recent_traces()[-1]
        names = {e.name for e in trace.events}
        assert "window.h2d_delta.s7" in names

    def test_empty_stage_is_trace_only(self):
        rec = self.make_recorder()
        with rec.span("cycle"):
            with rec.span("noise.instance42", stage=""):
                pass
        stages = rec.stats()["stages"]
        assert "noise.instance42" not in stages
        assert "cycle" in stages
        names = {e.name for e in rec.recent_traces()[-1].events}
        assert "noise.instance42" in names

    def test_sharded_run_stage_labels_independent_of_mesh(self):
        """Pin: a pipelined run on the 8-device mesh produces NO
        per-shard stage series — the stage-label set would be identical
        on any mesh size."""
        from kepler_tpu.telemetry.spans import SpanRecorder

        rec = SpanRecorder(enabled=True)
        with telemetry.installed(rec):
            agg = make_agg(2)
            run_schedule(agg, churn_schedule(4))
            agg.shutdown()
        stages = rec.stats()["stages"]
        per_shard = [s for s in stages
                     if s.startswith("window.h2d_delta.s")
                     and s != "window.h2d_delta.shard"]
        assert per_shard == [], f"per-shard stage series minted: {per_shard}"
        # churn windows staged deltas, so the shared stage observed
        assert "window.h2d_delta.shard" in stages
        assert "window.h2d_delta" in stages  # the whole-window total


class TestShardedExposition:
    """Satellite: telemetry + fleet families under ShardedWindowEngine
    render on BOTH exposition fast paths, byte-identical to stock."""

    def run_sharded(self, rec):
        with telemetry.installed(rec):
            agg = make_agg(2)
            run_schedule(agg, churn_schedule(4))
            agg.shutdown()
        return agg

    def test_both_exposition_paths_byte_identical(self):
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest
        from prometheus_client.openmetrics.exposition import (
            generate_latest as om_latest,
        )

        from kepler_tpu.exporter.prometheus.fastexpo import (
            fast_generate_latest,
            fast_generate_openmetrics,
        )
        from kepler_tpu.telemetry.spans import SpanRecorder

        rec = SpanRecorder(enabled=True)
        agg = self.run_sharded(rec)
        registry = CollectorRegistry()
        registry.register(agg)
        with telemetry.installed(rec):
            registry.register(telemetry.collector())
            classic = fast_generate_latest(registry)
            assert classic == generate_latest(registry)
            assert fast_generate_openmetrics(registry) == \
                om_latest(registry)
        text = classic.decode()
        for needle in ("kepler_fleet_window_shard_skew_ratio",
                       "kepler_fleet_window_program_flops",
                       "kepler_fleet_window_shard_rows",
                       "kepler_fleet_window_buffer_staleness_windows",
                       "kepler_fleet_scoreboard_nodes",
                       'kepler_self_stage_duration_seconds_count{'
                       'stage="window.h2d_delta.shard"}'):
            assert needle in text, f"{needle} missing from exposition"

    def test_chrome_trace_from_sharded_run_validates(self):
        from kepler_tpu.telemetry.spans import SpanRecorder
        from tests.test_telemetry import TestChromeTrace

        rec = SpanRecorder(enabled=True)
        self.run_sharded(rec)
        payload = json.loads(json.dumps(rec.chrome_trace()))
        TestChromeTrace().validate_chrome_schema(payload)
        names = {e["name"] for e in payload["traceEvents"]
                 if e["ph"] == "X"}
        assert "aggregator.window" in names
        assert any(n.startswith("window.h2d_delta.s") for n in names)


class TestScoreboardUnit:
    def test_healthy_then_stale(self):
        sb = FleetScoreboard(flag_ttl=60.0)
        sb.observe_report("n1", 100.0, 50.0)
        assert sb.states(101.0, 15.0) == {"n1": STATE_HEALTHY}
        assert sb.states(200.0, 15.0) == {"n1": STATE_STALE}

    def test_quarantine_flag_decays(self):
        sb = FleetScoreboard(flag_ttl=60.0)
        sb.observe_report("n1", 100.0, 50.0)
        sb.observe_quarantine("n1", 100.0, "malformed")
        assert sb.states(110.0, 1e9) == {"n1": STATE_QUARANTINED}
        assert sb.states(200.0, 1e9) == {"n1": STATE_HEALTHY}
        row = sb.snapshot(110.0, 1e9)["nodes"]["n1"]
        assert row["quarantined"] == 1
        assert row["last_quarantine_reason"] == "malformed"

    def test_lossy_flag_decays(self):
        sb = FleetScoreboard(flag_ttl=60.0)
        sb.observe_report("n1", 100.0, 50.0, lost=3)
        assert sb.states(110.0, 1e9) == {"n1": STATE_LOSSY}
        sb.observe_report("n1", 170.0, 50.0)
        assert sb.states(170.0, 1e9) == {"n1": STATE_HEALTHY}
        assert sb.snapshot(170.0, 1e9)["nodes"]["n1"]["windows_lost"] == 3

    def test_anomaly_needs_baseline_then_flags_spike(self):
        sb = FleetScoreboard(anomaly_z=4.0, flag_ttl=60.0)
        rng = np.random.default_rng(0)
        t = 100.0
        # noisy-but-steady baseline: never flags, including the early
        # min_samples window
        for _ in range(20):
            sb.observe_report("n1", t, 100.0 + float(rng.normal(0, 2.0)))
            assert sb.states(t, 1e9)["n1"] == STATE_HEALTHY
            t += 5.0
        sb.observe_report("n1", t, 500.0)  # 5× spike
        assert sb.states(t, 1e9)["n1"] == STATE_ANOMALOUS
        row = sb.snapshot(t, 1e9)["nodes"]["n1"]
        assert row["anomalous"] and abs(row["power_z"]) > 4.0
        # the flag decays after the ttl
        assert sb.states(t + 120.0, 1e9)["n1"] == STATE_HEALTHY

    def test_flat_signal_never_flags(self):
        """Variance floor: a fake meter reporting a constant must not
        flag micro-wiggle as anomalous — the documented floor is
        max(5% of mean, 0.5 W), so a flat 10 W baseline flags only past
        a z × 0.5 W = 2 W excursion."""
        sb = FleetScoreboard(anomaly_z=4.0)
        t = 100.0
        for _ in range(30):
            sb.observe_report("n1", t, 80.0)
            t += 5.0
        sb.observe_report("n1", t, 80.4)  # 0.5% wiggle
        assert sb.states(t, 1e9)["n1"] == STATE_HEALTHY
        flat = FleetScoreboard(anomaly_z=4.0)
        t = 100.0
        for _ in range(30):
            flat.observe_report("n2", t, 10.0)
            t += 5.0
        flat.observe_report("n2", t, 11.5)  # inside the 2 W guarantee
        assert flat.states(t, 1e9)["n2"] == STATE_HEALTHY
        flat.observe_report("n2", t + 5.0, 13.0)  # 3 W: past the floor
        assert flat.states(t + 5.0, 1e9)["n2"] == STATE_ANOMALOUS

    def test_garbage_power_is_ignored(self):
        sb = FleetScoreboard()
        sb.observe_report("n1", 100.0, float("nan"))
        sb.observe_report("n1", 105.0, float("inf"))
        sb.observe_report("n1", 110.0, -5.0)
        row = sb.snapshot(110.0, 1e9)["nodes"]["n1"]
        assert row["reports"] == 3
        assert row["power_mean_w"] == 0.0  # stats never poisoned

    def test_lru_cap_evicts_longest_silent(self):
        sb = FleetScoreboard(cap=3)
        for i, t in enumerate((1.0, 2.0, 3.0)):
            sb.observe_report(f"n{i}", t, 10.0)
        sb.observe_report("n0", 4.0, 10.0)  # refresh n0
        sb.observe_report("n9", 5.0, 10.0)  # evicts n1 (oldest update)
        assert set(sb.states(5.0, 1e9)) == {"n0", "n2", "n9"}
        assert len(sb) == 3

    def test_quarantine_flood_never_evicts_real_nodes(self):
        """Quarantine names are unvalidated wire bytes: a burst of
        spoofed names must churn junk rows, not real nodes' health."""
        sb = FleetScoreboard(cap=4)
        for i in range(3):
            sb.observe_report(f"real{i}", 1.0 + i, 10.0)
        for j in range(50):  # 50 distinct junk names, cap is 4
            sb.observe_quarantine(f"junk{j}", 10.0, "decode")
        nodes = set(sb.states(10.0, 1e9))
        assert {"real0", "real1", "real2"} <= nodes
        assert len(sb) <= 4  # at most one junk row alive at a time
        # once full of accepted reporters, weak inserts are dropped
        sb.observe_report("real3", 11.0, 10.0)
        sb.observe_quarantine("junk-late", 12.0, "decode")
        assert set(sb.states(12.0, 1e9)) == {"real0", "real1",
                                             "real2", "real3"}
        # a known node's quarantine still lands
        sb.observe_quarantine("real1", 13.0, "skew")
        assert sb.states(13.0, 1e9)["real1"] == STATE_QUARANTINED

    def test_junk_rows_subcapped_and_expire(self):
        """Below the LRU cap, spoofed-name rows are bounded by the junk
        sub-cap while their quarantine flag is fresh and expire once it
        decays — never a permanent 'stale' series per junk name."""
        sb = FleetScoreboard(cap=1024, flag_ttl=60.0, junk_cap=8)
        for i in range(3):
            sb.observe_report(f"real{i}", 1.0 + i, 10.0)
        for j in range(200):
            sb.observe_quarantine(f"junk{j}", 10.0, "decode")
        snap = sb.snapshot(11.0, 1e9)
        assert snap["states"]["quarantined"] == 8  # sub-cap, not 200
        assert len(sb) == 3 + 8
        # flag decay expires the junk rows; real rows keep their LRU life
        snap = sb.snapshot(100.0, 1e9)
        assert set(snap["nodes"]) == {"real0", "real1", "real2"}
        assert len(sb) == 3
        # a junk row that starts reporting is promoted, never expired
        sb.observe_quarantine("late", 100.0, "decode")
        sb.observe_report("late", 101.0, 10.0)
        assert "late" in sb.snapshot(500.0, 1e9)["nodes"]

    def test_delivery_ewma(self):
        sb = FleetScoreboard(ewma_alpha=0.5)
        # delivery always follows an accepted report on the real ingest
        # path (a delivery-only row would read as junk and expire)
        sb.observe_report("n1", 0.0, 10.0)
        sb.observe_delivery("n1", 0.1)
        sb.observe_delivery("n1", 0.3)
        row = sb.snapshot(0.0, 0.0)["nodes"]["n1"]
        assert row["delivery_ewma_s"] == pytest.approx(0.2)


class TestScoreboardIngest:
    """The scoreboard through the aggregator's real ingest path."""

    def make(self, **kw):
        ticks = [1e9]
        kw.setdefault("stale_after", 15.0)
        kw.setdefault("degraded_ttl", 60.0)
        agg = Aggregator(APIServer(), model_mode=None,
                         clock=lambda: ticks[0], **kw)
        return agg, ticks

    def post(self, agg, report, zones=("package", "dram"), seq=1,
             run="r1", **kw):
        body = encode_report(report, list(zones), seq=seq, run=run, **kw)
        return agg._handle_report(_Req("/v1/report", "POST", body))

    def test_states_via_ingest(self):
        from tests.test_fleet import make_report

        agg, ticks = self.make()
        status, _, _ = self.post(agg, make_report("node-a"), seq=1)
        assert status == 204
        fleet = fleet_payload(agg)
        assert fleet["nodes"]["node-a"]["state"] == "healthy"
        # a seq gap marks the node lossy and counts the lost windows
        self.post(agg, make_report("node-a"), seq=10)
        fleet = fleet_payload(agg)
        assert fleet["nodes"]["node-a"]["state"] == "lossy"
        assert fleet["nodes"]["node-a"]["windows_lost"] == 8
        # a duplicate is counted but keeps liveness
        self.post(agg, make_report("node-a"), seq=10)
        assert fleet_payload(agg)["nodes"]["node-a"]["duplicates"] == 1
        # silence → stale (after the lossy flag decays)
        ticks[0] += 100.0
        assert fleet_payload(agg)["nodes"]["node-a"]["state"] == "stale"

    def test_quarantined_via_skewed_clock(self):
        from tests.test_fleet import make_report

        agg, ticks = self.make(skew_tolerance=120.0)
        status, _, _ = self.post(agg, make_report("node-b"),
                                 sent_at=ticks[0] - 1e6)
        assert status == 422
        fleet = fleet_payload(agg)
        assert fleet["nodes"]["node-b"]["state"] == "quarantined"
        assert fleet["states"]["quarantined"] == 1

    def test_node_state_gauge_and_rollup(self):
        from tests.test_fleet import make_report

        agg, ticks = self.make()
        self.post(agg, make_report("node-a"), seq=1)
        self.post(agg, make_report("node-c", seed=2), seq=1)
        fams = families(agg)
        states = fams["kepler_fleet_node_state"].samples
        assert {s.labels["node_name"]: s.value for s in states} == \
            {"node-a": 0, "node-c": 0}
        rollup = {s.labels["state"]: s.value
                  for s in fams["kepler_fleet_scoreboard_nodes"].samples}
        assert rollup == {"healthy": 2, "stale": 0, "lossy": 0,
                          "anomalous": 0, "quarantined": 0}
        assert set(rollup) == set(STATE_NAMES)
        ticks[0] += 100.0
        fams = families(agg)
        assert all(s.value == STATE_STALE
                   for s in fams["kepler_fleet_node_state"].samples)

    def test_scoreboard_cap_bounds_gauge_cardinality(self):
        from tests.test_fleet import make_report

        agg, ticks = self.make(scoreboard_cap=4)
        for i in range(10):
            self.post(agg, make_report(f"node-{i:02d}", seed=i), seq=1)
            ticks[0] += 1.0
        fams = families(agg)
        assert len(fams["kepler_fleet_node_state"].samples) == 4
        assert len(fleet_payload(agg)["nodes"]) == 4
