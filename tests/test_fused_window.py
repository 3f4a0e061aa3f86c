"""Fused device-resident window loop (ISSUE 20).

Correctness contracts of ``FusedWindowEngine`` + the aggregator's fused
tier (rung 0's top tier, ``fusedWindowK > 1``):

* the fused ``lax.scan`` over K intervals publishes windows BIT-IDENTICAL
  to the serial unfused packed path, per mode, across bucket-shape
  points including pad-heavy edges — staging, the device-resident delta
  ring, donation, and the batched K-window fetch change scheduling,
  never results;
* mid-scan churn (join, drop, restart/reassign) lands in the NEXT
  interval's scan slot — a window never mixes rows from two intervals
  (torn windows would break the per-window bit comparison);
* a ``device.dispatch_error`` mid-scan abandons the fused ring, demotes
  ONE tier (fused → ordinary rung 0), and republishes every pending
  snapshotted window at the lower tier — zero gaps, bit-consistent;
* clean windows at the demoted tier re-promote back to the fused tier.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

from kepler_tpu import fault
from kepler_tpu.fault import FaultPlan, FaultSpec
from kepler_tpu.fleet.scheduler import RUNG_NAME_FUSED, RUNG_PIPELINED
from kepler_tpu.fleet.window import (FusedWindowEngine, PackedWindowEngine,
                                     RowInput)
from kepler_tpu.parallel.mesh import make_mesh
from tests.test_window_pipeline import (ZONES, assert_windows_equal,
                                        churn_schedule, make_agg,
                                        make_report, run_schedule,
                                        seed_window)


def _rows(names, seed, w=4, zones=ZONES):
    return [RowInput(name=n, report=make_report(n, seed * 1000 + k, w=w,
                                                zones=zones),
                     zone_names=zones, ident=("run", seed))
            for k, n in enumerate(names)]


def run_capture_all(agg, schedules, fault_skip=None):
    """Drive the schedule, recording EVERY published window (a fused
    flush publishes K results inside one ``aggregate_once`` call)."""
    published = []
    orig = agg.windows._publish

    def spy(p):
        res = orig(p)
        published.append(res)
        return res

    agg.windows._publish = spy
    ctx = contextlib.nullcontext()
    if fault_skip is not None:
        ctx = fault.installed(FaultPlan([FaultSpec(
            site="device.dispatch_error", skip=fault_skip, count=1)]))
    with ctx:
        for sched in schedules:
            agg.test_clock[0] += 5.0
            seed_window(agg, sched, agg.test_clock[0])
            agg.aggregate_once()
        agg.windows.drain()
    return published


class TestEngineBitExact:
    """Seeded property sweep: fused K ≡ serial unfused, engine level,
    over bucket-shape points including pad rows (nodes below the node
    bucket, one-workload columns, a bucket-ladder growth trigger)."""

    # (n_nodes, workloads, n_windows) — node_bucket 8 / workload_bucket
    # 256 defaults put every point but the last well inside pad territory
    SHAPES = [
        (3, 4, 6),     # pad rows: 3 live rows in an 8-row bucket
        (8, 1, 6),     # full node bucket, minimal workload column
        (5, 17, 5),    # odd workload count (pad columns)
        (9, 100, 5),   # node-bucket growth (9 > 8) mid-sweep shape
        (2, 300, 5),   # workload-ladder growth past the 256 base bucket
    ]

    @pytest.mark.parametrize("n_nodes,w,n_win", SHAPES)
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_fused_equals_serial_across_shapes(self, n_nodes, w, n_win,
                                               k):
        mesh = make_mesh()
        base = PackedWindowEngine(mesh, backend="einsum")
        eng = FusedWindowEngine(mesh, backend="einsum", fused_k=k)
        names = [f"n{i}" for i in range(n_nodes)]
        serial_out, fused_out = {}, {}
        for i in range(n_win):
            rows = _rows(names, i, w=w)
            plan = base.plan_window(rows, ZONES, None)
            serial_out[i] = np.asarray(plan.program(*plan.args))
            _meta, flush = eng.stage(rows, ZONES, None)
            if flush is not None:
                outs = np.asarray(eng.dispatch(flush))
                for j in range(flush.k_live):
                    fused_out[len(fused_out)] = outs[j]
        flush = eng.flush(None)
        if flush is not None:
            outs = np.asarray(eng.dispatch(flush))
            for j in range(flush.k_live):
                fused_out[len(fused_out)] = outs[j]
        assert len(fused_out) == n_win
        assert eng.pending_occupancy() == 0
        for i in range(n_win):
            np.testing.assert_array_equal(fused_out[i], serial_out[i],
                                          err_msg=f"window {i}")

    def test_mid_scan_churn_lands_in_next_slot_never_torn(self):
        """A join, a drop, and a restart arriving while the ring is
        filling land in exactly their own interval's scan slot: every
        published window matches the serial engine fed the same
        per-interval fleet, so no window mixes rows across intervals."""
        mesh = make_mesh()
        base = PackedWindowEngine(mesh, backend="einsum")
        eng = FusedWindowEngine(mesh, backend="einsum", fused_k=4)
        fleets = {
            0: ["n0", "n1", "n2"],
            1: ["n0", "n1", "n2", "n3"],   # join mid-ring
            2: ["n0", "n2", "n3"],          # drop mid-ring
            3: ["n0", "n2", "n3", "n4"],   # another join at the flush
            4: ["n0", "n2", "n4"],          # drop right after the flush
            5: ["n0", "n2", "n4"],
        }
        serial_out, fused_out = {}, {}
        for i in sorted(fleets):
            rows = _rows(fleets[i], i)
            plan = base.plan_window(rows, ZONES, None)
            serial_out[i] = np.asarray(plan.program(*plan.args))
            meta, flush = eng.stage(rows, ZONES, None)
            # the staged window's metadata names exactly ITS interval's
            # fleet — the joiner is visible the interval it arrived, the
            # dropped node gone the interval it left
            assert sorted(meta.names) == sorted(fleets[i])
            if flush is not None:
                outs = np.asarray(eng.dispatch(flush))
                for j in range(flush.k_live):
                    fused_out[len(fused_out)] = outs[j]
        flush = eng.flush(None)
        if flush is not None:
            outs = np.asarray(eng.dispatch(flush))
            for j in range(flush.k_live):
                fused_out[len(fused_out)] = outs[j]
        assert len(fused_out) == len(fleets)
        for i in sorted(fleets):
            np.testing.assert_array_equal(fused_out[i], serial_out[i],
                                          err_msg=f"window {i}")


class TestAggregatorFusedTier:
    @pytest.mark.parametrize("model_mode", [None, "mlp"])
    @pytest.mark.parametrize("k", [2, 4])
    def test_fused_tier_matches_serial_under_churn(self, model_mode, k):
        schedules = churn_schedule(9)
        serial = run_schedule(make_agg(1, model_mode=model_mode),
                              schedules)
        agg = make_agg(1, model_mode=model_mode, fused_window_k=k)
        fused = run_capture_all(agg, schedules)
        assert len(fused) == len(serial) == len(schedules)
        for a, b in zip(serial, fused):
            assert a.timestamp == b.timestamp
            assert_windows_equal(a, b)
        assert agg.windows._stats["attributions_total"] == len(schedules)
        # the flush set the amortized sync figure; ring-filling calls
        # reported a zero device leg
        assert agg.windows._stats["last_sync_per_window_ms"] > 0.0
        health = agg.window_health()
        assert health["fused"]["k"] == k
        assert health["fused"]["active"] is True
        assert health["fused"]["degraded"] is False
        agg.shutdown()

    def test_staleness_bounded_by_k_minus_one(self):
        """Windows publish in batches of K, oldest first: right before a
        flush the oldest snapshot is K−1 intervals old, never more."""
        k = 4
        agg = make_agg(1, model_mode=None, fused_window_k=k)
        schedules = churn_schedule(9)
        max_pending = 0
        for sched in schedules:
            agg.test_clock[0] += 5.0
            seed_window(agg, sched, agg.test_clock[0])
            agg.aggregate_once()
            max_pending = max(max_pending, len(agg.windows._fused_pending))
        assert max_pending == k - 1  # the K-th stage call flushes
        agg.shutdown()
        assert not agg.windows._fused_pending  # drain leaves nothing behind


@pytest.mark.chaos
class TestFusedChaos:
    def test_dispatch_error_mid_scan_demotes_and_republishes(self):
        """``device.dispatch_error`` while the ring holds staged windows:
        the fused ring is abandoned, the tier demotes by ONE step (fused
        → ordinary rung 0 — the rung index stays 0), and the pending
        snapshots republish at the lower tier — every interval still
        publishes exactly once, bit-consistent with a fault-free serial
        run."""
        schedules = churn_schedule(8)
        serial = run_schedule(make_agg(1, model_mode=None), schedules)
        agg = make_agg(1, model_mode=None, fused_window_k=4,
                       repromote_after=100)  # stay demoted for asserts
        published = run_capture_all(agg, schedules, fault_skip=2)
        assert len(published) == len(schedules)  # zero gaps
        for a, b in zip(serial, published):
            assert a.timestamp == b.timestamp
            assert_windows_equal(a, b)
        assert agg.windows._rung == RUNG_PIPELINED  # demotion stayed within rung 0
        assert agg.windows._fused_degraded
        assert agg.windows._stats["window_demotions_total"] == 1
        transitions = [t for t in agg.windows._rung_timeline
                       if t.get("from_rung_name") == RUNG_NAME_FUSED]
        assert transitions and transitions[0]["reason"] == "dispatch_error"
        health = agg.window_health()
        assert health["fused"]["degraded"] is True
        assert health["ok"] is False
        agg.shutdown()

    def test_clean_windows_repromote_to_fused_tier(self):
        schedules = churn_schedule(12)
        serial = run_schedule(make_agg(1, model_mode=None), schedules)
        agg = make_agg(1, model_mode=None, fused_window_k=2,
                       repromote_after=2)
        published = run_capture_all(agg, schedules, fault_skip=1)
        assert len(published) == len(schedules)
        for a, b in zip(serial, published):
            assert_windows_equal(a, b)
        assert not agg.windows._fused_degraded
        assert agg.windows._stats["window_repromotions_total"] >= 1
        assert agg.window_health()["fused"]["active"] is True
        agg.shutdown()
