"""Device-resident pipelined fleet windows (ISSUE 5).

Correctness contracts of `kepler_tpu.fleet.window` + the pipelined
`Aggregator.aggregate_once`:

* depth-2 pipelining publishes BIT-IDENTICAL windows to the serial
  (depth-1) cycle, per mode, under churn (joins, drops, restarts, zone
  changes) — the strongest possible statement that the resident batch,
  delta H2D, ping-pong donation, and sparse model evaluation change
  scheduling, never results;
* shutdown (and an emptied fleet) drains in-flight windows
  deterministically;
* a mid-pipeline drop/join never mixes stale rows into a fresh window;
* donated-buffer reuse never aliases a window still being read (the
  churn stress would corrupt the bit-exact comparison if it did);
* bucket ladders grow geometrically and shrink only after the
  hysteresis window; delta-H2D row accounting matches what changed;
* under the served loop (``run``) a window is published when its program
  is done, not when the loop next comes round (ISSUE 32): in dispatch
  order, once each, bit-equal to the serial cycle, never more than
  ``pipeline_depth`` in flight, a failure raised by the loop's next step.
  Those tests drive the loop's interval by hand (``TickGate``) and wait
  on conditions with deadlines: no time a CPU run yields is asserted.
"""

from __future__ import annotations

import collections
import json
import threading
import time

import numpy as np
import pytest

from kepler_tpu import fault
from kepler_tpu.fault import FaultPlan, FaultSpec
from kepler_tpu.fleet.aggregator import Aggregator, _Stored
from kepler_tpu.fleet.scheduler import RUNG_PACKED_SERIAL, RUNG_PIPELINED
from kepler_tpu.fleet.window_record import LEGS, MARKS
from kepler_tpu.fleet.window import BucketLadder
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
from kepler_tpu.parallel.mesh import make_mesh
from kepler_tpu.server.http import APIServer

ZONES = ("package", "dram")
ZONES_WIDE = ("package", "dram", "uncore")


def make_report(name: str, seed: int, w: int = 4, zones=ZONES,
                mode: int = MODE_RATIO) -> NodeReport:
    rng = np.random.default_rng(abs(hash((name, seed))) % (2**32))
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    z = len(zones)
    return NodeReport(
        node_name=name,
        zone_deltas_uj=rng.uniform(1e7, 5e8, z).astype(np.float32),
        zone_valid=np.ones(z, bool),
        usage_ratio=float(rng.uniform(0.2, 0.9)),
        cpu_deltas=cpu,
        workload_ids=[f"{name}-w{k}" for k in range(w)],
        node_cpu_delta=float(cpu.sum()),
        dt_s=5.0,
        mode=mode,
        workload_kinds=np.ones(w, np.int8),
    )


def make_agg(depth: int, **kw) -> Aggregator:
    rows_base = kw.pop("history_rows_base", None)
    agg = _make_agg(depth, **kw)
    if rows_base is not None:
        # a ladder small enough that this fleet's history goes up compact
        # (a device's 8 dense rows are under the shipped base of 1024)
        agg.windows._history_rows = BucketLadder(rows_base, 16)
    return agg


def _make_agg(depth: int, **kw) -> Aggregator:
    kw.setdefault("model_mode", "mlp")
    kw.setdefault("node_bucket", 8)
    kw.setdefault("workload_bucket", 8)
    kw.setdefault("stale_after", 1e9)
    if "clock" not in kw:
        ticks = [1e9]
        kw["clock"] = lambda: ticks[0]
        agg = Aggregator(APIServer(), pipeline_depth=depth, **kw)
        agg.test_clock = ticks  # driven by run_schedule/seed helpers
    else:
        agg = Aggregator(APIServer(), pipeline_depth=depth, **kw)
    agg.windows.mesh = make_mesh()
    return agg


def churn_schedule(n_windows: int, base_nodes: int = 6) -> list[dict]:
    """Per-window {name: (seed, zones, mode, seq, run)} with joins,
    drops, a restart, and a zone-set change sprinkled in."""
    schedules = []
    for win in range(n_windows):
        sched = {}
        for i in range(base_nodes):
            name = f"n{i:02d}"
            if win % 5 == 2 and i == 1:
                continue  # n01 drops out this window
            zones = ZONES_WIDE if (win >= 4 and i == 2) else ZONES
            run = "r2" if (win >= 3 and i == 3) else "r1"
            seq = win + 1 if run == "r1" else win - 1  # restart resets
            mode = MODE_MODEL if i % 2 else MODE_RATIO
            sched[name] = (win * 100 + i, zones, mode, max(1, seq), run)
        if win >= 3:  # a late joiner
            sched["n99"] = (win * 100 + 99, ZONES, MODE_MODEL,
                            win - 2, "r1")
        schedules.append(sched)
    return schedules


def seed_window(agg: Aggregator, sched: dict, now: float) -> None:
    for name, (seed, zones, mode, seq, run) in sched.items():
        rep = make_report(name, seed, zones=zones, mode=mode)
        agg._reports[name] = _Stored(report=rep, zone_names=tuple(zones),
                                     received=now, seq=seq, run=run)
    for name in list(agg._reports):
        if name not in sched:
            del agg._reports[name]


def run_schedule(agg: Aggregator, schedules: list[dict]) -> list:
    published = []
    for sched in schedules:
        agg.test_clock[0] += 5.0
        seed_window(agg, sched, agg.test_clock[0])
        result = agg.aggregate_once()
        if result is not None:
            published.append(result)
    tail = agg.windows.drain()
    if tail is not None:
        published.append(tail)
    return published


def assert_windows_equal(a, b) -> None:
    assert set(a.names) == set(b.names)
    assert list(a.zones) == list(b.zones)
    for name in a.names:
        i, j = a.rows[name], b.rows[name]
        assert int(a.mode[i]) == int(b.mode[j]), name
        np.testing.assert_array_equal(a.node_power_uw[i],
                                      b.node_power_uw[j], err_msg=name)
        np.testing.assert_array_equal(a.node_energy_uj[i],
                                      b.node_energy_uj[j], err_msg=name)
        np.testing.assert_array_equal(a.node_joules_total[i],
                                      b.node_joules_total[j], err_msg=name)
        assert a.counts[i] == b.counts[j]
        assert a.workload_ids[i] == b.workload_ids[j]
        ra, rb = a.render_node(name), b.render_node(name)
        assert ra == rb, name


class TestPipelineBitExact:
    @pytest.mark.parametrize("model_mode", [None, "mlp"])
    def test_depth2_matches_serial_under_churn(self, model_mode):
        schedules = churn_schedule(9)
        serial = run_schedule(make_agg(1, model_mode=model_mode),
                              schedules)
        piped = run_schedule(make_agg(2, model_mode=model_mode),
                             schedules)
        assert len(serial) == len(schedules)
        assert len(piped) == len(schedules)
        for a, b in zip(serial, piped):
            assert a.timestamp == b.timestamp
            assert_windows_equal(a, b)

    def test_accuracy_mode_legacy_path_pipelines_bit_exact(self):
        schedules = churn_schedule(6)
        serial = run_schedule(make_agg(1, accuracy_mode=True), schedules)
        piped = run_schedule(make_agg(2, accuracy_mode=True), schedules)
        assert len(piped) == len(serial) == len(schedules)
        for a, b in zip(serial, piped):
            assert_windows_equal(a, b)

    def test_temporal_mode_pipelines(self):
        schedules = churn_schedule(4)
        piped = run_schedule(
            make_agg(2, model_mode="temporal", history_window=4),
            schedules)
        assert len(piped) == len(schedules)
        for res in piped:
            for name in res.names:
                node = res.render_node(name)
                assert all(np.isfinite(w["power_uw"]).all()
                           for w in node["workloads"])

    def test_packed_default_within_budget_of_accuracy_path(self):
        # the f16 packed default vs the einsum-f32 accuracy path: node
        # power must agree within the 0.5% budget (ratio-only fleet —
        # untrained estimators have near-zero watts, useless for a
        # relative bound)
        schedules = churn_schedule(3)
        packed = run_schedule(make_agg(1, model_mode=None), schedules)
        exact = run_schedule(
            make_agg(1, model_mode=None, accuracy_mode=True), schedules)
        for a, b in zip(packed, exact):
            for name in a.names:
                pa = a.node_power_uw[a.rows[name]]
                pb = b.node_power_uw[b.rows[name]]
                np.testing.assert_allclose(pa, pb, rtol=5e-3, atol=1.0)


class TestPipelineDrain:
    def test_shutdown_drains_in_flight_window(self):
        agg = make_agg(2)
        seed_window(agg, churn_schedule(1)[0], 1e9)
        assert agg.aggregate_once() is None  # in flight, not published
        assert len(agg.windows._inflight) == 1
        agg.shutdown()
        assert not agg.windows._inflight
        with agg.windows._results_lock:
            assert agg.windows._results is not None
        assert agg.windows._stats["attributions_total"] == 1

    def test_empty_fleet_drains_instead_of_rotting(self):
        agg = make_agg(2, stale_after=10.0, clock=lambda: clock[0])
        clock = [1e9]
        seed_window(agg, churn_schedule(1)[0], clock[0])
        assert agg.aggregate_once() is None
        clock[0] += 100.0  # everything stale now
        result = agg.aggregate_once()  # empty fleet → drain
        assert result is not None
        assert not agg.windows._inflight
        assert agg.windows._stats["attributions_total"] == 1

    def test_run_loop_exit_drains(self):
        from kepler_tpu.service.lifecycle import CancelContext

        agg = make_agg(2, interval=0.01)
        seed_window(agg, churn_schedule(1)[0], 1e9)
        ctx = CancelContext()
        import threading

        t = threading.Thread(target=agg.run, args=(ctx,))
        t.start()
        import time as _t

        deadline = _t.monotonic() + 10
        while (agg.windows._stats["attributions_total"] == 0
               and _t.monotonic() < deadline):
            _t.sleep(0.02)
        ctx.cancel()
        t.join(timeout=10)
        assert not t.is_alive()
        assert not agg.windows._inflight

    def test_published_results_at_most_one_interval_stale(self):
        agg = make_agg(2)
        schedules = churn_schedule(4)
        stamps = []
        for sched in schedules:
            agg.test_clock[0] += 5.0
            seed_window(agg, sched, agg.test_clock[0])
            res = agg.aggregate_once()
            stamps.append((agg.test_clock[0],
                           None if res is None else res.timestamp))
        for dispatched_at, published_ts in stamps[1:]:
            assert published_ts == dispatched_at - 5.0  # exactly 1 behind


class TestMidPipelineChurn:
    def test_drop_join_never_mixes_stale_rows(self):
        agg = make_agg(2)
        now = 1e9
        win1 = {f"n{i}": (i, ZONES, i % 2, 1, "r1") for i in range(4)}
        seed_window(agg, win1, now)
        agg.aggregate_once()
        # n2 drops; n5 joins — dispatched while window 1 is in flight
        # (fresh data seeds: the re-reports carry NEW values)
        win2 = {name: (seed + 10, z, m, 2, r)
                for name, (seed, z, m, _s, r) in win1.items()
                if name != "n2"}
        win2["n5"] = (50, ZONES, MODE_RATIO, 1, "r1")
        now += 5.0
        seed_window(agg, win2, now)
        first = agg.aggregate_once()  # publishes window 1
        assert set(first.names) == set(win1)
        second = agg.windows.drain()  # publishes window 2
        assert set(second.names) == set(win2)
        assert "n2" not in second.rows
        assert "n5" in second.rows
        # fresh node's watts actually computed (not a stale zero row)
        n5 = second.render_node("n5")
        assert any(np.asarray(w["power_uw"]).sum() != 0.0
                   for w in n5["workloads"])
        # n0's re-report (new seed → new data) actually refreshed
        assert not np.array_equal(
            first.node_power_uw[first.rows["n0"]],
            second.node_power_uw[second.rows["n0"]])

    def test_returning_node_gets_fresh_row_not_old_buffer_contents(self):
        # absent for one window (row cleared), back with NEW data: the
        # published watts must match a from-scratch aggregator fed the
        # same final window — old resident contents must never leak
        schedules = [
            {f"n{i}": (i, ZONES, MODE_RATIO, 1, "r1") for i in range(3)},
            {f"n{i}": (10 + i, ZONES, MODE_RATIO, 2, "r1")
             for i in range(2)},  # n2 absent
            {f"n{i}": (20 + i, ZONES, MODE_RATIO, 3, "r1")
             for i in range(3)},  # n2 back, new data
        ]
        published = run_schedule(make_agg(2, model_mode=None), schedules)
        fresh = run_schedule(make_agg(1, model_mode=None), [schedules[-1]])
        got = published[-1].render_node("n2")
        want = fresh[-1].render_node("n2")
        assert got["node_power_uw"] == want["node_power_uw"]
        assert [w["power_uw"] for w in got["workloads"]] == \
            [w["power_uw"] for w in want["workloads"]]


class TestBucketLadder:
    def test_grow_is_immediate_and_geometric(self):
        ladder = BucketLadder(8, shrink_after=3)
        assert ladder.fit(5) == 8
        assert ladder.fit(9) == 16
        assert ladder.fit(100) == 128

    def test_align_rounds_base_and_survives_growth(self):
        ladder = BucketLadder(6, shrink_after=3, align=4)
        assert ladder.base == 8
        assert ladder.fit(9) % 4 == 0

    def test_shrink_needs_consecutive_underhalf_windows(self):
        ladder = BucketLadder(8, shrink_after=3)
        ladder.fit(100)  # → 128
        assert ladder.fit(10) == 128  # under half #1
        assert ladder.fit(10) == 128  # under half #2
        assert ladder.fit(100) == 128  # back over half: streak resets
        assert ladder.fit(10) == 128
        assert ladder.fit(10) == 128
        assert ladder.fit(10) == 64  # third consecutive → one step down
        assert ladder.fit(10) == 64  # streak restarts after a shrink

    def test_never_shrinks_below_base(self):
        ladder = BucketLadder(8, shrink_after=1)
        ladder.fit(8)
        for _ in range(10):
            ladder.fit(1)
        assert ladder.bucket == 8


class TestDeltaAccounting:
    def test_unchanged_fleet_uploads_zero_rows(self):
        agg = make_agg(1)
        sched = {f"n{i}": (i, ZONES, i % 2, 1, "r1") for i in range(5)}
        now = 1e9
        seed_window(agg, sched, now)
        agg.aggregate_once()
        assert agg.windows._stats["last_h2d_rows"] == 5  # rebuild packs all
        # same (run, seq) → nothing re-uploaded, on every ring buffer
        for _ in range(3):
            agg.aggregate_once()
            assert agg.windows._stats["last_h2d_rows"] == 0
        # one change → staged once per ring buffer it must reach, then 0
        sched["n3"] = (99, ZONES, 1, 2, "r1")
        seed_window(agg, sched, now)
        staged = []
        for _ in range(4):
            agg.aggregate_once()
            staged.append(agg.windows._stats["last_h2d_rows"])
        assert staged[0] == 1 and staged[-1] == 0
        assert sum(staged) == len(agg.windows._engine._buffers)
        # the first delta compiled the scatter-update once; further
        # same-sized deltas never recompile
        compiles = agg.windows._stats["window_compiles_total"]
        sched["n3"] = (123, ZONES, 1, 3, "r1")
        seed_window(agg, sched, now)
        agg.aggregate_once()
        agg.aggregate_once()
        assert agg.windows._stats["window_compiles_total"] == compiles

    def test_pre_nonce_rows_always_reupload(self):
        agg = make_agg(1)
        sched = {"n0": (1, ZONES, 0, 0, "")}  # no run nonce, seq 0
        seed_window(agg, sched, 1e9)
        agg.aggregate_once()
        agg.aggregate_once()
        assert agg.windows._stats["last_h2d_rows"] == 1

    def test_fleet_growth_compiles_once_per_rung(self):
        agg = make_agg(1, node_bucket=8)
        now = 1e9
        sched = {f"n{i}": (i, ZONES, 0, 1, "r1") for i in range(5)}
        seed_window(agg, sched, now)
        agg.aggregate_once()
        base_compiles = agg.windows._stats["window_compiles_total"]
        # grow past the node bucket: one new program + one new update
        sched.update({f"m{i}": (i, ZONES, 0, 1, "r1") for i in range(8)})
        seed_window(agg, sched, now)
        agg.aggregate_once()
        grown = agg.windows._stats["window_compiles_total"]
        assert grown > base_compiles
        # repeat windows at the new rung: no further compiles
        agg.aggregate_once()
        agg.aggregate_once()
        assert agg.windows._stats["window_compiles_total"] == grown


class TestShardedWindow:
    """ISSUE 7: the packed window sharded over the device mesh
    (ShardedWindowEngine — per-shard rings, sticky node→shard
    assignment, per-shard delta H2D, one sharded dispatch)."""

    def test_rung0_engine_is_sharded_on_multidevice_mesh(self):
        import jax

        from kepler_tpu.fleet.window import ShardedWindowEngine

        agg = make_agg(1)
        seed_window(agg, churn_schedule(1)[0], 1e9)
        agg.aggregate_once()
        assert isinstance(agg.windows._engine, ShardedWindowEngine)
        assert agg.windows._engine.n_shards == len(jax.devices())
        assert agg.windows._stats["window_shards"] == len(jax.devices())
        assert len(agg.windows._stats["last_h2d_shards"]) == len(jax.devices())
        health = agg.window_health()
        assert health["rung_name"] == "packed-sharded-pipelined"
        assert health["shards"] == len(jax.devices())
        families = {f.name: f for f in agg.collect()}
        shards = families["kepler_fleet_window_shards"]
        assert shards.samples[0].value == len(jax.devices())
        agg.shutdown()

    def test_2d_mesh_falls_back_to_unsharded_engine(self):
        from kepler_tpu.fleet.window import (PackedWindowEngine,
                                             ShardedWindowEngine)

        agg = make_agg(1)
        agg.windows.mesh = make_mesh([4, 2], ["node", "model"])
        seed_window(agg, churn_schedule(1)[0], 1e9)
        agg.aggregate_once()
        assert type(agg.windows._engine) is PackedWindowEngine
        assert not isinstance(agg.windows._engine, ShardedWindowEngine)
        assert agg.windows._stats["window_shards"] == 1
        assert agg.window_health()["rung_name"] == "packed-pipelined"
        agg.shutdown()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_sharded_matches_single_device_bit_exact_under_churn(
            self, depth):
        import jax

        schedules = churn_schedule(9)
        sharded = run_schedule(make_agg(depth), schedules)
        single = make_agg(1)
        single.windows.mesh = make_mesh([1], devices=jax.devices()[:1])
        reference = run_schedule(single, schedules)
        assert len(sharded) == len(reference) == len(schedules)
        for a, b in zip(reference, sharded):
            assert a.timestamp == b.timestamp
            assert_windows_equal(a, b)

    def test_sticky_assignment_join_drop_rejoin_touch_one_shard(self):
        """A join (and a drop, and a rejoin) stages rows ONLY to the
        owning shard: every other shard sees zero H2D and no engine
        compiles — surviving nodes never migrate."""
        agg = make_agg(1, node_bucket=32)  # shard bucket 4 on 8 devices
        base = {f"n{i:02d}": (i, ZONES, i % 2, 1, "r1") for i in range(10)}
        now = 1e9
        seed_window(agg, base, now)
        agg.aggregate_once()
        engine = agg.windows._engine
        slots = len(engine._buffers)
        # warm the delta path (every shard stages once, the scatter-
        # update compiles its one shared key), then settle to zero H2D
        warm = {name: (seed + 1000, z, m, 2, r)
                for name, (seed, z, m, _s, r) in base.items()}
        seed_window(agg, warm, now)
        for _ in range(slots):
            agg.aggregate_once()
        agg.aggregate_once()
        assert agg.windows._stats["last_h2d_rows"] == 0
        base = warm
        home = dict(engine._shard_of)
        compiles = agg.windows._stats["window_compiles_total"]

        joined = dict(base)
        joined["n99"] = (99, ZONES, MODE_RATIO, 1, "r1")
        seed_window(agg, joined, now)
        touched = set()
        for _ in range(slots + 1):
            agg.aggregate_once()
            staged = agg.windows._stats["last_h2d_shards"]
            touched |= {k for k, n in enumerate(staged) if n}
        # the join staged on exactly its shard (once per ring slot),
        # nothing recompiled, and nobody else moved or restaged
        assert touched == {engine._shard_of["n99"]}
        assert agg.windows._stats["window_compiles_total"] == compiles
        assert {n: k for n, k in engine._shard_of.items()
                if n != "n99"} == home

        n99_shard = engine._shard_of["n99"]
        seed_window(agg, base, now)  # n99 drops: its shard clears the row
        touched = set()
        for _ in range(slots + 1):
            agg.aggregate_once()
            staged = agg.windows._stats["last_h2d_shards"]
            touched |= {k for k, n in enumerate(staged) if n}
        assert touched == {n99_shard}  # only the freed row's shard cleared
        assert agg.windows._stats["window_compiles_total"] == compiles
        assert dict(engine._shard_of) == home

        joined["n99"] = (123, ZONES, MODE_RATIO, 2, "r1")  # rejoin, new data
        seed_window(agg, joined, now)
        result = agg.aggregate_once()
        staged = agg.windows._stats["last_h2d_shards"]
        assert sum(1 for n in staged if n) == 1
        assert agg.windows._stats["window_compiles_total"] == compiles
        assert {n: k for n, k in engine._shard_of.items()
                if n != "n99"} == home
        # the rejoined node's published row is the FRESH report (old
        # resident contents never leak; joules/timestamp are cumulative
        # and legitimately differ between the two aggregators)
        fresh = make_agg(1)
        fresh_result = run_schedule(fresh, [joined])[-1]
        got = result.render_node("n99")
        want = fresh_result.render_node("n99")
        for key in ("mode", "node_power_uw", "node_energy_uj", "workloads"):
            assert got[key] == want[key], key
        agg.shutdown()
        fresh.shutdown()

    def test_changed_row_stages_only_on_owning_shard(self):
        agg = make_agg(1, node_bucket=32)
        sched = {f"n{i:02d}": (i, ZONES, i % 2, 1, "r1") for i in range(10)}
        seed_window(agg, sched, 1e9)
        agg.aggregate_once()
        engine = agg.windows._engine
        for _ in range(len(engine._buffers)):
            agg.aggregate_once()
        sched["n04"] = (321, ZONES, 0, 2, "r1")
        seed_window(agg, sched, 1e9)
        agg.aggregate_once()
        staged = agg.windows._stats["last_h2d_shards"]
        owner = engine._shard_of["n04"]
        assert staged[owner] == 1
        assert sum(staged) == 1
        agg.shutdown()

    def test_bucket_overflow_rebalances_all_shards(self):
        """Only overflow (no shard has a free row) migrates nodes: the
        rebuild restages every shard at the grown bucket and balances
        MODE_MODEL rows across shards within one row."""
        import jax

        from kepler_tpu.parallel.fleet import MODE_MODEL as MM

        n_dev = len(jax.devices())
        agg = make_agg(1, node_bucket=n_dev)  # shard bucket 1: 8 rows
        sched = {f"n{i:02d}": (i, ZONES, i % 2, 1, "r1")
                 for i in range(n_dev)}
        seed_window(agg, sched, 1e9)
        agg.aggregate_once()
        engine = agg.windows._engine
        compiles = agg.windows._stats["window_compiles_total"]
        sched.update({f"m{i:02d}": (50 + i, ZONES, i % 2, 1, "r1")
                      for i in range(4)})  # 12 nodes > 8 rows: overflow
        seed_window(agg, sched, 1e9)
        agg.aggregate_once()
        staged = agg.windows._stats["last_h2d_shards"]
        assert all(n > 0 for n in staged)  # full rebalance restage
        assert agg.windows._stats["window_compiles_total"] > compiles
        mode_arr = list(engine._mode)
        sb = engine._ladder_n.bucket
        per_shard_model = [
            sum(1 for r in range(k * sb, (k + 1) * sb)
                if mode_arr[r] == MM) for k in range(engine.n_shards)]
        assert max(per_shard_model) - min(per_shard_model) <= 1
        # steady again afterwards
        agg.aggregate_once()
        agg.aggregate_once()
        assert agg.windows._stats["window_compiles_total"] > compiles
        agg.shutdown()


# -- publication on completion (ISSUE 32) -----------------------------------

DEADLINE = 120.0  # generous: the suite runs under six workers
KINDS = {"packed": {"model_mode": "mlp"},
         "legacy": {"model_mode": "mlp", "accuracy_mode": True},
         "temporal": {"model_mode": "temporal", "history_window": 4},
         # the history as its valid rows, the blocks of published windows
         # written again: the loop pops them, the publisher appends them
         "temporal_compact": {"model_mode": "temporal", "history_window": 4,
                              "history_rows_base": 2}}


def wait_until(pred, what: str) -> None:
    deadline = time.monotonic() + DEADLINE
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting: {what}"
        time.sleep(0.005)


class TickGate:
    """In the place of the loop's ``CancelContext``: its interval ends when
    the test says so, and ``tick`` returns once the loop has done the step
    and is back in its wait."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._ticks = 0
        self._cancelled = False
        self.arrivals = 0  # times the loop began a wait

    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self) -> None:
        with self._cond:
            self._cancelled = True
            self._cond.notify_all()

    def wait(self, timeout: float | None = None) -> bool:
        with self._cond:
            self.arrivals += 1
            self._cond.notify_all()
            self._cond.wait_for(lambda: self._ticks or self._cancelled,
                                timeout)
            if self._cancelled:
                return True
            self._ticks = max(0, self._ticks - 1)
            return False

    def tick(self) -> None:
        with self._cond:
            seen = self.arrivals
            self._ticks += 1
            self._cond.notify_all()
            assert self._cond.wait_for(lambda: self.arrivals > seen,
                                       DEADLINE), "the step did not end"


class ServedLoop:
    """``Aggregator.run`` on a thread of its own behind a ``TickGate``, the
    interval itself far beyond any deadline here: whatever is published
    was published without the loop coming round for it. Every publication
    is kept, with the thread that made it."""

    def __init__(self, depth: int = 2, **kw) -> None:
        self.agg = make_agg(depth, interval=3600.0, **kw)
        self.gate = TickGate()
        self.published: list = []  # (FleetResults, thread name)
        inner = self.agg.windows._publish

        def publish(p, on_loop=True):
            results = inner(p, on_loop=on_loop)
            self.published.append((results,
                                   threading.current_thread().name))
            return results

        self.agg.windows._publish = publish
        self.thread = threading.Thread(target=self.agg.run,
                                       args=(self.gate,), daemon=True)
        self.thread.start()
        wait_until(lambda: self.gate.arrivals == 1, "the loop's first wait")

    def step(self, sched: dict) -> None:
        """One interval: the fleet reports, the loop takes its step."""
        self.agg.test_clock[0] += 5.0
        seed_window(self.agg, sched, self.agg.test_clock[0])
        self.gate.tick()

    def attributions(self) -> int:
        with self.agg.windows._results_lock:
            return self.agg.windows._stats["attributions_total"]

    def stop(self) -> None:
        self.gate.cancel()
        self.thread.join(timeout=DEADLINE)
        assert not self.thread.is_alive()
        self.agg.shutdown()


class _Request:
    def __init__(self, path: str) -> None:
        self.path = path


class TestPublishedOnCompletion:
    @pytest.mark.parametrize("kind", ["packed", "temporal"])
    def test_first_window_is_served_before_the_second_tick(self, kind):
        loop = ServedLoop(2, **KINDS[kind])
        try:
            loop.step(churn_schedule(1)[0])
            # the loop is back in its wait and no second tick ever comes
            wait_until(lambda: loop.attributions() == 1,
                       "the first window's publication")
            assert loop.agg.windows._window_seq == 1 and loop.gate.arrivals == 2
            assert not loop.agg.windows._inflight
            status, _hdr, body = loop.agg._handle_results(
                _Request("/v1/results?node=n00"))
            assert status == 200
            assert json.loads(body)["timestamp"] == loop.agg.test_clock[0]
            (_res, thread), = loop.published
            assert thread == "kepler-window-publish"
            families = {f.name: f for f in loop.agg.collect()}
            early = families["kepler_fleet_windows_published_early"]
            assert early.samples[0].value == 1
        finally:
            loop.stop()

    @pytest.mark.parametrize("kind", sorted(KINDS))
    def test_in_dispatch_order_and_bit_equal_to_the_serial_cycle(self, kind):
        schedules = churn_schedule(9)
        serial = run_schedule(make_agg(1, **KINDS[kind]), schedules)
        loop = ServedLoop(2, **KINDS[kind])
        try:
            for k, sched in enumerate(schedules):
                loop.step(sched)
                # a step returns with fewer than `depth` windows in flight
                assert loop.attributions() >= k
        finally:
            loop.stop()
        served = [res for res, _thread in loop.published]
        assert len(served) == len(serial) == len(schedules)
        stamps = [res.timestamp for res in served]
        assert stamps == sorted(set(stamps))  # in order, each once
        for a, b in zip(serial, served):
            assert a.timestamp == b.timestamp
            assert_windows_equal(a, b)
        assert loop.agg.windows._stats["attributions_total"] == len(schedules)
        assert loop.agg.windows._rung == RUNG_PIPELINED
        assert loop.agg.windows._stats["window_demotions_total"] == 0
        sent = loop.agg.windows._window_ledger.counts["hist_rows_sent"]
        if kind == "temporal_compact":  # fewer rows than 8 nodes x 8 slots
            assert 0 < sent < 64 * len(schedules)
            assert loop.agg.windows._history_spare
        else:
            assert sent == (64 * len(schedules) if kind == "temporal" else 0)

    def test_stressed_loop_publisher_and_readers_lose_no_window(self):
        """The loop, the publisher and four readers of what they publish,
        handed the interpreter every 10 µs: 30 windows back to back come
        out once each, in order, bit-equal to the serial cycle (a lost or
        doubled publication would break the cumulative joules)."""
        import sys

        schedules = churn_schedule(30)
        serial = run_schedule(make_agg(1), schedules)
        loop = ServedLoop(2)
        done = threading.Event()
        errors: list = []

        def reader() -> None:
            try:
                while not done.is_set():
                    loop.agg._handle_results(_Request("/v1/results"))
                    loop.agg._handle_window_debug(None)
                    list(loop.agg.collect())
            except Exception as err:  # relayed to the test's thread
                errors.append(err)

        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(4)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in readers:
                t.start()
            for sched in schedules:
                loop.step(sched)
            loop.stop()
        finally:
            sys.setswitchinterval(interval)
            done.set()
            for t in readers:
                t.join(timeout=DEADLINE)
            loop.stop()
        assert not errors and not any(t.is_alive() for t in readers)
        served = [res for res, _thread in loop.published]
        assert [r.timestamp for r in served] == [r.timestamp for r in serial]
        for a, b in zip(serial, served):
            assert_windows_equal(a, b)
        assert loop.agg.windows._stats["attributions_total"] == len(schedules)
        assert loop.agg.windows._stats["window_demotions_total"] == 0
        assert not loop.agg.windows._inflight

    @pytest.mark.parametrize("depth", [2, 3])
    def test_never_more_than_depth_windows_in_flight(self, depth):
        """Every fetch hangs 0.2 s and the ticks come as fast as the loop
        takes them: an unbounded loop would run five windows ahead."""

        class Watched(collections.deque):
            peak = 0

            def append(self, item):
                super().append(item)
                self.peak = max(self.peak, len(self))

        loop = ServedLoop(depth, dispatch_timeout=DEADLINE)
        loop.agg.windows._inflight = Watched()
        plan = FaultPlan([FaultSpec(site="device.stall", arg=0.2)])
        try:
            with fault.installed(plan):
                for sched in churn_schedule(6):
                    loop.step(sched)
                    assert len(loop.agg.windows._inflight) < depth
                loop.stop()
        finally:
            loop.stop()
        assert loop.agg.windows._inflight.peak <= depth
        assert plan.fired("device.stall") == 6
        stamps = [res.timestamp for res, _thread in loop.published]
        assert len(stamps) == 6 and stamps == sorted(set(stamps))
        assert loop.agg.windows._stats["window_demotions_total"] == 0

    @pytest.mark.parametrize("how", ["stall", "error"])
    def test_a_failed_early_fetch_is_raised_by_the_next_step(self, how):
        """The publisher leaves the failure on the window; the loop's next
        step demotes one rung and publishes its own window there, once.
        The failed window is abandoned with the ring, as a failed fetch
        always abandoned it."""
        loop = ServedLoop(2, dispatch_timeout=0.2 if how == "stall"
                          else DEADLINE)
        plan = FaultPlan([FaultSpec(site="device.stall", count=1, arg=2.0)]
                         if how == "stall" else [])
        if how == "error":
            inner, calls = loop.agg.windows._fetch_device, []

            def fetch_device(fn):
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError("the device is gone")
                return inner(fn)

            loop.agg.windows._fetch_device = fetch_device
        schedules = churn_schedule(3)
        try:
            with fault.installed(plan):
                loop.step(schedules[0])

                def failed() -> bool:
                    with loop.agg.windows._pipeline_lock:
                        return bool(loop.agg.windows._inflight) and \
                            loop.agg.windows._inflight[0].failure is not None

                wait_until(failed, "the publisher's failure")
                # the publisher neither publishes nor demotes
                assert loop.attributions() == 0
                assert loop.agg.windows._stats["window_demotions_total"] == 0
                loop.step(schedules[1])
                assert loop.attributions() == 1
                assert loop.agg.windows._rung == RUNG_PACKED_SERIAL
                assert loop.agg.windows._demotions_by_reason == {
                    "stall" if how == "stall" else "runtime_error": 1}
                loop.step(schedules[2])
        finally:
            loop.stop()
        stamps = [res.timestamp for res, _thread in loop.published]
        base = loop.agg.test_clock[0] - 15.0
        assert stamps == [base + 10.0, base + 15.0]  # each once, in order
        assert not loop.agg.windows._inflight
        assert loop.agg.windows._stats["window_demotions_total"] == 1

    def test_cancel_during_a_publication_drains_every_window(self):
        loop = ServedLoop(2, dispatch_timeout=DEADLINE)
        plan = FaultPlan([FaultSpec(site="device.stall", arg=0.3)])
        try:
            with fault.installed(plan):
                schedules = churn_schedule(3)
                loop.step(schedules[0])
                loop.step(schedules[1])
                loop.step(schedules[2])
                # the third window's fetch is under way (or about to be)
                wait_until(lambda: plan.fired("device.stall") >= 2,
                           "a publication in progress")
                loop.stop()
        finally:
            loop.stop()
        assert not loop.agg.windows._inflight
        assert loop.agg.windows._window_seq == 3
        assert loop.agg.windows._stats["attributions_total"] == 3
        stamps = [res.timestamp for res, _thread in loop.published]
        assert stamps == sorted(set(stamps)) and len(stamps) == 3

    def test_the_record_is_ordered_and_counts_the_early_publication(self):
        loop = ServedLoop(2, **KINDS["temporal"])
        try:
            for k, sched in enumerate(churn_schedule(3)):
                loop.step(sched)
                # as where the interval outlasts the program: the next
                # tick finds the window published
                wait_until(lambda: loop.attributions() == k + 1,
                           "the window's publication")
            body = json.loads(loop.agg._handle_window_debug(None)[2])
        finally:
            loop.stop()
        assert body["counts"]["windows"] == 3
        assert body["counts"]["published_early"] == 3
        fields = body["records"]["fields"]
        rows = [dict(zip(fields, row)) for row in body["records"]["rows"]]
        assert [r["seq"] for r in rows] == [0, 1, 2]
        for row in rows:
            marks = [row[m] for m in MARKS[1:]]  # no tick under the gate
            assert None not in marks and marks == sorted(marks)
            a, b = LEGS["window.queued"]
            assert row[b] - row[a] < loop.agg._interval

    def test_called_directly_only_the_serial_depth_publishes_early(self):
        """``published_early`` follows what happened, not a setting: at
        depth 1 a window is published by its own call; at depth 2, with no
        loop and so no publisher, by the next call or by a drain."""
        for depth, want in ((1, 3), (2, 1)):
            agg = make_agg(depth)
            run_schedule(agg, churn_schedule(3))
            assert agg.windows._stats["attributions_total"] == 3
            assert agg.windows._stats["published_early_total"] == want
            assert agg.windows._window_ledger.counts["published_early"] == want
            assert agg.windows._publisher is None
            agg.shutdown()
