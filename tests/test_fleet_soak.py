"""Fleet-plane churn soak.

The aggregator's nonce/seq/zone-union/staleness/cumulative logic is the
most state-heavy code in the tree; the unit tests exercise it case by
case. This soak drives ~100 simulated agents through restarts, network
reorders, delayed stragglers from dead runs, zone-set churn, and node
churn for 150 windows on the CPU mesh, asserting after EVERY window:

  * conservation — Σ workload energy == node active energy on every
    ratio-mode node (the reference's executable-spec invariant);
  * monotonicity — per-node cumulative joules never regress;
  * bounded state — superseded-run lists, report store, and history
    buffers never grow past their documented bounds.

In-process ingest (fake request objects) keeps the 10k+ reports fast; the
HTTP leg is covered by tests/test_fleet.py.
"""

from __future__ import annotations

import numpy as np

from kepler_tpu.fleet import Aggregator, encode_report
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport

ZONES_BASE = ("package", "dram")
ZONES_WIDE = ("package", "dram", "uncore")


class StubServer:
    def register(self, *a, **kw):
        pass


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class FakeRequest:
    command = "POST"

    def __init__(self, body: bytes):
        self.body = body


class SimAgent:
    """One simulated node agent: owns its run nonce, seq, zones, mode."""

    def __init__(self, name: str, rng: np.random.Generator,
                 mode: int) -> None:
        self.name = name
        self.rng = rng
        self.mode = mode
        self.seq = 0
        self.run = f"{name}-run-0"
        self.restarts = 0
        self.zones = ZONES_BASE
        self.dead_runs: list[str] = []

    def restart(self) -> None:
        self.dead_runs.append(self.run)
        self.restarts += 1
        self.run = f"{self.name}-run-{self.restarts}"
        self.seq = 0

    def report(self, w: int | None = None) -> tuple[bytes, int]:
        self.seq += 1
        w = w or int(self.rng.integers(1, 8))
        cpu = self.rng.uniform(0.1, 5.0, w).astype(np.float32)
        z = len(self.zones)
        r = NodeReport(
            node_name=self.name,
            zone_deltas_uj=self.rng.uniform(1e6, 1e8, z).astype(np.float32),
            zone_valid=np.ones(z, bool),
            usage_ratio=float(self.rng.uniform(0.1, 0.95)),
            cpu_deltas=cpu,
            workload_ids=[f"{self.name}-w{i}" for i in range(w)],
            # the informer computes node totals by summing proc deltas, so
            # conservation (Σ workload == active) is exact by construction
            node_cpu_delta=float(cpu.sum()),
            dt_s=5.0,
            mode=self.mode,
            workload_kinds=np.ones(w, np.int8),
        )
        return encode_report(r, list(self.zones), seq=self.seq,
                             run=self.run), self.seq

    def straggler_from_dead_run(self) -> bytes | None:
        """A delayed report carrying a SUPERSEDED run nonce."""
        if not self.dead_runs:
            return None
        cpu = np.asarray([1.0], np.float32)
        r = NodeReport(
            node_name=self.name,
            zone_deltas_uj=np.asarray([9e9, 9e9], np.float32),
            zone_valid=np.ones(2, bool), usage_ratio=0.5,
            cpu_deltas=cpu, workload_ids=[f"{self.name}-old"],
            node_cpu_delta=1.0, dt_s=5.0, mode=self.mode,
        )
        return encode_report(r, list(ZONES_BASE), seq=999,
                             run=self.dead_runs[-1])


class TestFleetChurnSoak:
    WINDOWS = 150
    AGENTS = 96

    def test_soak(self):
        clock = FakeClock()
        agg = Aggregator(StubServer(), interval=0, stale_after=15.0,
                         model_mode="mlp", node_bucket=8,
                         workload_bucket=8, clock=clock)
        agg.init()
        rng = np.random.default_rng(42)
        agents = {
            f"node-{i:03d}": SimAgent(
                f"node-{i:03d}", np.random.default_rng(1000 + i),
                MODE_RATIO if i % 2 == 0 else MODE_MODEL)
            for i in range(self.AGENTS)
        }
        joules_seen: dict[str, list[float]] = {}
        rejected_strugglers = 0
        conservation_checked = 0
        spawned = 0

        for win in range(self.WINDOWS):
            clock.t += 5.0
            # -- churn events ------------------------------------------
            names = sorted(agents)
            if win % 7 == 3:  # agent restarts (new run nonce, seq reset)
                for name in rng.choice(names, 3, replace=False):
                    agents[name].restart()
            if win % 11 == 5 and len(agents) > 90:  # node churn: leave
                for name in rng.choice(names, 2, replace=False):
                    del agents[name]
            if win % 11 == 7 and len(agents) < self.AGENTS:  # join
                spawned += 1
                name = f"fresh-{spawned:03d}"
                agents[name] = SimAgent(
                    name, np.random.default_rng(5000 + spawned),
                    MODE_RATIO)
            if win % 13 == 2:  # zone-set churn
                a = agents[sorted(agents)[int(rng.integers(len(agents)))]]
                a.zones = ZONES_WIDE if a.zones == ZONES_BASE else ZONES_BASE

            # -- every live agent reports ------------------------------
            for a in agents.values():
                body, _ = a.report()
                status, _, _ = agg._handle_report(FakeRequest(body))
                assert status == 204

            # -- hostile traffic ---------------------------------------
            if win % 5 == 1:  # straggler from a dead run → 409
                for a in agents.values():
                    blob = a.straggler_from_dead_run()
                    if blob is not None:
                        status, _, _ = agg._handle_report(FakeRequest(blob))
                        assert status == 409, "dead-run straggler accepted"
                        rejected_strugglers += 1
                        break
            if win % 6 == 2:  # same-run seq regression (network reorder)
                a = next(iter(agents.values()))
                old_seq = a.seq
                a.seq -= 2  # re-send an older window
                body, _ = a.report()
                agg._handle_report(FakeRequest(body))
                a.seq = old_seq
                stored = agg._reports[a.name]
                assert stored.seq == old_seq, "reordered report regressed seq"

            # -- aggregate + invariants --------------------------------
            result = agg.aggregate_once()
            assert result is not None
            with agg.windows._results_lock:
                results = {name: agg.windows._results.render_node(name)
                           for name in agg.windows._results.names}
            for name, row in results.items():
                if name not in agents:
                    continue  # node left mid-window; skip
                zl = row["zones"]
                node_e = np.asarray(row["node_energy_uj"], np.float64)
                if row["mode"] == MODE_RATIO and row["workloads"]:
                    wl_e = np.asarray(
                        [wl["energy_uj"] for wl in row["workloads"]],
                        np.float64)
                    # conservation: Σ workload == node active, per zone,
                    # where this node actually reported the zone
                    stored = agg._reports[name]
                    ratio = float(
                        np.clip(stored.report.usage_ratio, 0.0, 1.0))
                    active = node_e * ratio
                    got = wl_e.sum(axis=0)
                    mask = np.asarray(
                        [zn in stored.zone_names for zn in zl])
                    # 2e-3 covers the packed-f16 default path (watts are
                    # f16 on the wire-back: ~1e-3 quantization, inside
                    # the 0.5% budget the accuracy bench gates)
                    np.testing.assert_allclose(
                        got[mask], active[mask], rtol=2e-3, atol=10.0,
                        err_msg=f"conservation broke on {name} win {win}")
                    conservation_checked += 1
                # monotonic cumulative joules
                totals = dict(zip(zl, row["node_joules_total"]))
                hist = joules_seen.setdefault(name, [])
                prev = hist[-1] if hist else 0.0
                total_all = sum(totals.values())
                assert total_all >= prev - 1e-9, (
                    f"{name} joules regressed at win {win}")
                hist.append(total_all)

            # -- bounded state -----------------------------------------
            for runs in agg._superseded_runs.values():
                assert len(runs) <= agg._superseded_cap
            assert len(agg._reports) <= self.AGENTS + 8

        assert conservation_checked > 2000
        assert rejected_strugglers >= 10
        assert agg.windows._stats["attributions_total"] == self.WINDOWS
        assert agg._stats["rejected_total"] >= rejected_strugglers


class TestTemporalHistorySoak:
    """Temporal mode: history buffers must advance per report, survive
    restarts, and stay bounded through node churn."""

    def test_history_bounded_and_serving(self):
        clock = FakeClock()
        agg = Aggregator(StubServer(), interval=0, stale_after=15.0,
                         model_mode="temporal", node_bucket=8,
                         workload_bucket=8, history_window=4, clock=clock)
        agg.init()
        agents = {
            f"t-{i}": SimAgent(f"t-{i}", np.random.default_rng(i),
                               MODE_MODEL)
            for i in range(12)
        }
        for win in range(30):
            clock.t += 5.0
            if win == 10:
                agents["t-3"].restart()
            if win == 15:
                del agents["t-5"]
            for a in agents.values():
                body, _ = a.report(w=3)
                status, _, _ = agg._handle_report(FakeRequest(body))
                assert status == 204
            result = agg.aggregate_once()
            assert result is not None
            assert np.isfinite(np.asarray(result.wl_power_uw)).all()
            for _, buf in agg._history.values():
                assert buf.window == 4  # ring never grows
        assert "t-5" not in agg._history  # evicted with its node
        assert len(agg._history) == len(agents)
