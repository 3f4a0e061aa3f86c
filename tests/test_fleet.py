"""Fleet-plane tests: wire format, agent→aggregator over real HTTP, the
aggregator's zone alignment/staleness/metrics — the "synthetic fleet"
fixture strategy from SURVEY §4 (no real nodes needed)."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from kepler_tpu.fleet import (
    Aggregator,
    FleetAgent,
    WireError,
    decode_report,
    encode_report,
)
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext


def make_report(name="node-a", w=3, z=2, mode=MODE_RATIO, seed=0,
                meta_pad=None):
    rng = np.random.default_rng(seed)
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    meta = {"os": "linux"}
    if meta_pad is not None:
        # size-boundary tests: pad the wire body to an exact byte length
        meta["pad"] = meta_pad
    return NodeReport(
        node_name=name,
        zone_deltas_uj=rng.uniform(1e6, 1e8, z).astype(np.float32),
        zone_valid=np.ones(z, bool),
        usage_ratio=0.6,
        cpu_deltas=cpu,
        workload_ids=[f"{name}-w{i}" for i in range(w)],
        node_cpu_delta=float(cpu.sum()),
        dt_s=5.0,
        mode=mode,
        workload_kinds=np.ones(w, np.int8),
        meta=meta,
    )


class TestWire:
    def test_roundtrip(self):
        report = make_report()
        blob = encode_report(report, ["package", "dram"], seq=7)
        decoded, header = decode_report(blob)
        assert header["seq"] == 7
        assert header["zone_names"] == ["package", "dram"]
        assert decoded.node_name == report.node_name
        np.testing.assert_array_equal(decoded.zone_deltas_uj,
                                      report.zone_deltas_uj)
        np.testing.assert_array_equal(decoded.cpu_deltas, report.cpu_deltas)
        np.testing.assert_array_equal(decoded.workload_kinds,
                                      report.workload_kinds)
        assert decoded.workload_ids == report.workload_ids
        assert decoded.meta == {"os": "linux"}
        assert decoded.mode == MODE_RATIO
        assert decoded.dt_s == 5.0

    def test_roundtrip_without_kinds(self):
        report = make_report()
        report.workload_kinds = None
        decoded, _ = decode_report(encode_report(report, ["package", "dram"]))
        assert decoded.workload_kinds is None

    @pytest.mark.parametrize("mutate", [
        lambda b: b[:4],  # truncated magic
        lambda b: b"XXXX" + b[4:],  # bad magic
        lambda b: b[: len(b) // 2],  # truncated arrays
        lambda b: b.replace(b'"v":1', b'"v":9'),  # bad version
        lambda b: b.replace(b"float32", b"object_", 1),  # evil dtype
    ])
    def test_rejects_malformed(self, mutate):
        blob = encode_report(make_report(), ["package", "dram"])
        with pytest.raises(WireError):
            decode_report(mutate(blob))

    def test_rejects_non_string_zone_names(self):
        blob = encode_report(make_report(z=2), ["package", "dram"])
        # same byte length so the header length prefix stays valid
        bad = blob.replace(b'"zone_names":["package","dram"]',
                           b'"zone_names":["package",123456]')
        with pytest.raises(WireError):
            decode_report(bad)

    def test_rejects_length_mismatch(self):
        report = make_report(w=3)
        report.workload_ids = ["only-one"]
        with pytest.raises(WireError):
            decode_report(encode_report(report, ["package", "dram"]))

    def test_restamp_ring_fields_roundtrip(self):
        """The HA-ingest transmit stamps (owner/epoch/acked_through)
        rewrite only the header; arrays pass through untouched."""
        from kepler_tpu.fleet.wire import peek_identity, restamp_transmit

        report = make_report()
        blob = encode_report(report, ["package", "dram"], seq=9, run="r1")
        stamped = restamp_transmit(blob, 123.0, owner="10.0.0.2:28283",
                                   epoch=4, acked_through=8)
        decoded, header = decode_report(stamped)
        assert header["owner"] == "10.0.0.2:28283"
        assert header["epoch"] == 4
        assert header["acked_through"] == 8
        assert header["sent_at"] == 123.0
        assert header["seq"] == 9
        np.testing.assert_array_equal(decoded.zone_deltas_uj,
                                      report.zone_deltas_uj)
        assert peek_identity(stamped) == ("r1", 9)
        assert peek_identity(b"garbage") == ("", 0)


@pytest.fixture()
def server():
    s = APIServer(listen_addresses=["127.0.0.1:0"])
    s.init()
    ctx = CancelContext()
    import threading
    t = threading.Thread(target=s.run, args=(ctx,), daemon=True)
    t.start()
    time.sleep(0.05)
    yield s
    ctx.cancel()
    s.shutdown()


def post_report(server, report, zones=("package", "dram"), seq=1, run=""):
    host, port = server.addresses[0]
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/report",
        data=encode_report(report, list(zones), seq=seq, run=run),
        method="POST")
    return urllib.request.urlopen(req, timeout=5)


class TestAggregator:
    def test_ingest_and_aggregate(self, server):
        agg = Aggregator(server, model_mode="mlp", node_bucket=8,
                         workload_bucket=16)
        agg.init()
        resp = post_report(server, make_report("node-a", mode=MODE_RATIO))
        assert resp.status == 204
        post_report(server, make_report("node-b", mode=MODE_MODEL, seed=1))
        result = agg.aggregate_once()
        assert result is not None
        host, port = server.addresses[0]
        with urllib.request.urlopen(
                f"http://{host}:{port}/v1/results", timeout=5) as r:
            payload = json.loads(r.read())
        assert set(payload["nodes"]) == {"node-a", "node-b"}
        a = payload["nodes"]["node-a"]
        assert a["zones"] == ["dram", "package"]  # canonical sorted union
        assert len(a["workloads"]) == 3
        assert all(np.isfinite(w["power_uw"]).all() for w in a["workloads"])
        # ratio node: conservation Σ workload power == node active power
        node_b = payload["nodes"]["node-b"]
        assert node_b["mode"] == MODE_MODEL
        assert payload["stats"]["attributions_total"] == 1

    def test_ratio_conservation_through_wire(self, server):
        # accuracy mode = the einsum-f32 serial path: this test pins
        # conservation at f32 tightness (1e-4); the packed-f16 default
        # path is held to the 0.5% budget in test_window_pipeline.py
        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16, accuracy_mode=True)
        agg.init()
        report = make_report("node-a", w=4)
        post_report(server, report)
        agg.aggregate_once()
        host, port = server.addresses[0]
        with urllib.request.urlopen(
                f"http://{host}:{port}/v1/results?node=node-a", timeout=5) as r:
            res = json.loads(r.read())
        total_wl = np.sum([w["energy_uj"] for w in res["workloads"]], axis=0)
        # zones arrive sorted; map report zones (package, dram) → canonical
        active = np.zeros(2)
        for j, zn in enumerate(["package", "dram"]):
            i = res["zones"].index(zn)
            active[i] = report.zone_deltas_uj[j] * report.usage_ratio
        np.testing.assert_allclose(total_wl, active, rtol=1e-4)

    def test_zone_union_alignment(self, server):
        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.init()
        post_report(server, make_report("node-a", z=2),
                    zones=("package", "dram"))
        post_report(server, make_report("node-b", z=1), zones=("psys",))
        agg.aggregate_once()
        host, port = server.addresses[0]
        with urllib.request.urlopen(
                f"http://{host}:{port}/v1/results", timeout=5) as r:
            payload = json.loads(r.read())
        assert payload["nodes"]["node-a"]["zones"] == [
            "dram", "package", "psys"]
        # node-a has no psys → zero power there
        a = payload["nodes"]["node-a"]
        assert a["node_power_uw"][a["zones"].index("psys")] == 0.0
        b = payload["nodes"]["node-b"]
        assert b["node_power_uw"][b["zones"].index("psys")] > 0.0

    def test_stale_nodes_fall_out(self, server):
        now = [1000.0]
        agg = Aggregator(server, model_mode=None, stale_after=15.0,
                         clock=lambda: now[0], node_bucket=8,
                         workload_bucket=16)
        agg.init()
        post_report(server, make_report("node-a"))
        post_report(server, make_report("node-b", seed=1))
        agg.aggregate_once()
        assert agg.windows._stats["last_batch_nodes"] == 2
        now[0] += 10.0
        post_report(server, make_report("node-b", seed=2), seq=2)
        now[0] += 10.0  # node-a now 20s old, node-b 10s old
        agg.aggregate_once()
        assert agg.windows._stats["last_batch_nodes"] == 1
        with agg.windows._results_lock:
            assert set(agg.windows._results.names) == {"node-b"}

    def test_rejects_garbage_post(self, server):
        agg = Aggregator(server, model_mode=None)
        agg.init()
        host, port = server.addresses[0]
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/report", data=b"not a report",
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5)
        assert err.value.code == 400
        assert agg._stats["rejected_total"] == 1

    def test_oversized_post_rejected_without_buffering(self, server):
        agg = Aggregator(server, model_mode=None)
        agg.init()
        host, port = server.addresses[0]
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/report", data=b"x",
            headers={"Content-Length": str(10**10)}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5)
        assert err.value.code == 413

    def test_cumulative_survives_missed_batch(self, server):
        now = [1000.0]
        agg = Aggregator(server, model_mode=None, stale_after=15.0,
                         clock=lambda: now[0], node_bucket=8,
                         workload_bucket=16)
        agg.init()
        def cum(agg, name):
            return dict(zip(agg.windows._cum_zones,
                            agg.windows._cum.value(name).tolist()))

        post_report(server, make_report("node-a"))
        agg.aggregate_once()
        before = cum(agg, "node-a")
        now[0] += 100.0  # node-a silent past stale_after but < retention
        post_report(server, make_report("node-b", seed=1))
        agg.aggregate_once()
        assert cum(agg, "node-a") == before  # kept
        now[0] += 10.0
        post_report(server, make_report("node-a", seed=2), seq=2)
        agg.aggregate_once()
        for zone, uj in cum(agg, "node-a").items():
            assert uj >= before.get(zone, 0.0)  # accumulated, not reset

    def test_stale_after_accepts_duration_string(self, tmp_path):
        from kepler_tpu.config.config import from_file
        path = tmp_path / "cfg.yaml"
        path.write_text(
            "aggregator:\n  interval: 2s\n  stale-after: 15s\n")
        cfg = from_file(str(path))
        assert cfg.aggregator.interval == 2.0
        assert cfg.aggregator.stale_after == 15.0

    def test_prometheus_families(self, server):
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest

        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.init()
        post_report(server, make_report("node-a"))
        agg.aggregate_once()
        registry = CollectorRegistry()
        registry.register(agg)
        text = generate_latest(registry).decode()
        assert "kepler_fleet_nodes 1.0" in text
        assert 'kepler_fleet_node_cpu_watts{mode="ratio",node_name="node-a"'
        assert "kepler_fleet_attributions_total 1.0" in text
        assert "kepler_fleet_node_cpu_watts" in text

    def test_model_params_reinit_on_zone_mismatch(self, server):
        import jax
        from kepler_tpu.models import init_mlp

        agg = Aggregator(server, model_mode="mlp",
                         model_params=init_mlp(jax.random.PRNGKey(0),
                                               n_zones=5),
                         node_bucket=8, workload_bucket=16)
        agg.init()
        post_report(server, make_report("node-a", mode=MODE_MODEL))
        result = agg.aggregate_once()  # fleet has 2 zones, params have 5
        assert result is not None
        # trained params survive the mismatch; an untrained fallback served
        # the window (review finding: transient zone changes must not
        # destroy loaded params)
        assert agg.windows._model_out_dim() == 5
        assert 2 in agg.windows._fallback_params


class FakeMeterMonitor:
    """Minimal monitor stand-in exposing add_window_listener."""

    def __init__(self):
        self.listeners = []

    def add_window_listener(self, fn):
        self.listeners.append(fn)

    def emit(self, sample):
        for fn in self.listeners:
            fn(sample)


def make_sample(ts=100.0):
    from kepler_tpu.monitor.monitor import WindowSample
    from kepler_tpu.resource.informer import FeatureBatch

    cpu = np.asarray([1.0, 2.0], np.float32)
    batch = FeatureBatch(
        kinds=np.asarray([0, 1], np.int8),
        ids=["p1", "c1"],
        cpu_deltas=cpu,
        node_cpu_delta=3.0,
        usage_ratio=0.5,
    )
    return WindowSample(
        timestamp=ts, dt_s=5.0, zone_names=("package", "dram"),
        zone_deltas_uj=np.asarray([1e7, 2e7]),
        zone_valid=np.ones(2, bool), usage_ratio=0.5, batch=batch)


class TestFleetMetricsHandler:
    def test_both_formats_byte_identical_to_stock(self, server):
        """The aggregator's /metrics handler (make_registry_handler)
        serves BOTH negotiated formats through the fast renderers —
        byte-identical to prometheus_client's stock/OM renderers over a
        live fleet registry."""
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest
        from prometheus_client.openmetrics.exposition import (
            generate_latest as om_latest,
        )

        from kepler_tpu.exporter.prometheus.exporter import (
            make_registry_handler,
        )

        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.init()
        post_report(server, make_report("node-a"))
        post_report(server, make_report("node-b", seed=1))
        agg.aggregate_once()
        registry = CollectorRegistry()
        registry.register(agg)
        handler = make_registry_handler(registry)

        class Classic:
            headers = {"Accept": "text/plain"}

        class OM:
            headers = {"Accept": ("application/openmetrics-text;"
                                  "version=1.0.0;q=0.5,text/plain;q=0.3")}

        status, hdrs, body = handler(Classic())
        assert status == 200 and "text/plain" in hdrs["Content-Type"]
        assert body == generate_latest(registry)
        assert b"kepler_fleet_node_cpu_watts" in body

        status, hdrs, body = handler(OM())
        assert status == 200
        assert "openmetrics-text" in hdrs["Content-Type"]
        assert body == om_latest(registry)
        assert body.endswith(b"# EOF\n")

        # bare request objects (tests, curl without Accept) get classic
        status, hdrs, body = handler(None)
        assert status == 200 and body == generate_latest(registry)

    def test_om_fast_renderer_edge_parity(self):
        """fast_generate_openmetrics promises byte-identity-or-fallback;
        pin the edges review found: colon names (stock underscore-escapes
        them → must fall back) and quoted HELP docs (OM escapes quotes,
        classic does not)."""
        from prometheus_client import CollectorRegistry
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )
        from prometheus_client.openmetrics.exposition import (
            generate_latest as om_latest,
        )

        from kepler_tpu.exporter.prometheus.fastexpo import (
            fast_generate_openmetrics,
        )

        class Fams:
            def __init__(self, fams):
                self.fams = fams

            def collect(self):
                yield from self.fams

        counter = CounterMetricFamily("kepler_a", "plain", labels=["l"])
        counter.add_metric(["v"], 3.5)
        for fams in (
            [GaugeMetricFamily("job:foo:rate", "recording-rule name")],
            [GaugeMetricFamily("x", 'doc with "quote" and \\ and \nnl')],
            [counter],
        ):
            registry = CollectorRegistry()
            registry.register(Fams(fams))
            assert (fast_generate_openmetrics(registry)
                    == om_latest(registry)), fams[0].name


class TestAgent:
    def test_agent_end_to_end(self, server):
        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.init()
        monitor = FakeMeterMonitor()
        host, port = server.addresses[0]
        agent = FleetAgent(monitor, endpoint=f"{host}:{port}",
                           node_name="test-node")
        agent.init()
        assert monitor.listeners  # subscribed
        monitor.emit(make_sample())
        # drain the queue synchronously (run() would do this in a thread)
        seq, sample, _emitted, _trace = agent._queue.popleft()
        agent._send(sample, seq)
        result = agg.aggregate_once()
        assert result is not None
        with agg.windows._results_lock:
            res = agg.windows._results.render_node("test-node")
        assert [w["id"] for w in res["workloads"]] == ["p1", "c1"]
        # workload kinds survive the wire
        assert [w["kind"] for w in res["workloads"]] == [0, 1]

    def test_agent_authenticates_to_protected_aggregator(self):
        # aggregator behind web-config basic auth: creds ride in the
        # endpoint URL userinfo (kepler_tpu/server/webconfig.py)
        import base64
        import http.client

        from kepler_tpu.server.shacrypt import sha_crypt
        from kepler_tpu.server.webconfig import make_authenticator

        hashed = sha_crypt("pw", "$5$rounds=1000$fleetauthsalt")
        s = APIServer(listen_addresses=["127.0.0.1:0"],
                      basic_auth_check=make_authenticator({"agent": hashed}))
        s.init()
        ctx = CancelContext()
        import threading
        threading.Thread(target=s.run, args=(ctx,), daemon=True).start()
        time.sleep(0.05)
        try:
            agg = Aggregator(s, model_mode=None, node_bucket=8,
                             workload_bucket=16)
            agg.init()
            monitor = FakeMeterMonitor()
            host, port = s.addresses[0]
            # without credentials: 401 surfaces as HTTPException
            bare = FleetAgent(monitor, endpoint=f"{host}:{port}",
                              node_name="n1")
            bare.init()
            monitor.emit(make_sample())
            with pytest.raises(http.client.HTTPException, match="401"):
                seq, sample, _emitted, _trace = bare._queue.popleft()
                bare._send(sample, seq)
            # with credentials in the URL: accepted
            authed = FleetAgent(monitor,
                                endpoint=f"http://agent:pw@{host}:{port}",
                                node_name="n1")
            assert authed._auth_header == "Basic " + base64.b64encode(
                b"agent:pw").decode()
            authed.init()
            monitor.emit(make_sample())
            seq, sample, _emitted, _trace = authed._queue.popleft()
            authed._send(sample, seq)
            assert agg.aggregate_once() is not None
        finally:
            ctx.cancel()
            s.shutdown()

    def test_agent_survives_down_aggregator(self):
        monitor = FakeMeterMonitor()
        agent = FleetAgent(monitor, endpoint="127.0.0.1:9",  # discard port
                           node_name="test-node", timeout_s=0.2)
        agent.init()
        monitor.emit(make_sample())
        seq, sample, _emitted, _trace = agent._queue.popleft()
        with pytest.raises(OSError):
            agent._send(sample, seq)  # run() catches this and logs

    def test_agent_run_loop_drains(self, server):
        agg = Aggregator(server, model_mode=None, node_bucket=8,
                         workload_bucket=16)
        agg.init()
        monitor = FakeMeterMonitor()
        host, port = server.addresses[0]
        agent = FleetAgent(monitor, endpoint=f"http://{host}:{port}",
                           node_name="loop-node")
        agent.init()
        ctx = CancelContext()
        import threading
        t = threading.Thread(target=agent.run, args=(ctx,), daemon=True)
        t.start()
        monitor.emit(make_sample())
        deadline = time.time() + 5
        while time.time() < deadline:
            with agg._lock:
                if "loop-node" in agg._reports:
                    break
            time.sleep(0.02)
        ctx.cancel()
        agent.shutdown()
        t.join(timeout=2)
        with agg._lock:
            assert "loop-node" in agg._reports

    def test_bad_endpoint_rejected(self):
        with pytest.raises(ValueError):
            FleetAgent(FakeMeterMonitor(), endpoint="nonsense")


class TestParamsPersistence:
    def test_save_load_roundtrip(self, tmp_path):
        import jax
        from kepler_tpu.models import init_mlp
        from kepler_tpu.models.estimator import load_params, save_params

        params = init_mlp(jax.random.PRNGKey(0), n_zones=3)
        path = str(tmp_path / "params.npz")
        save_params(path, params)
        loaded = load_params(path)
        assert set(loaded) == set(params)
        for k in params:
            np.testing.assert_array_equal(np.asarray(loaded[k]),
                                          np.asarray(params[k]))


class TestTemporalAggregator:
    def test_history_accretes_per_node(self, server):
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        for seq in range(1, 4):
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=seq)
        _, buf = agg._history["node-a"]
        feats, tv = buf.window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, True, True, False]

    def test_temporal_attribution_end_to_end(self, server):
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        # mixed fleet: ratio node + model node, several windows of history
        for seq in range(1, 4):
            post_report(server, make_report("node-r", mode=MODE_RATIO),
                        seq=seq)
            post_report(server, make_report("node-m", mode=MODE_MODEL,
                                            seed=seq), seq=seq)
        result = agg.aggregate_once()
        assert result is not None
        host, port = server.addresses[0]
        with urllib.request.urlopen(
                f"http://{host}:{port}/v1/results", timeout=5) as r:
            payload = json.loads(r.read())
        # ratio node unaffected by the estimator: conservation holds
        rnode = payload["nodes"]["node-r"]
        assert rnode["mode"] == MODE_RATIO
        assert all(np.isfinite(w["power_uw"]).all()
                   for w in rnode["workloads"])
        mnode = payload["nodes"]["node-m"]
        assert mnode["mode"] == MODE_MODEL
        assert all(np.isfinite(w["power_uw"]).all()
                   for w in mnode["workloads"])
        # node totals for the model node = Σ workload power
        total = np.sum([w["power_uw"] for w in mnode["workloads"]], axis=0)
        np.testing.assert_allclose(total, mnode["node_power_uw"], rtol=1e-3)

    def test_stale_node_history_pruned(self, server):
        clock = [1000.0]
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4,
                         stale_after=10.0, clock=lambda: clock[0])
        agg.init()
        post_report(server, make_report("node-a", mode=MODE_MODEL))
        assert "node-a" in agg._history
        clock[0] += 60.0
        agg.aggregate_once()
        assert "node-a" not in agg._history

    def test_duplicate_seq_does_not_duplicate_history(self, server):
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        for _ in range(2):  # LB retry redelivers the same seq
            post_report(server, make_report("node-a", mode=MODE_MODEL), seq=1)
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, False, False, False]

    def test_restart_with_same_seq_still_pushes_history(self, server):
        # an agent restart that re-sends the previous run's seq value must
        # advance the temporal window (a new run nonce marks the restart)
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="run-1")
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="run-2")  # restarted agent, same seq
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, True, False, False]

    def test_superseded_run_straggler_rejected(self, server):
        # a network-delayed report from the PREVIOUS agent run arriving
        # after the new run's reports must NOT be classified as yet another
        # restart (advisor r2): it would overwrite the fresher run and, in
        # temporal mode, push a spurious history window — and alternating
        # stragglers would flip-flop the stored run forever
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=7, run="run-1")
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="run-2")  # genuine restart
        with pytest.raises(urllib.error.HTTPError) as exc:
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=8, run="run-1")  # old run's straggler
        assert exc.value.code == 409
        assert agg._reports["node-a"].run == "run-2"
        assert agg._reports["node-a"].seq == 1
        # exactly two windows pushed (run-1 seq=7, run-2 seq=1) — the
        # straggler must not have advanced the temporal window
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, True, False, False]
        # and the next report from the LIVE run still lands normally
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=2, run="run-2")
        assert agg._reports["node-a"].seq == 2

    def test_straggler_from_two_runs_back_rejected(self, server):
        # reviewer repro: with only the LAST superseded run remembered, a
        # straggler from TWO runs back is accepted as a "restart" and then
        # marks the LIVE run as superseded — every later live report 409s
        # until the next real restart. The superseded list must remember
        # all dead runs (bounded).
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=8)
        agg.init()
        for run in ("run-1", "run-2", "run-3"):
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=1, run=run)
        with pytest.raises(urllib.error.HTTPError) as exc:
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=9, run="run-1")  # two runs back
        assert exc.value.code == 409
        assert agg._reports["node-a"].run == "run-3"
        # the LIVE run must still be accepted afterwards
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=2, run="run-3")
        assert agg._reports["node-a"].seq == 2
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].sum() == 4  # 3 restarts + seq advance, no straggler

    def test_results_node_query_url_decoded(self, server):
        # node names with URL-encoded characters must round-trip through
        # /v1/results?node=… (weak r2 #5)
        agg = Aggregator(server, model_mode="mlp", node_bucket=8,
                         workload_bucket=16)
        agg.init()
        post_report(server, make_report("rack 1/node-a", mode=MODE_RATIO))
        agg.aggregate_once()
        host, port = server.addresses[0]
        from urllib.parse import quote
        with urllib.request.urlopen(
                f"http://{host}:{port}/v1/results?node="
                f"{quote('rack 1/node-a', safe='')}", timeout=5) as r:
            payload = json.loads(r.read())
        assert len(payload["workloads"]) == 3

    def test_same_run_reordered_first_seq_rejected(self, server):
        # a network-duplicated copy of seq=1 arriving after seq=3 within
        # ONE run is a reorder, not a restart: it must neither regress the
        # stored report nor re-push the temporal window
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        for seq in (1, 2, 3):
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=seq, run="run-1")
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="run-1")  # late duplicate of the first
        assert agg._reports["node-a"].seq == 3
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, True, True, False]

    def test_same_run_duplicate_seq_not_pushed_twice(self, server):
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        for _ in range(2):  # retransmission within ONE run
            post_report(server, make_report("node-a", mode=MODE_MODEL),
                        seq=1, run="run-1")
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, False, False, False]

    def test_ratio_nodes_accrete_no_history(self, server):
        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        post_report(server, make_report("metal", mode=MODE_RATIO))
        assert "metal" not in agg._history

    def test_history_windows_equal_the_per_pod_oracle(self, server):
        """A small fleet through real ingest — a wrapped model node, one
        whose pods changed, a young one, a ratio node, and a node the
        aggregator holds no history of: the [N, W, T, F] assembly equals
        what per-pod buffers fed the same stored reports give, bit for bit,
        and every other row is zero."""
        import dataclasses

        from kepler_tpu.parallel.fleet import assemble_fleet_batch
        from kepler_tpu.resource.informer import FeatureBatch
        from tests.test_ring import PerPodHistory, same_bits

        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)
        agg.init()
        oracles: dict[str, PerPodHistory] = {}

        def send(report, seq):
            post_report(server, report, seq=seq)
            got = agg._reports[report.node_name].report  # as decoded
            if got.mode == MODE_MODEL:
                oracles.setdefault(got.node_name, PerPodHistory(4)).push(
                    FeatureBatch(
                        kinds=got.workload_kinds, ids=got.workload_ids,
                        cpu_deltas=np.asarray(got.cpu_deltas, np.float32),
                        node_cpu_delta=float(got.node_cpu_delta),
                        usage_ratio=float(got.usage_ratio)),
                    float(got.dt_s))

        for seq in range(1, 7):
            send(make_report("m-wrapped", w=5, mode=MODE_MODEL, seed=seq),
                 seq)
            send(make_report("r-ratio", w=4, mode=MODE_RATIO, seed=seq), seq)
            changed = make_report("m-changed", w=5, mode=MODE_MODEL,
                                  seed=seq)
            if seq > 3:  # two pods gone, two new, one moved to the front
                changed = dataclasses.replace(changed, workload_ids=[
                    "m-changed-w4", "m-changed-w0", "m-changed-w1",
                    "m-changed-w5", "m-changed-w6"])
            send(changed, seq)
            if seq > 4:
                send(make_report("m-young", w=3, mode=MODE_MODEL, seed=seq),
                     seq)
        assert sorted(agg._history) == ["m-changed", "m-wrapped", "m-young"]
        reports = [agg._reports[name].report for name in sorted(
            agg._reports)] + [make_report("m-unheard", w=2, mode=MODE_MODEL)]
        batch = assemble_fleet_batch(reports, n_zones=2, node_bucket=8,
                                     workload_bucket=16)
        hist, tv = agg._history_windows(batch)
        assert hist.shape == (8, 16, 4, 7) and hist.dtype == np.float32
        assert tv.shape == (8, 16, 4) and tv.dtype == np.bool_
        want_hist, want_tv = np.zeros_like(hist), np.zeros_like(tv)
        for i, report in enumerate(reports):
            oracle = oracles.get(report.node_name)
            if oracle is not None:
                k = len(report.workload_ids)
                want_hist[i, :k], want_tv[i, :k] = oracle.window_arrays(
                    report.workload_ids)
        assert same_bits(hist, want_hist) and same_bits(tv, want_tv)
        counts = {r.node_name: int(tv[i].sum())
                  for i, r in enumerate(reports)}
        assert counts == {"m-changed": 3 * 4 + 2 * 3, "m-wrapped": 5 * 4,
                          "m-young": 3 * 2, "r-ratio": 0, "m-unheard": 0}

    def test_a_push_beside_the_assembly_never_tears_a_window(self, server):
        """One thread pushes a node's history (every pod's row of push k
        reads k) while another assembles windows: each assembled window is
        the same run of consecutive pushes for every pod — never a row of a
        push whose cursor is not advanced yet, never half the pods ahead.
        The per-node lock around push and window_arrays is what holds it."""
        import dataclasses
        import sys
        import threading

        from kepler_tpu.parallel.fleet import assemble_fleet_batch

        agg = Aggregator(server, model_mode="temporal", node_bucket=8,
                         workload_bucket=16, history_window=4)

        def report(k):
            base = make_report("node-a", w=12, mode=MODE_MODEL)
            return dataclasses.replace(
                base, cpu_deltas=np.full(12, float(k), np.float32))

        for k in range(1, 5):
            agg._push_history(report(k))
        batch = assemble_fleet_batch([report(0)], n_zones=2, node_bucket=8,
                                     workload_bucket=16)
        stop = threading.Event()
        pushed = [4]

        def pusher():
            while not stop.is_set() and pushed[0] < 200_000:
                agg._push_history(report(pushed[0] + 1))
                pushed[0] += 1

        old_interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        thread = threading.Thread(target=pusher, daemon=True)
        thread.start()
        try:
            deadline = time.monotonic() + 2.0
            windows = 0
            while time.monotonic() < deadline and windows < 2000:
                hist, tv = agg._history_windows(batch)
                assert tv[0, :12].all()
                ticks = hist[0, :12, :, 0]
                assert (ticks == ticks[0]).all(), ticks
                assert (np.diff(ticks[0]) == 1.0).all(), ticks[0]
                windows += 1
        finally:
            stop.set()
            thread.join(timeout=30)
            sys.setswitchinterval(old_interval)
        assert not thread.is_alive()
        assert windows > 50 and pushed[0] > 50

    def test_window_longer_than_params_rejected_at_startup(self, server):
        import jax

        from kepler_tpu.models import init_temporal

        params = {k: np.asarray(v) for k, v in init_temporal(
            jax.random.PRNGKey(0), 2, d_model=32, t_max=8).items()}
        agg = Aggregator(server, model_mode="temporal", history_window=16,
                         model_params=params)
        with pytest.raises(ValueError, match="t_max"):
            agg.init()


class TestWireFuzz:
    def test_random_mutations_never_crash(self):
        """Any corrupted report must raise WireError/ValueError — never
        segfault, hang, or propagate random exceptions into the server."""
        rng = np.random.default_rng(0)
        blob = bytearray(encode_report(make_report(w=6, z=3),
                                       ["a", "b", "c"], seq=3))
        for _ in range(300):
            mutated = bytearray(blob)
            for _ in range(rng.integers(1, 8)):
                op = rng.integers(0, 3)
                if op == 0 and len(mutated) > 1:  # flip byte
                    mutated[rng.integers(0, len(mutated))] = rng.integers(
                        0, 256)
                elif op == 1 and len(mutated) > 8:  # truncate
                    mutated = mutated[: rng.integers(1, len(mutated))]
                else:  # append garbage
                    mutated += bytes(rng.integers(0, 256, 16).tolist())
            try:
                report, header = decode_report(bytes(mutated))
            except (WireError, ValueError):
                continue
            # a mutation that still decodes must yield a well-formed report
            assert len(report.workload_ids) == report.cpu_deltas.shape[0]
            assert report.zone_deltas_uj.shape == report.zone_valid.shape

    def test_truncation_sweep_never_crashes(self):
        blob = encode_report(make_report(), ["package", "dram"])
        for n in range(len(blob)):
            with pytest.raises((WireError, ValueError)):
                decode_report(blob[:n])


class TestParamsFeatureDimCheck:
    def test_stale_feature_dim_fails_at_startup(self):
        """A checkpoint trained before a feature-set change (F mismatch on
        the input projection) must fail at _check_params_shape, not as an
        XLA shape error inside the first window."""
        import jax

        from kepler_tpu.models import init_mlp

        params = {k: np.asarray(v) for k, v in
                  init_mlp(jax.random.PRNGKey(0), 2,
                           n_features=6).items()}  # pre-F=7 checkpoint
        agg = Aggregator(APIServer(), model_mode="mlp", model_params=params)
        with pytest.raises(ValueError, match="feature dim"):
            agg.windows._check_params_shape()

    def test_current_feature_dim_passes(self):
        import jax

        from kepler_tpu.models import init_mlp

        params = {k: np.asarray(v) for k, v in
                  init_mlp(jax.random.PRNGKey(0), 2).items()}
        Aggregator(APIServer(), model_mode="mlp",
                   model_params=params).windows._check_params_shape()
