"""Multi-host SPMD fleet window (ISSUE 15): the in-process virtual-host
tier.

The real two-process gate (``make multihost`` / ``tests/test_multihost``)
needs a jax build with the Gloo multi-process CPU backend; everything the
multi-host ENGINE guarantees — host-local staging and delta H2D, global
assembly from local shards, bucket agreement, owned-rows publish fetch,
mesh-derived ingest ownership, the "mesh minus one host" demotion — is
pinned HERE with a virtual topology: two ``MultiHostWindowEngine``\\ s in
one process, each claiming half the simulated devices as "local", wired
through a :class:`HostLocalFabric` standing in for the DCN exchanges.
Because every device is addressable in one process, the SPMD dispatch
actually runs, so bit-consistency against the single-host
``ShardedWindowEngine`` is a real check, not a mock.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from kepler_tpu.fleet.aggregator import Aggregator
from kepler_tpu.fleet.scheduler import (RUNG_NAME_MESH_DEGRADED,
                                        RUNG_NAME_MULTIHOST, RUNG_PIPELINED)
from kepler_tpu.fleet.ring import (HashRing, MeshRing, RingError,
                                   ring_from_mesh)
from kepler_tpu.fleet.window import (DeviceWindowError, HostLocalFabric,
                                     MultiHostWindowEngine, RowInput,
                                     ShardedWindowEngine)
from kepler_tpu.parallel.fleet import MODE_MODEL, NodeReport
from kepler_tpu.parallel.mesh import (MultihostInit, initialize_multihost,
                                      make_mesh, multihost_status)
from kepler_tpu.server.http import APIServer

ZONES = ("package", "dram")
PEERS = ["127.0.0.1:28291", "127.0.0.1:28292"]


def _jax():
    import jax

    return jax


def make_report(name: str, seed: int, w: int = 4,
                mode: int = 0) -> NodeReport:
    rng = np.random.default_rng(abs(hash((name, seed))) % (2 ** 32))
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    return NodeReport(
        node_name=name,
        zone_deltas_uj=rng.uniform(1e7, 5e8, len(ZONES)).astype(
            np.float32),
        zone_valid=np.ones(len(ZONES), bool),
        usage_ratio=float(rng.uniform(0.2, 0.9)),
        cpu_deltas=cpu,
        workload_ids=[f"{name}-w{k}" for k in range(w)],
        node_cpu_delta=float(cpu.sum()),
        dt_s=5.0,
        mode=mode,
        workload_kinds=np.ones(w, np.int8),
    )


def make_rows(names: list[str], seq: int,
              zones: tuple = ZONES) -> list[RowInput]:
    rows = []
    for i, name in enumerate(names):
        rep = make_report(name, seq * 1000 + i,
                          mode=MODE_MODEL if i % 2 else 0)
        rows.append(RowInput(name=name, report=rep, zone_names=zones,
                             ident=("run", seq)))
    return rows


def virtual_topology(n_hosts: int = 2):
    """(mesh, device_process fn) splitting the simulated devices evenly
    over ``n_hosts`` virtual processes."""
    jax = _jax()
    devs = jax.devices()
    if len(devs) < 2 * n_hosts:
        pytest.skip(f"needs >= {2 * n_hosts} simulated devices")
    per = len(devs) // n_hosts
    n = per * n_hosts
    mesh = make_mesh([n], ["node"], devices=devs[:n])
    proc_of = {d: min(k // per, n_hosts - 1)
               for k, d in enumerate(devs[:n])}
    return mesh, proc_of.get


# the lockstep two-thread window runner is THE shared harness's (same
# code `make multihost` and the bench multihost row run)
from benchmarks.multihost_virtual import run_hosts  # noqa: E402


class TestHostLocalFabric:
    def test_agree_is_elementwise_max(self):
        fabric = HostLocalFabric(2, timeout=10)
        got = [None, None]

        def party(p, vec):
            got[p] = fabric.agree(p, "needs", np.asarray(vec, np.int64))

        a = threading.Thread(target=party, args=(0, [1, 9]))
        b = threading.Thread(target=party, args=(1, [5, 2]))
        a.start(); b.start(); a.join(10); b.join(10)
        np.testing.assert_array_equal(got[0], [5, 9])
        np.testing.assert_array_equal(got[1], [5, 9])

    def test_exchange_merges_mappings(self):
        fabric = HostLocalFabric(2, timeout=10)
        got = [None, None]

        def party(p, mapping):
            got[p] = fabric.exchange(p, "shards", mapping)

        a = threading.Thread(target=party, args=(0, {0: "a", 1: "b"}))
        b = threading.Thread(target=party, args=(1, {2: "c"}))
        a.start(); b.start(); a.join(10); b.join(10)
        assert got[0] == got[1] == {0: "a", 1: "b", 2: "c"}

    def test_kill_breaks_waiters_and_future_calls(self):
        fabric = HostLocalFabric(2, timeout=30)
        err = [None]

        def waiter():
            try:
                fabric.agree(0, "needs", np.asarray([1], np.int64))
            except DeviceWindowError as e:
                err[0] = e

        t = threading.Thread(target=waiter)
        t.start()
        fabric.kill()
        t.join(10)
        assert err[0] is not None and err[0].reason == "host_dead"
        with pytest.raises(DeviceWindowError) as exc:
            fabric.agree(1, "needs", np.asarray([1], np.int64))
        assert exc.value.reason == "host_dead"

    def test_diverged_call_sites_detected(self):
        fabric = HostLocalFabric(2, timeout=10)
        errs = [None, None]

        def party(p, name):
            try:
                fabric.agree(p, name, np.asarray([1], np.int64))
            except DeviceWindowError as e:
                errs[p] = e

        a = threading.Thread(target=party, args=(0, "needs"))
        b = threading.Thread(target=party, args=(1, "other"))
        a.start(); b.start(); a.join(10); b.join(10)
        assert all(e is not None and e.reason == "mesh_desync"
                   for e in errs)


class TestMultiHostEngine:
    def make_engines(self, n_hosts: int = 2, **kw):
        mesh, device_process = virtual_topology(n_hosts)
        fabric = HostLocalFabric(n_hosts, timeout=60)
        kw.setdefault("model_mode", "mlp")
        kw.setdefault("node_bucket", 8)
        kw.setdefault("workload_bucket", 16)
        engines = [MultiHostWindowEngine(mesh, process_index=p,
                                         device_process=device_process,
                                         fabric=fabric, **kw)
                   for p in range(n_hosts)]
        return mesh, engines, fabric, device_process

    def split_by_ring(self, ring, names):
        by_host = {p: [] for p in range(len(PEERS))}
        for name in names:
            by_host[PEERS.index(ring.owner(name))].append(name)
        return by_host

    def test_bit_equal_vs_single_host_under_churn(self):
        """Acceptance core: the two virtual hosts' published planes are
        BIT-identical per node to a single-host ShardedWindowEngine fed
        the union fleet, across full-pack, delta, join, and drop
        windows — and remote shards see zero H2D every window."""
        jax = _jax()
        from kepler_tpu.models import init_mlp

        mesh, engines, fabric, device_process = self.make_engines()
        ring = ring_from_mesh(
            PEERS, [device_process(d) for d in mesh.devices.flat])
        single = ShardedWindowEngine(
            make_mesh([mesh.devices.size], ["node"],
                      devices=list(mesh.devices.flat)),
            model_mode="mlp", node_bucket=8, workload_bucket=16)
        params = init_mlp(jax.random.PRNGKey(0), n_zones=2)

        base_names = [f"node-{i:02d}" for i in range(12)]
        schedules = [
            (1, base_names),                          # full pack
            (2, base_names),                          # pure delta
            (3, base_names + ["node-99"]),            # join
            (4, [n for n in base_names if n != "node-03"]),  # drop
            (5, [n for n in base_names if n != "node-03"]),  # delta again
        ]
        for seq, names in schedules:
            all_rows = make_rows(names, seq)
            owned = self.split_by_ring(ring, names)
            rows_by_host = [
                [r for r in all_rows if r.name in set(owned[p])]
                for p in range(2)]
            results = run_hosts(engines, rows_by_host, ZONES, params)
            plan_1 = single.plan_window(all_rows, ZONES, params)
            ref = plan_1.fetch(plan_1.program(*plan_1.args))
            for p, (plan, plane) in enumerate(results):
                assert plane.shape[0] == plan.meta.n_rows
                # each host publishes exactly the nodes it ingested
                assert sorted(plan.meta.rows) == sorted(owned[p])
                for name, li in plan.meta.rows.items():
                    np.testing.assert_array_equal(
                        plane[li], ref[plan_1.meta.rows[name]],
                        err_msg=f"{name} diverged at seq {seq}")
                # host-local invariant: zero H2D on remote shards
                owned_shards = set(engines[p]._owned_shards)
                for k, n in enumerate(plan.h2d_shards):
                    if k not in owned_shards:
                        assert n == 0
                # remote shards' buffers are never materialized
                for k, buf in enumerate(
                        engines[p]._buffers[engines[p]._buf_i]):
                    assert (buf is not None) == (k in owned_shards)

    def test_capacity_scales_with_host_count(self):
        """Node capacity (bucket rows hosted) from 1 process to 2
        processes of the same per-host device count scales ≥ 1.8× at
        the same PER-HOST load: 8 nodes on one 4-device host vs 16
        nodes over two 4-device hosts."""
        jax = _jax()
        from kepler_tpu.models import init_mlp

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 simulated devices")
        params = init_mlp(jax.random.PRNGKey(0), n_zones=2)

        # one host: 8 nodes on 4 devices
        single = ShardedWindowEngine(
            make_mesh([4], ["node"], devices=devs[:4]),
            model_mode="mlp", node_bucket=8, workload_bucket=16)
        plan_1 = single.plan_window(
            make_rows([f"node-{i:02d}" for i in range(8)], 1),
            ZONES, params)
        cap_1 = plan_1.meta.n_rows  # global rows = n_shards × bucket

        # two hosts: 4 devices each, double the fleet (same per-host
        # pressure), nodes landing per the mesh-derived ring
        names = [f"node-{i:02d}" for i in range(16)]
        mesh, engines, fabric, device_process = self.make_engines()
        ring = ring_from_mesh(
            PEERS, [device_process(d) for d in mesh.devices.flat])
        owned = self.split_by_ring(ring, names)
        rows_by_host = [make_rows(owned[p], 1) for p in range(2)]
        results = run_hosts(engines, rows_by_host, ZONES, params,
                            dispatch=False)
        plan = results[0][0]
        sb = plan.meta.n_rows // max(1, len(engines[0]._owned_shards))
        cap_2 = plan.n_shards * sb  # global rows across both hosts
        assert cap_2 / cap_1 >= 1.8, (cap_2, cap_1)

    def test_zone_desync_raises_mesh_desync(self):
        """Hosts packing different canonical zone axes would compile
        divergent SPMD shapes — the agreement hash turns that into a
        mesh_desync failure instead of a wedged dispatch."""
        jax = _jax()
        from kepler_tpu.models import init_mlp

        mesh, engines, fabric, _ = self.make_engines()
        params = init_mlp(jax.random.PRNGKey(0), n_zones=2)
        rows0 = make_rows(["a0"], 1)
        rows1 = make_rows(["b0"], 1, zones=("package", "core"))
        with pytest.raises(DeviceWindowError) as exc:
            run_hosts(engines, [rows0, rows1],
                      [ZONES, ("package", "core")], params,
                      dispatch=False)
        assert exc.value.reason == "mesh_desync"

    def test_owned_shards_partition_the_mesh(self):
        mesh, engines, fabric, _ = self.make_engines()
        all_shards = sorted(engines[0]._owned_shards
                            + engines[1]._owned_shards)
        assert all_shards == list(range(mesh.devices.size))
        assert not (set(engines[0]._owned_shards)
                    & set(engines[1]._owned_shards))
        for eng in engines:
            snap = eng.introspect()
            assert snap["multihost"]["hosts"] == 2
            assert snap["multihost"]["simulated_fabric"] is True


class TestRingFromMesh:
    def test_ownership_follows_shard_process_map(self):
        shard_procs = [0, 0, 0, 0, 1, 1, 1, 1]
        ring = ring_from_mesh(PEERS, shard_procs)
        assert isinstance(ring, MeshRing)
        assert ring.n_shards == 8
        for name in (f"node-{i}" for i in range(64)):
            shard = ring.shard_of(name)
            assert ring.owner(name) == PEERS[shard_procs[shard]]
        # determinism: two builds agree exactly (the no-coordination
        # contract every replica relies on)
        ring2 = ring_from_mesh(PEERS, shard_procs)
        assert all(ring.owner(f"n{i}") == ring2.owner(f"n{i}")
                   for i in range(200))

    def test_ownership_ratio_sums_to_one(self):
        ring = ring_from_mesh(PEERS, [0, 0, 0, 1, 1, 1, 1, 1])
        ratios = [ring.ownership_ratio(p) for p in PEERS]
        assert abs(sum(ratios) - 1.0) < 1e-9
        assert ratios[0] == pytest.approx(3 / 8)

    def test_membership_change_degrades_to_hash_ring(self):
        ring = ring_from_mesh(PEERS, [0, 0, 1, 1], epoch=1)
        survivor = ring.with_members([PEERS[0]], epoch=2)
        assert isinstance(survivor, HashRing)
        assert not isinstance(survivor, MeshRing)
        assert survivor.epoch == 2
        assert survivor.owner("anything") == PEERS[0]
        with pytest.raises(RingError):
            ring.with_members([PEERS[0]], epoch=1)  # must increase

    def test_invalid_shard_process_rejected(self):
        with pytest.raises(RingError):
            ring_from_mesh(PEERS, [0, 2])  # 2 indexes no peer
        with pytest.raises(RingError):
            ring_from_mesh(PEERS, [])


class TestMultihostInitStatus:
    """Satellite: a failed join surfaces its DISTINCT reason — a
    coordinator that never answered is not a generic decline."""

    def test_unconfigured_is_a_clean_decline(self, monkeypatch):
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        out = initialize_multihost()
        assert not out
        assert out.reason == "unconfigured"
        assert multihost_status().reason == "unconfigured"

    def test_coordinator_unreachable_is_distinct(self, monkeypatch):
        import jax

        def boom(**kw):
            raise RuntimeError(
                "DEADLINE_EXCEEDED: Barrier timed out connecting to "
                "coordinator 10.0.0.1:1234")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        out = initialize_multihost(coordinator_address="10.0.0.1:1234",
                                   num_processes=2, process_id=0,
                                   init_timeout=1.0)
        assert not out
        assert out.reason == "coordinator_unreachable"
        assert "DEADLINE_EXCEEDED" in out.detail
        assert multihost_status().reason == "coordinator_unreachable"

    def test_worker_preprobe_declines_before_native_abort(self,
                                                          monkeypatch):
        """jax's distributed client LOG(FATAL)s the whole process on a
        connect deadline (observed live on 0.4.37) — so for a worker
        process the unreachable coordinator MUST be caught by the
        Python pre-probe, before jax.distributed.initialize runs at
        all."""
        import socket

        import jax

        def must_not_run(**kw):
            raise AssertionError(
                "initialize() reached with an unreachable coordinator "
                "— the native client would have aborted the process")

        monkeypatch.setattr(jax.distributed, "initialize", must_not_run)
        # a port nothing listens on (bind-then-close reserves a dead one)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        out = initialize_multihost(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=2, process_id=1, init_timeout=1.5)
        assert not out
        assert out.reason == "coordinator_unreachable"
        assert "no coordinator listening" in out.detail

    def test_other_init_failures_keep_their_own_reason(self, monkeypatch):
        import jax

        def boom(**kw):
            raise ValueError("process_id 7 out of range")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        out = initialize_multihost(coordinator_address="10.0.0.1:1234")
        assert not out
        assert out.reason == "init_error"
        assert "out of range" in out.detail

    def test_joined_reports_topology(self, monkeypatch):
        import jax

        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: None)
        out = initialize_multihost(coordinator_address="127.0.0.1:1",
                                   num_processes=1, process_id=0)
        assert out
        assert out.reason == "joined"
        assert isinstance(out, MultihostInit)

    def test_probe_republishes_init_reason(self, monkeypatch):
        import jax

        def boom(**kw):
            raise RuntimeError("UNAVAILABLE: failed to connect")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        initialize_multihost(coordinator_address="10.0.0.1:9")
        agg = Aggregator(APIServer(), model_mode="mlp",
                         multihost_enabled=True, stale_after=1e9)
        agg.windows.mesh = make_mesh()
        probe = agg.window_health()
        assert probe["multihost"]["init_reason"] == \
            "coordinator_unreachable"
        assert probe["multihost"]["init_joined"] is False
        assert "init_detail" in probe["multihost"]


def make_mh_aggregator(process_index: int = 0, fabric=None,
                       **kw) -> Aggregator:
    """An Aggregator with the virtual 2-host topology injected."""
    mesh, device_process = virtual_topology(2)
    kw.setdefault("model_mode", "mlp")
    kw.setdefault("node_bucket", 8)
    kw.setdefault("workload_bucket", 8)
    kw.setdefault("stale_after", 1e9)
    agg = Aggregator(
        APIServer(),
        multihost_enabled=True,
        multihost_topology={
            "process_index": process_index,
            "device_process": device_process,
            "fabric": fabric,
        },
        peers=list(PEERS), self_peer=PEERS[process_index],
        **kw)
    agg.init()
    return agg


class TestAggregatorMultihost:
    def test_rung0_engine_and_mesh_derived_ring(self):
        agg = make_mh_aggregator(0)
        try:
            assert isinstance(agg._ring, MeshRing)
            assert agg._ring.ownership_ratio(PEERS[0]) == \
                pytest.approx(0.5)
            engine = agg.windows._packed_engine(RUNG_PIPELINED)
            assert isinstance(engine, MultiHostWindowEngine)
            assert agg.windows._rung_display(RUNG_PIPELINED) == \
                RUNG_NAME_MULTIHOST
            probe = agg.window_health()
            assert probe["multihost"]["active"] is True
            assert probe["multihost"]["mesh_degraded"] is False
        finally:
            agg.shutdown()

    def test_misordered_peers_rejected(self):
        """A peers list not in process-index order would silently
        INVERT mesh-derived ownership (every replica ingesting the
        OTHER host's agents) — init must refuse it."""
        mesh, device_process = virtual_topology(2)
        agg = Aggregator(
            APIServer(), model_mode="mlp", stale_after=1e9,
            multihost_enabled=True,
            multihost_topology={"process_index": 0,
                                "device_process": device_process},
            peers=[PEERS[1], PEERS[0]],  # reversed
            self_peer=PEERS[0])
        with pytest.raises(ValueError, match="process index"):
            agg.init()

    @staticmethod
    def _three_host_agg(process_index: int, alive: set[str],
                        delivered: list | None = None) -> Aggregator:
        """A 3-host virtual aggregator with injected liveness/delivery
        seams — the succession tier above 2 hosts (ISSUE 16)."""
        jax = _jax()

        devs = jax.devices()
        if len(devs) < 6:
            pytest.skip("needs >= 6 simulated devices")
        per = len(devs) // 3
        mesh_devs = devs[:3 * per]
        proc_of = {d: min(k // per, 2)
                   for k, d in enumerate(mesh_devs)}
        peers3 = PEERS + ["127.0.0.1:28293"]

        def deliver(peer, payload):
            if delivered is not None:
                delivered.append((peer, payload))
            return {"ok": True}

        agg = Aggregator(
            APIServer(), model_mode="mlp", stale_after=1e9,
            node_bucket=8, workload_bucket=8,
            multihost_enabled=True,
            multihost_topology={"process_index": process_index,
                                "device_process": proc_of.get},
            membership_topology={"peer_alive": lambda p: p in alive,
                                 "deliver": deliver},
            peers=list(peers3), self_peer=peers3[process_index],
            mesh=make_mesh([3 * per], ["node"], devices=mesh_devs))
        agg.init()
        return agg

    def test_succession_on_three_host_mesh(self):
        """The 2-host-only takeover gate is GONE: on a 3-host mesh a
        host death elects exactly ONE issuer (the lease holder, alive)
        who bumps the epoch over the survivor set and broadcasts it —
        no operator in the loop."""
        peers3 = PEERS + ["127.0.0.1:28293"]
        delivered = []
        # host 2 dies; hosts 0 and 1 survive; 0 is the incumbent holder
        agg = self._three_host_agg(0, alive=set(peers3[:2]),
                                   delivered=delivered)
        try:
            agg.windows._packed_engine(RUNG_PIPELINED)
            epoch_before = agg._ring.epoch
            agg.windows._handle_device_failure(
                DeviceWindowError("host_dead", "peer lost"))
            assert agg.windows._mesh_degraded is True
            # exactly one issuer (self = incumbent holder): epoch
            # bumped over the survivors, dead peer excised
            assert agg._ring.epoch == epoch_before + 1
            assert set(agg._ring.peers) == set(peers3[:2])
            assert agg._lease.holder == peers3[0]
            assert agg._lease.epoch == agg._ring.epoch
            probe = agg.window_health()
            assert probe["multihost"]["awaiting_membership"] is False
            # the membership was broadcast to the OTHER survivor only
            targets = [p for p, _ in delivered]
            assert targets == [peers3[1]]
            assert delivered[0][1]["op"] == "apply"
            assert delivered[0][1]["epoch"] == agg._ring.epoch
        finally:
            agg.shutdown()

    def test_non_issuer_survivor_awaits_membership(self):
        """The survivor that is NOT the succession issuer must NOT
        bump the epoch (that second writer is the split-brain the
        equal-epoch conflict detector exists for) — it flags itself
        'degraded, awaiting membership' until the issuer's broadcast
        lands, then recovers by adopting it."""
        peers3 = PEERS + ["127.0.0.1:28293"]
        delivered = []
        # host 2 dies; survivor 1 is NOT the holder (0 is, and alive)
        agg = self._three_host_agg(1, alive=set(peers3[:2]),
                                   delivered=delivered)
        try:
            agg.windows._packed_engine(RUNG_PIPELINED)
            epoch_before = agg._ring.epoch
            owner_before = agg._ring.owner("some-node")
            agg.windows._handle_device_failure(
                DeviceWindowError("host_dead", "peer lost"))
            # not the issuer: epoch and ownership untouched, no
            # broadcast sent, probe degraded awaiting membership
            assert agg._ring.epoch == epoch_before
            assert agg._ring.owner("some-node") == owner_before
            assert delivered == []
            probe = agg.window_health()
            assert probe["ok"] is False
            assert probe["multihost"]["awaiting_membership"] is True
            assert agg.ring_health()["awaiting_membership"] is True
            # the issuer's broadcast arrives → adopt and recover
            agg.apply_membership(peers3[:2], epoch_before + 1,
                                 source="wire", issuer=peers3[0])
            probe = agg.window_health()
            assert probe["multihost"]["awaiting_membership"] is False
            assert agg._lease.holder == peers3[0]
        finally:
            agg.shutdown()

    def test_peers_must_cover_every_process(self):
        mesh, device_process = virtual_topology(2)
        agg = Aggregator(
            APIServer(), model_mode="mlp", stale_after=1e9,
            multihost_enabled=True,
            multihost_topology={"process_index": 0,
                                "device_process": device_process},
            peers=[PEERS[0], PEERS[1], "127.0.0.1:28293"],
            self_peer=PEERS[0])
        with pytest.raises(ValueError, match="one peer endpoint per"):
            agg.init()

    def test_mesh_demotion_keeps_rung0_and_bumps_epoch(self):
        """Unit tier of the host-death story: a cross-host failure at
        rung 0 demotes to the LOCAL sharded engine (rung 0 kept, sticky),
        bumps the ring epoch so displaced agents follow 421s here, and
        the probe/timeline name the mesh-minus-one-host tier."""
        agg = make_mh_aggregator(0)
        try:
            agg.windows._packed_engine(RUNG_PIPELINED)  # build the mh engine
            epoch_before = agg._ring.epoch
            agg.windows._handle_device_failure(
                DeviceWindowError("host_dead", "peer lost"))
            assert agg.windows._mesh_degraded is True
            assert agg.windows._rung == RUNG_PIPELINED  # rung kept, tier changed
            assert agg._ring.epoch == epoch_before + 1
            assert not isinstance(agg._ring, MeshRing)
            assert agg._ring.owner("anything") == PEERS[0]  # takeover
            assert agg.windows._rung_display(RUNG_PIPELINED) == \
                RUNG_NAME_MESH_DEGRADED
            entry = agg.windows._rung_timeline[-1]
            assert entry["from_rung_name"] == RUNG_NAME_MULTIHOST
            assert entry["rung_name"] == RUNG_NAME_MESH_DEGRADED
            assert entry["reason"] == "host_dead"
            # the rebuilt engine is the survivors' single-host sharded
            # engine over LOCAL devices only
            engine = agg.windows._packed_engine(RUNG_PIPELINED)
            assert isinstance(engine, ShardedWindowEngine)
            assert not isinstance(engine, MultiHostWindowEngine)
            assert engine.n_shards == 4
            probe = agg.window_health()
            assert probe["ok"] is False
            assert probe["multihost"]["mesh_degraded"] is True
        finally:
            agg.shutdown()

    def test_publish_fetch_is_per_shard_and_surfaced(self):
        """Satellite: the publish path fetches per-shard addressable
        arrays (never one monolithic device fetch), and the leg is
        surfaced as ``last_fetch_ms`` + ``kepler_fleet_window_fetch_ms``
        so the owned-rows scaling claim is measurable."""
        jax = _jax()

        agg = Aggregator(APIServer(), model_mode="mlp", stale_after=1e9,
                         node_bucket=8, workload_bucket=8,
                         pipeline_depth=1, clock=lambda: 1e9)
        agg.windows.mesh = make_mesh()
        from kepler_tpu.fleet.aggregator import _Stored

        for i in range(5):
            rep = make_report(f"n{i:02d}", i,
                              mode=MODE_MODEL if i % 2 else 0)
            agg._reports[rep.node_name] = _Stored(
                report=rep, zone_names=ZONES, received=1e9, seq=1,
                run="r1")
        result = agg.aggregate_once()
        assert result is not None
        assert agg.windows._stats["last_fetch_ms"] >= 0.0
        if agg.windows.mesh.devices.size > 1:
            # the sharded plan carries the per-shard fetch override
            assert isinstance(agg.windows._engine, ShardedWindowEngine)
        families = {f.name for f in agg.collect()}
        assert "kepler_fleet_window_fetch_ms" in families
        agg.shutdown()

    def test_takeover_disabled_keeps_ring_epoch(self):
        agg = make_mh_aggregator(0, multihost_takeover=False)
        try:
            agg.windows._packed_engine(RUNG_PIPELINED)
            epoch_before = agg._ring.epoch
            agg.windows._handle_device_failure(
                DeviceWindowError("host_dead", "peer lost"))
            assert agg.windows._mesh_degraded is True
            assert agg._ring.epoch == epoch_before
        finally:
            agg.shutdown()
