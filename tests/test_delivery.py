"""Durable delivery plane (ISSUE 3): agent spool replay end-to-end,
idempotent ingest via the (run, seq) dedup window, per-node loss
accounting, ingest header-coercion hardening, the retired seq==1 restart
heuristic, monitor counter-state persistence, and the chaos-marked
SIGKILL crash/replay test."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from kepler_tpu import fault
from kepler_tpu.fault import FaultPlan, FaultSpec
from kepler_tpu.fleet import Aggregator, FleetAgent, Spool, encode_report
from kepler_tpu.fleet.agent import BREAKER_CLOSED, BREAKER_OPEN
from kepler_tpu.fleet.wire import MAGIC, _HEADER_LEN
from kepler_tpu.parallel.fleet import MODE_MODEL
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext

from tests.test_fleet import (
    FakeMeterMonitor,
    make_report,
    make_sample,
    post_report,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    fault.uninstall()
    yield
    fault.uninstall()


@pytest.fixture()
def server():
    s = APIServer(listen_addresses=["127.0.0.1:0"])
    s.init()
    ctx = CancelContext()
    t = threading.Thread(target=s.run, args=(ctx,), daemon=True)
    t.start()
    time.sleep(0.05)
    yield s
    ctx.cancel()
    s.shutdown()


def make_agg(server, **kw):
    kw.setdefault("model_mode", None)
    kw.setdefault("node_bucket", 8)
    kw.setdefault("workload_bucket", 16)
    agg = Aggregator(server, **kw)
    agg.init()
    return agg


def make_agent(server, monitor, spool=None, **kw):
    host, port = server.addresses[0]
    kw.setdefault("backoff_initial", 0.005)
    kw.setdefault("backoff_max", 0.02)
    kw.setdefault("jitter_seed", 0)
    agent = FleetAgent(monitor, endpoint=f"http://{host}:{port}",
                       node_name="dur-node", spool=spool, **kw)
    agent.init()
    return agent


def mutate_header(blob: bytes, **overrides) -> bytes:
    """Reframe a report with arbitrary (possibly type-broken) header
    fields — the attacker's/buggy-agent's view of the wire."""
    off = len(MAGIC)
    (hlen,) = _HEADER_LEN.unpack_from(blob, off)
    off += _HEADER_LEN.size
    header = json.loads(blob[off: off + hlen])
    header.update(overrides)
    hb = json.dumps(header, separators=(",", ":")).encode()
    return b"".join([MAGIC, _HEADER_LEN.pack(len(hb)), hb,
                     blob[off + hlen:]])


def post_raw(server, body):
    host, port = server.addresses[0]
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/report", data=body, method="POST")
    return urllib.request.urlopen(req, timeout=5)


class TestIngestHeaderCoercion:
    """Satellite: a non-int seq / non-str run must quarantine as
    malformed (400, charged to the node), never raise into a 500."""

    @pytest.mark.parametrize("bad", [
        {"seq": "abc"},
        {"seq": [1]},
        {"seq": True},
        {"seq": -3},
        {"seq": 2.5},
        {"run": ["r1"]},
        {"run": 42},
        {"seq": "abc", "run": {}},
    ])
    def test_bad_identity_types_quarantined(self, server, bad):
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("typed"), ["package", "dram"],
                          seq=1), **bad)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, blob)
        assert err.value.code == 400
        assert agg._stats["malformed_total"] == 1
        assert "typed" in agg.degraded_nodes()
        assert "typed" not in agg._reports  # nothing ingested

    def test_good_identity_still_ingests(self, server):
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("typed"), ["package", "dram"],
                          seq=1), seq=7, run="r1")
        assert post_raw(server, blob).status == 204
        assert agg._reports["typed"].seq == 7


class TestRingHeaderCoercion:
    """Satellite (ISSUE 11): the owner/epoch/acked_through ring fields
    are hardened exactly like run/seq — hostile values (non-int,
    negative, bool, overlong/non-printable) quarantine as a 400 charged
    to the node, never a 500."""

    @pytest.mark.parametrize("bad", [
        {"owner": 42},
        {"owner": ["a"]},
        {"owner": "evil\nname"},
        {"owner": "x" * 300},
        {"epoch": "abc"},
        {"epoch": -1},
        {"epoch": True},
        {"epoch": 2.5},
        {"acked_through": "9"},
        {"acked_through": -2},
        {"acked_through": 1.5},
        {"acked_through": [1]},
    ])
    def test_bad_ring_headers_quarantined(self, server, bad):
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("ringed"), ["package", "dram"],
                          seq=1, run="r1"), **bad)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, blob)
        assert err.value.code == 400
        assert agg._stats["malformed_total"] == 1
        assert "ringed" in agg.degraded_nodes()
        assert "ringed" not in agg._reports

    def test_good_ring_headers_ingest(self, server):
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("ringed"), ["package", "dram"],
                          seq=3, run="r1"),
            owner="10.0.0.1:28283", epoch=2, acked_through=2)
        assert post_raw(server, blob).status == 204
        assert agg._reports["ringed"].seq == 3

    def test_acked_through_suppresses_handoff_leading_gap(self, server):
        """A fresh owner meeting a mid-run stream seeds its tracker
        from the agent's delivered watermark: windows a previous owner
        acknowledged were delivered, not lost — while gaps ABOVE the
        watermark keep counting as real loss."""
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("moved"), ["package", "dram"],
                          seq=7, run="r1"), acked_through=6)
        assert post_raw(server, blob).status == 204
        assert agg._stats["windows_lost_total"] == 0
        blob = mutate_header(
            encode_report(make_report("moved"), ["package", "dram"],
                          seq=10, run="r1"), acked_through=6)
        assert post_raw(server, blob).status == 204
        assert agg._stats["windows_lost_total"] == 2  # seqs 8, 9

    def test_hostile_watermark_clamped_to_own_stream(self, server):
        """An inflated acked_through can hide at most the node's OWN
        leading gap (min() clamp) — later gaps still count."""
        agg = make_agg(server)
        blob = mutate_header(
            encode_report(make_report("liar"), ["package", "dram"],
                          seq=4, run="r1"), acked_through=10_000)
        assert post_raw(server, blob).status == 204
        assert agg._seq_trackers["liar"].max_seen == 4
        blob = mutate_header(
            encode_report(make_report("liar"), ["package", "dram"],
                          seq=8, run="r1"), acked_through=10_000)
        assert post_raw(server, blob).status == 204
        assert agg._stats["windows_lost_total"] == 3  # seqs 5, 6, 7

    def test_ownership_return_honors_watermark_after_epoch_bump(
            self, server):
        """Elastic membership (ISSUE 16): a replica that owned a node,
        lost it to a scale-up, and got it back on a scale-down has a
        STALE tracker — the away-period windows were 2xx'd by the
        interim owner, and the agent's watermark vouches for them.
        After a ring-epoch advance the existing tracker honors the
        watermark (clamped); with membership at rest it still
        doesn't."""
        self_peer = "127.0.0.1:28283"
        agg = make_agg(server, peers=[self_peer], self_peer=self_peer)
        blob = mutate_header(
            encode_report(make_report("elastic"), ["package", "dram"],
                          seq=1, run="r1"))
        assert post_raw(server, blob).status == 204
        # ownership leaves and returns: membership advanced to epoch 2
        agg.apply_membership([self_peer], 2)
        blob = mutate_header(
            encode_report(make_report("elastic"), ["package", "dram"],
                          seq=7, run="r1"), acked_through=6)
        assert post_raw(server, blob).status == 204
        assert agg._stats["windows_lost_total"] == 0  # 2..6 delivered
        # same epoch, later gap: the watermark hides NOTHING now
        blob = mutate_header(
            encode_report(make_report("elastic"), ["package", "dram"],
                          seq=10, run="r1"), acked_through=9)
        assert post_raw(server, blob).status == 204
        assert agg._stats["windows_lost_total"] == 2  # seqs 8, 9

    def test_no_watermark_keeps_conservative_accounting(self, server):
        """Pre-handoff agents (no acked_through) keep PR-3 semantics:
        a fresh tracker counts the full leading gap."""
        agg = make_agg(server)
        post_report(server, make_report("plain"), seq=5, run="r1")
        assert agg._stats["windows_lost_total"] == 4


MEMBER_PEERS = ["127.0.0.1:28283", "127.0.0.1:28284", "127.0.0.1:28285"]


def post_membership(server, payload):
    """POST to /v1/membership, returning (status, parsed body) for
    both success and error responses."""
    host, port = server.addresses[0]
    body = payload if isinstance(payload, bytes) \
        else json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/membership", data=body, method="POST")
    try:
        resp = urllib.request.urlopen(req, timeout=5)
        return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"{}")


class TestMembershipWireCoercion:
    """Satellite (ISSUE 16): the /v1/membership control plane launders
    every wire field through the same chokepoint discipline as the
    ring headers — hostile peers/epoch/lease values answer a bounded
    structured 400 (counted in ``membership_rejected_total``), stale
    and conflicting epochs answer 409 with the current epoch as
    evidence, and join/leave on a non-holder answers 421 naming the
    holder. Never a 500, never an unbounded echo."""

    def make_ring_agg(self, server, **kw):
        kw.setdefault("peers", list(MEMBER_PEERS))
        kw.setdefault("self_peer", MEMBER_PEERS[0])
        return make_agg(server, **kw)

    @pytest.mark.parametrize("payload,reason", [
        (b"not json at all {", "bad_payload"),
        (b"[1, 2, 3]", "bad_payload"),
        (b'"a string"', "bad_payload"),
        ({"op": "takeover"}, "bad_op"),
        ({"op": 42}, "bad_op"),
        ({"op": "apply", "peers": "not-a-list", "epoch": 2},
         "bad_peer"),
        ({"op": "apply", "peers": [42], "epoch": 2}, "bad_peer"),
        ({"op": "apply", "peers": ["ok:1", "evil\nname"], "epoch": 2},
         "bad_peer"),
        ({"op": "apply", "peers": ["x" * 300], "epoch": 2}, "bad_peer"),
        ({"op": "apply", "peers": MEMBER_PEERS, "epoch": "abc"},
         "bad_epoch"),
        ({"op": "apply", "peers": MEMBER_PEERS, "epoch": -1},
         "bad_epoch"),
        ({"op": "apply", "peers": MEMBER_PEERS, "epoch": True},
         "bad_epoch"),
        ({"op": "apply", "peers": MEMBER_PEERS, "epoch": 2,
          "issuer": "bad\x01issuer"}, "bad_peer"),
        ({"op": "apply", "peers": MEMBER_PEERS, "epoch": 2,
          "lease": "no-separator"}, "bad_lease"),
        ({"op": "join", "peer": 42}, "bad_peer"),
    ])
    def test_hostile_payloads_structured_400(self, server, payload,
                                             reason):
        agg = self.make_ring_agg(server)
        status, body = post_membership(server, payload)
        assert status == 400
        assert body["ok"] is False
        assert body["reason"] == reason
        assert len(body.get("error", "")) < 512  # bounded, no echo
        assert agg._membership_rejected[reason] == 1
        assert agg._ring.epoch == 1  # nothing applied

    def test_non_post_method_rejected(self, server):
        self.make_ring_agg(server)
        host, port = server.addresses[0]
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://{host}:{port}/v1/membership", timeout=5)
        assert err.value.code == 405

    def test_stale_epoch_answers_409_with_current(self, server):
        agg = self.make_ring_agg(server)
        agg.apply_membership(MEMBER_PEERS, 3)
        status, body = post_membership(server, {
            "op": "apply", "peers": MEMBER_PEERS[:2], "epoch": 2,
            "issuer": MEMBER_PEERS[0]})
        assert status == 409
        assert body["reason"] == "stale_epoch"
        assert body["epoch"] == 3  # evidence: the epoch it lost to
        assert agg._membership_rejected["stale_epoch"] == 1

    def test_equal_epoch_conflict_answers_409(self, server):
        """Two issuers writing DIFFERENT peer sets at the same epoch is
        the split-brain the lease exists to catch — loud, counted,
        evidence in the reply."""
        agg = self.make_ring_agg(server)
        status, body = post_membership(server, {
            "op": "apply", "peers": MEMBER_PEERS[:2], "epoch": 1,
            "issuer": MEMBER_PEERS[0]})
        assert status == 409
        assert body["reason"] == "equal_epoch_conflict"
        assert agg._membership_rejected["equal_epoch_conflict"] == 1
        assert list(agg._ring.peers) == sorted(MEMBER_PEERS)

    def test_equal_epoch_same_set_is_idempotent_200(self, server):
        agg = self.make_ring_agg(server)
        status, body = post_membership(server, {
            "op": "apply", "peers": MEMBER_PEERS, "epoch": 1,
            "issuer": MEMBER_PEERS[0]})
        assert status == 200
        assert body["ok"] is True
        assert agg._ring.epoch == 1

    def test_good_apply_advances_ring(self, server):
        agg = self.make_ring_agg(server)
        status, body = post_membership(server, {
            "op": "apply", "peers": MEMBER_PEERS[:2], "epoch": 2,
            "issuer": MEMBER_PEERS[0]})
        assert status == 200
        assert body["ok"] is True
        assert agg._ring.epoch == 2
        assert agg._membership_applied["wire"] == 1

    def test_join_on_non_holder_answers_421(self, server):
        # self is NOT the lowest peer, so it does not hold the lease
        agg = self.make_ring_agg(server, self_peer=MEMBER_PEERS[1])
        status, body = post_membership(server, {
            "op": "join", "peer": "127.0.0.1:28299"})
        assert status == 421
        assert body["ok"] is False
        assert body["reason"] == "not_leader"
        assert body["holder"] == MEMBER_PEERS[0]
        assert agg._ring.epoch == 1  # the non-holder changed nothing


class TestWireV2HeaderCoercion:
    """ISSUE 14: the hostile-field discipline re-run against the BINARY
    v2 header — non-printable/overlong name, hostile owner, hostile
    delta payloads — always a 400 quarantine (charged to the node when
    the name survives sanitization), never a 500."""

    def _kf(self, name="v2coerce", seq=1, run="r1"):
        from kepler_tpu.fleet.wire import encode_report_v2

        return encode_report_v2(make_report(name), ["package", "dram"],
                                seq=seq, run=run)

    def _patch_str(self, blob: bytes, field: str, value: bytes) -> bytes:
        """Rewrite one var-length header string in place (same length —
        the attacker's minimal bit-flip view of the wire)."""
        import struct as _s

        from kepler_tpu.fleet.wire import WireLayoutV2 as L

        fixed = L.FIXED.unpack_from(blob, len(L.MAGIC))
        name_len, run_len = fixed[14], fixed[15]
        off = L.fixed_end()
        offs = {"name": off, "run": off + name_len}
        start = offs[field]
        assert len(value) == (name_len if field == "name" else run_len)
        out = bytearray(blob)
        out[start: start + len(value)] = value
        return bytes(out)

    def test_nonprintable_name_quarantined(self, server):
        agg = make_agg(server)
        blob = self._patch_str(self._kf("victim01"), "name",
                               b"victim\n1")
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, blob)
        assert err.value.code == 400
        assert agg._stats["malformed_total"] == 1
        # charged to the SANITIZED name, never the raw bytes
        assert "victim1" in agg.degraded_nodes()
        assert not agg._reports

    def test_hostile_owner_quarantined(self, server):
        from kepler_tpu.fleet.wire import restamp_transmit

        agg = make_agg(server)
        blob = restamp_transmit(self._kf(), time.time(),
                                owner="evil owner\x01")
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, blob)
        assert err.value.code == 400
        assert b"owner" in err.value.read()
        assert agg._stats["malformed_total"] == 1
        assert "v2coerce" in agg.degraded_nodes()

    def test_overlong_owner_rejected(self, server):
        """An owner past the layout cap can't even be framed by the
        encoder; a hand-built frame claiming one fails the header
        parse → 400, no allocation."""
        import struct as _s

        from kepler_tpu.fleet.wire import WireLayoutV2 as L

        agg = make_agg(server)
        blob = bytearray(self._kf())
        # owner_len is the last u16 of the fixed block
        off = len(L.MAGIC) + L.FIXED.size - _s.calcsize("<H")
        _s.pack_into("<H", blob, off, L.MAX_OWNER + 1)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, bytes(blob))
        assert err.value.code == 400
        assert agg._stats["malformed_total"] == 1

    def test_skew_and_dedup_semantics_unchanged(self, server):
        """Admission/dedup/quarantine semantics hold under v2: skewed
        sent_at quarantines (422), a redelivered (run, seq) dedups
        (204, duplicates_total)."""
        from kepler_tpu.fleet.wire import restamp_transmit

        agg = make_agg(server)
        skewed = restamp_transmit(self._kf(), time.time() + 10_000)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, skewed)
        assert err.value.code == 422
        assert agg._stats["clock_skew_total"] == 1
        ok = restamp_transmit(self._kf(), time.time())
        assert post_raw(server, ok).status == 204
        assert post_raw(server, ok).status == 204  # redelivery
        assert agg._stats["duplicates_total"] == 1
        assert agg._stats["reports_total"] == 2


class TestWireVersionFallback:
    """ISSUE 14 satellite: an old replica answering 415/400 ("bad
    magic") to a v2 frame downgrades that target to v1 — the SAME
    record retries transcoded, nothing dropped, nothing breaker-fed —
    and the agent re-probes v2 after ``wire_degraded_ttl``."""

    def _old_replica(self, agg):
        """Make the live aggregator answer v2 bytes exactly like a
        pre-v2 build: its v1 decoder's 400 "bad magic"."""
        from kepler_tpu.fleet.wire import WireLayoutV2

        real = agg._ingest_payload

        def v1_only(body, parsed=None):
            if body[: len(WireLayoutV2.MAGIC)] == WireLayoutV2.MAGIC:
                return (400, {"Content-Type": "text/plain"},
                        b"bad magic\n")
            return real(body, parsed=None)

        agg._ingest_payload = v1_only
        return real

    def test_downgrade_then_reprobe(self, server):
        agg = make_agg(server)
        monitor = FakeMeterMonitor()
        agent = make_agent(server, monitor, wire_degraded_ttl=0.2)
        real = self._old_replica(agg)
        agent._on_window(make_sample())
        agent._drain(None)
        # delivered as v1 on the SAME drain pass: one downgrade, no
        # failures, no breaker movement, nothing dropped
        assert agent._stats["wire_downgrades"] == 1
        assert agent._stats["sent_total"] == 1
        assert agent._stats["send_failures"] == 0
        assert agent._stats["dropped_total"] == 0
        assert agent._breaker_state == BREAKER_CLOSED
        assert agg._reports["dur-node"].wire_version == 1
        assert agent.health()["wire_version"] == 1
        # the replica upgrades; before the TTL the agent still sends v1
        agg._ingest_payload = real
        agent._on_window(make_sample())
        agent._drain(None)
        assert agg._reports["dur-node"].wire_version == 1
        # after the TTL it re-probes v2 and sticks
        time.sleep(0.25)
        agent._on_window(make_sample())
        agent._drain(None)
        assert agg._reports["dur-node"].wire_version == 2
        assert agent._stats["wire_downgrades"] == 1
        assert agent.health()["wire_version"] == 2
        agent.shutdown()

    def test_batch_drain_downgrades_without_loss(self, server,
                                                 tmp_path):
        """A spooled v2 backlog drained BATCHED into a v1-only replica
        (per-row 400 "bad magic") must never conclude/drop records —
        the target downgrades and the same batch retries transcoded."""
        agg = make_agg(server)
        spool = Spool(str(tmp_path / "sp"))
        agent = make_agent(server, FakeMeterMonitor(), spool=spool,
                           drain_batch_max=8)
        self._old_replica(agg)
        for _ in range(4):
            agent._on_window(make_sample())
        agent._drain(None)
        assert agent._stats["dropped_total"] == 0
        assert agent._stats["server_rejections"] == 0
        assert agent._stats["wire_downgrades"] == 1
        assert spool.pending_records() == 0
        assert agg._reports["dur-node"].seq == 4
        assert agg._reports["dur-node"].wire_version == 1
        agent.shutdown()

    def test_genuine_400_still_drops(self, server):
        """A 400 naming any other defect keeps permanent-reject
        semantics — no downgrade loop, the record drops once."""
        agg = make_agg(server)
        monitor = FakeMeterMonitor()
        agent = make_agent(server, monitor)

        def reject(body, parsed=None):
            return (400, {"Content-Type": "text/plain"},
                    b"seq must be a non-negative integer\n")

        agg._ingest_payload = reject
        agent._on_window(make_sample())
        agent._drain(None)
        assert agent._stats["wire_downgrades"] == 0
        assert agent._stats["dropped_total"] == 1
        assert agent._stats["server_rejections"] == 1
        assert agent.backlog() == 0
        agent.shutdown()


class TestThrottleHeaderCoercion:
    """Satellite (ISSUE 12): throttle-control values from the wire —
    the 429 ``Retry-After`` header and the batch response's per-record
    status fields — are hardened exactly like run/seq and the ring
    headers: non-numeric/negative/bool → default backoff, huge values
    clamped to a max. An adversarial owner must not be able to park an
    agent forever or trick it into acking unconcluded records."""

    @pytest.mark.parametrize("hostile", [
        None, "", "soon", "12h", "1e", True, False, "-5", -5, -0.01,
        float("nan"), float("inf"), "nan", "-inf", [], {}, b"2",
    ])
    def test_hostile_retry_after_coerces_to_default(self, hostile):
        from kepler_tpu.fleet.agent import coerce_retry_after
        assert coerce_retry_after(hostile, default=2.0, cap=300.0) == 2.0

    @pytest.mark.parametrize("huge", [10_000, "10000", 1e12, "9e9"])
    def test_huge_retry_after_clamped(self, huge):
        from kepler_tpu.fleet.agent import coerce_retry_after
        assert coerce_retry_after(huge, default=2.0, cap=300.0) == 300.0

    @pytest.mark.parametrize("good,expected", [
        ("0", 0.0), ("1", 1.0), ("2.5", 2.5), (" 3 ", 3.0),
        (7, 7.0), (0.25, 0.25), ("299.9", 299.9),
    ])
    def test_numeric_retry_after_honored(self, good, expected):
        from kepler_tpu.fleet.agent import coerce_retry_after
        assert coerce_retry_after(good, default=2.0, cap=300.0) \
            == expected

    def test_hostile_429_header_never_parks_the_drain(self, tmp_path):
        """End to end: a 429 whose Retry-After is a hostile huge string
        waits the agent-side clamp, not the adversarial value — and
        leaves the breaker/rotation/disruption state untouched."""
        from kepler_tpu.fleet.agent import BREAKER_CLOSED

        s = APIServer(listen_addresses=["127.0.0.1:0"])
        s.init()
        calls = {"n": 0}

        def hostile(request):
            calls["n"] += 1
            if calls["n"] == 1:
                return (429, {"Retry-After": "99999999"}, b"shed\n")
            return 204, {}, b""

        s.register("/v1/report", "evil", "hostile throttler", hostile,
                   max_body=64 << 20)
        ctx = CancelContext()
        t = threading.Thread(target=s.run, args=(ctx,), daemon=True)
        t.start()
        time.sleep(0.05)
        try:
            host, port = s.addresses[0]
            agent = FleetAgent(FakeMeterMonitor(),
                               endpoint=f"http://{host}:{port}",
                               node_name="clamp-node", jitter_seed=0,
                               spool=Spool(str(tmp_path / "sp")),
                               drain_retry_after_max=0.05)
            agent.init()
            agent._on_window(make_sample())
            drain_ctx = CancelContext()
            t0 = time.monotonic()
            agent._drain(drain_ctx)  # clamped wait, then delivery
            assert time.monotonic() - t0 < 2.0
            h = agent.health()
            assert h["queued"] == 0 and h["sent_total"] == 1
            assert h["throttled_total"] == 1
            assert h["breaker"] == BREAKER_CLOSED
            assert h["send_failures"] == 0
            assert agent._disrupted_at is None
            agent.shutdown()
        finally:
            ctx.cancel()
            s.shutdown()

    @pytest.mark.parametrize("rows", [
        "not-a-list",
        [{"status": True}],
        [{"status": "204"}],
        [{"status": 2.04}],
        [{"no_status": 1}],
        ["bare-string"],
    ])
    def test_hostile_batch_statuses_conclude_nothing(self, rows,
                                                     tmp_path):
        """Per-record status fields are wire input: any malformed row
        stops the conclusion walk — no ack, no drop, the record stays
        spooled for the failure path to retry."""
        s = APIServer(listen_addresses=["127.0.0.1:0"])
        s.init()
        body = json.dumps({"results": rows}).encode()
        s.register("/v1/reports", "evil", "hostile batch",
                   lambda r: (200, {"Content-Type": "application/json"},
                              body),
                   max_body=64 << 20)
        ctx = CancelContext()
        threading.Thread(target=s.run, args=(ctx,), daemon=True).start()
        time.sleep(0.05)
        try:
            host, port = s.addresses[0]
            spool = Spool(str(tmp_path / "sp"))
            for i in range(1, 4):
                spool.append(encode_report(
                    make_report("hb-node"), ["package", "dram"],
                    seq=i, run="r1"))
            agent = FleetAgent(FakeMeterMonitor(),
                               endpoint=f"http://{host}:{port}",
                               node_name="hb-node", jitter_seed=0,
                               spool=spool, drain_batch_max=4)
            agent.init()
            agent._drain(None)  # one attempt: fails, concludes nothing
            assert spool.stats()["acked_total"] == 0
            assert agent.backlog() == 3
            assert agent._stats["dropped_total"] == 0
            agent.shutdown()
        finally:
            ctx.cancel()
            s.shutdown()

    def test_hostile_batch_retry_after_field_clamped(self, tmp_path):
        """The per-record 429 row's retry_after is coerced exactly like
        the header (huge → clamp; the concluded prefix stays acked)."""
        s = APIServer(listen_addresses=["127.0.0.1:0"])
        s.init()
        body = json.dumps({"results": [
            {"status": 204},
            {"status": 429, "retry_after": "99999999"},
        ]}).encode()
        s.register("/v1/reports", "evil", "throttling batch",
                   lambda r: (200, {"Content-Type": "application/json"},
                              body),
                   max_body=64 << 20)
        ctx = CancelContext()
        threading.Thread(target=s.run, args=(ctx,), daemon=True).start()
        time.sleep(0.05)
        try:
            host, port = s.addresses[0]
            spool = Spool(str(tmp_path / "sp"))
            for i in range(1, 4):
                spool.append(encode_report(
                    make_report("tb-node"), ["package", "dram"],
                    seq=i, run="r1"))
            agent = FleetAgent(FakeMeterMonitor(),
                               endpoint=f"http://{host}:{port}",
                               node_name="tb-node", jitter_seed=0,
                               spool=spool, drain_batch_max=4,
                               drain_retry_after_max=0.05)
            agent.init()
            drain_ctx = CancelContext()
            t0 = time.monotonic()

            def cancel_soon():
                time.sleep(1.0)
                drain_ctx.cancel()

            threading.Thread(target=cancel_soon, daemon=True).start()
            agent._drain(drain_ctx)
            # record 1 concluded; the throttle wait was the CLAMP, so
            # several retries fit into the second before cancellation
            assert spool.stats()["acked_total"] >= 1
            assert agent._stats["throttled_total"] >= 2
            assert time.monotonic() - t0 < 5.0
            agent.shutdown()
        finally:
            ctx.cancel()
            s.shutdown()


class TestDedupWindow:
    def test_duplicate_run_seq_absorbed(self, server):
        agg = make_agg(server)
        for _ in range(3):
            post_report(server, make_report("node-a"), seq=1, run="r1")
        assert agg._stats["duplicates_total"] == 2
        assert agg._stats["windows_lost_total"] == 0
        assert agg._reports["node-a"].seq == 1

    def test_dedup_resets_on_restart(self, server):
        agg = make_agg(server)
        post_report(server, make_report("node-a"), seq=1, run="r1")
        post_report(server, make_report("node-a"), seq=1, run="r2")
        assert agg._stats["duplicates_total"] == 0  # new run: not a dup
        assert agg._reports["node-a"].run == "r2"

    def test_seq_zero_with_nonce_never_freezes(self, server):
        # review fix: seq 0 means "no sequencing" — deduping a constant-
        # zero stream would freeze the node's data on its first window
        # forever (while dup-liveness kept it from ever going stale)
        agg = make_agg(server)
        for seed in (1, 2, 3):
            post_report(server, make_report("node-a", seed=seed),
                        seq=0, run="r1")
        assert agg._stats["duplicates_total"] == 0
        assert agg._stats["windows_lost_total"] == 0
        # every report overwrote the stored window (newest wins)
        assert agg._stats["reports_total"] == 3
        assert "node-a" not in agg._seq_trackers

    def test_pre_nonce_agents_not_deduped(self, server):
        # run="" has no identity to dedup on; monotonic seq still governs
        agg = make_agg(server)
        post_report(server, make_report("legacy"), seq=1, run="")
        post_report(server, make_report("legacy"), seq=1, run="")
        assert agg._stats["duplicates_total"] == 0
        assert agg._reports["legacy"].seq == 1

    def test_window_bounded(self, server):
        agg = make_agg(server, dedup_window=4)
        for seq in range(1, 9):
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        tracker = agg._seq_trackers["node-a"]
        assert len(tracker.seen) <= 4
        # a seq that fell out of the window is treated as a duplicate
        post_report(server, make_report("node-a"), seq=1, run="r1")
        assert agg._stats["duplicates_total"] == 1

    def test_tracker_survives_partition_longer_than_stale_after(
            self, server):
        # review fix: a partition > stale_after (aggregator stays up)
        # followed by a spool replay must resume from max_seen — neither
        # a fabricated windows_lost spike nor re-ingest of delivered
        # windows
        now = [1000.0]
        agg = make_agg(server, stale_after=10.0, clock=lambda: now[0])
        for seq in (1, 2, 3):
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        now[0] += 60.0  # partition: node ages out of the batch entirely
        agg.aggregate_once()
        assert agg.windows._stats["last_batch_nodes"] == 0
        assert "node-a" in agg._seq_trackers  # survives staleness
        # replay: delivered-but-unacked tail (2, 3) then fresh 4
        for seq in (2, 3, 4):
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        assert agg._stats["duplicates_total"] == 2
        assert agg._stats["windows_lost_total"] == 0  # no fabricated loss
        assert agg._reports["node-a"].seq == 4

    def test_tracker_table_bounded_by_cap(self, server):
        # the cap binds only DEAD nodes' trackers: stale nodes fall out
        # of _reports, so their trackers become evictable
        now = [1000.0]
        agg = make_agg(server, stale_after=5.0, clock=lambda: now[0])
        agg._tracker_cap = 4
        for i in range(8):
            post_report(server, make_report(f"node-{i}"), seq=1,
                        run=f"r{i}")
            now[0] += 10.0  # each node goes stale before the next joins
            agg.aggregate_once()
        assert len(agg._seq_trackers) == 4
        assert "node-7" in agg._seq_trackers  # newest kept

    def test_tracker_cap_never_thrashes_a_live_fleet(self, server):
        # review fix: a fleet larger than the base cap must keep EVERY
        # live node's tracker — round-robin eviction would disable dedup
        # and fabricate a lost-window spike on every report
        agg = make_agg(server, stale_after=1e9)
        agg._tracker_cap = 4
        for i in range(8):  # all 8 stay live in _reports
            post_report(server, make_report(f"node-{i}"), seq=1,
                        run=f"r{i}")
        assert len(agg._seq_trackers) == 8  # cap grew with the fleet
        for i in range(8):  # every node's dedup still works
            post_report(server, make_report(f"node-{i}"), seq=1,
                        run=f"r{i}")
        assert agg._stats["duplicates_total"] == 8
        assert agg._stats["windows_lost_total"] == 0


class TestLossAccounting:
    def test_seq_jump_counts_lost_windows(self, server):
        agg = make_agg(server)
        post_report(server, make_report("node-a"), seq=1, run="r1")
        post_report(server, make_report("node-a"), seq=5, run="r1")
        assert agg._stats["windows_lost_total"] == 3
        assert agg._lost_by_node["node-a"] == 3
        assert agg.health()["windows_lost_total"] == 3

    def test_first_seen_seq_counts_leading_gap(self, server):
        agg = make_agg(server)
        post_report(server, make_report("node-a"), seq=4, run="r1")
        assert agg._stats["windows_lost_total"] == 3

    def test_contiguous_stream_counts_nothing(self, server):
        agg = make_agg(server)
        for seq in range(1, 6):
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        assert agg._stats["windows_lost_total"] == 0

    def test_pre_nonce_stream_never_counts_loss(self, server):
        # a pre-nonce agent's seq space restarts unannounced: gap math on
        # it would fabricate loss
        agg = make_agg(server)
        post_report(server, make_report("legacy"), seq=9, run="")
        assert agg._stats["windows_lost_total"] == 0

    def test_loss_table_evicts_least_recently_losing(self, server):
        # review fix: cap eviction must drop the node that stopped losing
        # longest ago, never an actively-firing series
        agg = make_agg(server)
        agg._lost_node_cap = 2
        post_report(server, make_report("node-a"), seq=2, run="ra")  # lost 1
        post_report(server, make_report("node-b"), seq=2, run="rb")  # lost 1
        # node-a loses AGAIN: it is now the most recent loser
        post_report(server, make_report("node-a"), seq=4, run="ra")  # lost 1
        post_report(server, make_report("node-c"), seq=2, run="rc")  # evicts
        assert set(agg._lost_by_node) == {"node-a", "node-c"}
        assert agg._lost_by_node["node-a"] == 2  # series never reset

    def test_loss_metric_exported_per_node(self, server):
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest

        agg = make_agg(server)
        post_report(server, make_report("node-a"), seq=1, run="r1")
        post_report(server, make_report("node-a"), seq=4, run="r1")
        post_report(server, make_report("node-a"), seq=4, run="r1")
        registry = CollectorRegistry()
        registry.register(agg)
        text = generate_latest(registry).decode()
        assert ('kepler_fleet_windows_lost_total'
                '{node_name="node-a"} 2.0') in text
        assert "kepler_fleet_reports_duplicate_total 1.0" in text


class TestLegacyHeuristicRemoved:
    """Satellite: the seq==1 restart heuristic is gone (a spool replay
    starting at seq 1 of an old run must not double-ingest), while
    pre-nonce agents keep ingesting normally."""

    def test_pre_nonce_agent_still_ingests(self, server):
        agg = make_agg(server)
        for seq in (1, 2, 3):
            assert post_report(server, make_report("legacy"), seq=seq,
                               run="").status == 204
        assert agg._reports["legacy"].seq == 3
        assert agg._stats["reports_total"] == 3

    def test_pre_nonce_seq_one_no_longer_overwrites(self, server):
        agg = make_agg(server)
        post_report(server, make_report("legacy", seed=1), seq=5, run="")
        post_report(server, make_report("legacy", seed=2), seq=1, run="")
        # pre-heuristic behavior would have stored seq 1 as a "restart";
        # now the newest report wins until stale_after ages the node out
        assert agg._reports["legacy"].seq == 5

    def test_nonce_replay_from_seq_one_not_treated_as_restart(self, server):
        agg = make_agg(server, model_mode="temporal", history_window=4)
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="r1")
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=2, run="r1")
        # replay of the same run's seq 1 (spool redelivery): dup, no
        # history push, no stored regression
        post_report(server, make_report("node-a", mode=MODE_MODEL),
                    seq=1, run="r1")
        assert agg._stats["duplicates_total"] == 1
        assert agg._reports["node-a"].seq == 2
        _, tv = agg._history["node-a"][1].window_arrays(["node-a-w0"])
        assert tv[0].tolist() == [True, True, False, False]


class TestDurableDeliveryEndToEnd:
    """Acceptance: an outage longer than queue_max loses nothing with the
    spool (every window ingested exactly once, loss counter stays 0) and
    loses visibly without it (loss properly counted)."""

    def _emit(self, monitor, n, start=0):
        for i in range(n):
            monitor.emit(make_sample(ts=100.0 + start + i))

    def test_spool_survives_outage_exactly_once(self, server, tmp_path):
        agg = make_agg(server, stale_after=1e9)
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"))
        agent = make_agent(server, monitor, spool=spool, queue_max=8,
                           breaker_threshold=2, breaker_cooldown=0.01)
        ctx = CancelContext()
        with fault.installed(FaultPlan([FaultSpec("net.refuse",
                                                  count=2)])):
            # outage: 12 windows arrive (> queue_max=8); every one lands
            # in the spool; the drain trips the breaker and sheds
            self._emit(monitor, 12)
            agent._drain(ctx)
            assert agent._breaker_state == BREAKER_OPEN
            assert spool.pending_records() == 12  # nothing dropped
        time.sleep(0.02)  # cooldown elapses; faults exhausted
        agent._drain(ctx)
        assert agent._breaker_state == BREAKER_CLOSED
        assert spool.pending_records() == 0
        tracker = agg._seq_trackers["dur-node"]
        assert tracker.max_seen == 12
        assert sorted(tracker.seen) == list(range(1, 13))  # all delivered
        assert agg._stats["windows_lost_total"] == 0
        assert agg._stats["duplicates_total"] == 0
        assert agg._stats["reports_total"] == 12  # exactly once each
        # every window waited out the outage → the delivery-latency
        # histogram observed all 12 under path="replay", none fresh
        assert agg._delivery_hist["replay"].count == 12
        assert agg._delivery_hist["fresh"].count == 0
        agent._close_conn()
        spool.close()

    def test_without_spool_loss_is_counted(self, server):
        agg = make_agg(server, stale_after=1e9)
        monitor = FakeMeterMonitor()
        agent = make_agent(server, monitor, queue_max=4)
        # same outage shape, no spool: the ring keeps only the newest 4
        self._emit(monitor, 12)
        assert agent._stats["dropped_total"] == 8
        agent._drain(CancelContext())
        tracker = agg._seq_trackers["dur-node"]
        assert tracker.max_seen == 12
        assert agg._stats["reports_total"] == 4
        assert agg._stats["windows_lost_total"] == 8  # loss, accounted
        agent._close_conn()

    def test_crash_before_cursor_persist_dedups(self, server, tmp_path):
        # deliver everything, then "crash" the agent before the cursor
        # hits disk: the full backlog redelivers and the aggregator
        # absorbs every duplicate
        agg = make_agg(server, stale_after=1e9)
        monitor = FakeMeterMonitor()
        d = str(tmp_path / "sp")
        spool = Spool(d)
        agent = make_agent(server, monitor, spool=spool)
        self._emit(monitor, 5)
        agent._drain(CancelContext())
        assert agg._stats["reports_total"] == 5
        agent._close_conn()
        spool.close()
        os.unlink(os.path.join(d, "cursor.json"))  # the "crash"
        spool2 = Spool(d)
        agent2 = FleetAgent(monitor, endpoint=agent._endpoint,
                            node_name="dur-node", spool=spool2,
                            jitter_seed=0)
        agent2._run_nonce = agent._run_nonce  # same logical agent run
        agent2._drain(CancelContext())
        assert agg._stats["duplicates_total"] == 5
        assert agg._stats["windows_lost_total"] == 0
        # ingested exactly once: the stored report never regressed
        assert agg._reports["dur-node"].seq == 5
        agent2._close_conn()
        spool2.close()

    def test_agent_restart_replays_old_run_then_new(self, server, tmp_path):
        agg = make_agg(server, stale_after=1e9)
        monitor = FakeMeterMonitor()
        d = str(tmp_path / "sp")
        spool = Spool(d)
        agent = make_agent(server, monitor, spool=spool)
        self._emit(monitor, 3)  # never drained: agent "crashes"
        spool.close()
        monitor2 = FakeMeterMonitor()
        spool2 = Spool(d)
        agent2 = make_agent(server, monitor2, spool=spool2)
        assert agent2._run_nonce != agent._run_nonce
        self._emit(monitor2, 2)  # new run's windows queue behind the replay
        agent2._drain(CancelContext())
        assert spool2.pending_records() == 0
        assert agg._stats["reports_total"] == 5
        assert agg._stats["rejected_total"] == 0  # no 409s: ordered replay
        assert agg._stats["windows_lost_total"] == 0
        assert agg._reports["dur-node"].run == agent2._run_nonce
        assert agg._reports["dur-node"].seq == 2
        agent2._close_conn()
        spool2.close()

    def test_skew_check_judges_transmit_time_not_backlog_age(
            self, server, tmp_path):
        # a backlog replayed long after the windows were measured must
        # NOT be quarantined as clock-skewed: sent_at is restamped at
        # transmit time (wire.restamp_sent_at)
        now = [5000.0]
        agg = make_agg(server, skew_tolerance=30.0, clock=lambda: now[0])
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"), clock=lambda: now[0] - 3600.0)
        agent = make_agent(server, monitor, spool=spool,
                           clock=lambda: now[0])  # healthy clock NOW
        self._emit(monitor, 2)
        agent._drain(CancelContext())
        assert agg._stats["clock_skew_total"] == 0
        assert agg._stats["reports_total"] == 2
        agent._close_conn()
        spool.close()

    def test_disk_failure_degrades_to_ring(self, server, tmp_path):
        agg = make_agg(server)
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"))
        agent = make_agent(server, monitor, spool=spool, queue_max=8)
        with fault.installed(FaultPlan([FaultSpec("disk.write_error")])):
            self._emit(monitor, 3)
        assert spool.pending_records() == 0
        assert len(agent._queue) == 3  # in-memory fallback took them
        agent._drain(CancelContext())
        assert agg._stats["reports_total"] == 3
        assert agg._stats["windows_lost_total"] == 0
        agent._close_conn()
        spool.close()

    def test_unsendable_record_never_closes_breaker(self, server,
                                                    tmp_path):
        # review fix: a spooled record that fails restamp is dropped
        # WITHOUT being treated as aggregator contact — the breaker must
        # not close on evidence that never crossed the network
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"))
        spool.append(b"garbage-not-a-wire-record")
        agent = make_agent(server, monitor, spool=spool,
                           breaker_threshold=1, breaker_cooldown=30.0)
        agent._breaker_state = BREAKER_OPEN
        agent._breaker_open_until = 0.0  # cooldown elapsed
        agent._drain(CancelContext())
        # the poisoned record was acked away, but the breaker did NOT
        # close off its back (no real probe ever succeeded)
        assert spool.pending_records() == 0
        assert agent._stats["dropped_total"] == 1
        assert agent._breaker_state != BREAKER_CLOSED
        spool.close()

    def test_long_duplicate_replay_keeps_tracker_alive(self, server):
        # review fix: duplicates refresh node liveness, so a replay
        # longer than stale_after can't get its tracker pruned mid-way
        # and re-ingest the rest of the backlog as fresh windows
        now = [1000.0]
        agg = make_agg(server, stale_after=10.0, clock=lambda: now[0])
        for seq in (1, 2, 3):
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        # replay trickles in slower than stale_after per record
        for seq in (1, 2, 3):
            now[0] += 8.0
            agg.aggregate_once()  # would prune a liveness-stale tracker
            post_report(server, make_report("node-a"), seq=seq, run="r1")
        assert agg._stats["duplicates_total"] == 3  # all absorbed
        assert agg._stats["windows_lost_total"] == 0
        assert "node-a" in agg._seq_trackers  # never pruned mid-replay

    def test_unusable_spool_degrades_healthz(self, tmp_path):
        from kepler_tpu.cmd.main import create_services
        from kepler_tpu.config.config import Builder

        blocker = tmp_path / "not-a-dir"
        blocker.write_text("file where the spool dir should be")
        cfg = Builder().use(f"""
dev: {{fakeCpuMeter: {{enabled: true}}}}
aggregator: {{endpoint: 'http://127.0.0.1:1'}}
agent: {{spool: {{dir: {blocker}}}}}
""").build()
        services = create_services(cfg)
        server = [s for s in services
                  if s.__class__.__name__ == "APIServer"][0]
        ok, components = server.health.check_health()
        assert not ok  # durability was requested and is NOT active
        assert components["fleet-spool"]["ok"] is False
        assert "unusable" in components["fleet-spool"]["error"]
        agent = [s for s in services
                 if s.__class__.__name__ == "FleetAgent"][0]
        assert agent._spool is None  # degraded to the ring, still serving

    def test_spool_probe_and_health(self, server, tmp_path):
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"))
        agent = make_agent(server, monitor, spool=spool)
        assert agent.spool_health()["enabled"]
        assert agent.spool_health()["ok"]
        monitor.emit(make_sample())
        assert agent.backlog() == 1
        assert agent.health()["spool_pending"] == 1
        # spool-less agents report a benign probe
        bare = make_agent(server, FakeMeterMonitor())
        assert bare.spool_health() == {"ok": True, "enabled": False}
        spool.close()

    def test_spool_metrics_collected(self, server, tmp_path):
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest

        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"))
        agent = make_agent(server, monitor, spool=spool)
        monitor.emit(make_sample())
        registry = CollectorRegistry()
        registry.register(agent)
        text = generate_latest(registry).decode()
        assert "kepler_fleet_spool_evicted_total 0.0" in text
        assert "kepler_fleet_spool_pending_records 1.0" in text
        assert "kepler_fleet_spool_utilization_ratio" in text
        assert "kepler_fleet_spool_oldest_record_age_seconds" in text
        spool.close()


_CHILD_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from kepler_tpu.fleet.spool import Spool
from kepler_tpu.fleet.wire import encode_report
from kepler_tpu.parallel.fleet import NodeReport

spool = Spool({spool_dir!r}, fsync="always")
seq = 0
while True:
    seq += 1
    time.sleep(0.001)  # bound the append rate below the spool's caps
    report = NodeReport(
        node_name="crash-node",
        zone_deltas_uj=np.full(2, 1e6, np.float32),
        zone_valid=np.ones(2, bool),
        usage_ratio=0.5,
        cpu_deltas=np.full(3, 1.0, np.float32),
        workload_ids=[f"w{{i}}" for i in range(3)],
        node_cpu_delta=3.0,
        dt_s=5.0,
        mode=0,
    )
    body = encode_report(report, ["package", "dram"], seq=seq,
                         run="crash-run")
    spool.append(body)
    if seq == 1:
        # signal readiness only once a record is DURABLY appended, so
        # the parent's SIGKILL can never race the first append
        sys.stdout.write("ready\n"); sys.stdout.flush()
"""


class TestDeliveryLatencyTelemetry:
    """ISSUE 4: the outage→recovery E2E observes
    kepler_fleet_delivery_latency_seconds for BOTH fresh and replayed
    windows — replays measured from the original appended_at and
    labeled path="replay" so outage backlogs never pollute the
    fresh-delivery signal."""

    def _emit(self, monitor, n, start=0):
        for i in range(n):
            monitor.emit(make_sample(ts=100.0 + start + i))

    def test_outage_recovery_observes_fresh_and_replay(self, server,
                                                       tmp_path):
        now = [1000.0]
        clock = lambda: now[0]  # noqa: E731 — shared frozen clock
        agg = make_agg(server, stale_after=1e9, clock=clock)
        monitor = FakeMeterMonitor()
        spool = Spool(str(tmp_path / "sp"), clock=clock)
        agent = make_agent(server, monitor, spool=spool, clock=clock,
                           breaker_threshold=2, breaker_cooldown=0.01)
        ctx = CancelContext()
        # steady state: two windows deliver fresh, ~0 latency
        self._emit(monitor, 2)
        agent._drain(ctx)
        assert agg._delivery_hist["fresh"].count == 2
        assert agg._delivery_hist["fresh"].sum == 0.0
        assert agg._delivery_hist["replay"].count == 0
        # outage: 3 windows spool while sends fail and the breaker opens
        with fault.installed(FaultPlan([FaultSpec("net.refuse",
                                                  count=2)])):
            self._emit(monitor, 3, start=10)
            agent._drain(ctx)
            assert agent._breaker_state == BREAKER_OPEN
        # recovery 120 s later (agent wall time): the backlog replays,
        # measured from the ORIGINAL append time
        now[0] += 120.0
        time.sleep(0.02)  # real-time breaker cooldown elapses
        agent._drain(ctx)
        assert spool.pending_records() == 0
        replay = agg._delivery_hist["replay"]
        assert replay.count == 3
        assert replay.sum == pytest.approx(3 * 120.0)
        # post-recovery windows are fresh again
        now[0] += 10.0
        self._emit(monitor, 2, start=20)
        agent._drain(ctx)
        fresh = agg._delivery_hist["fresh"]
        assert fresh.count == 4
        assert fresh.sum == 0.0
        assert agg._stats["windows_lost_total"] == 0
        # the histogram is exported with both path labels
        from prometheus_client import CollectorRegistry
        from prometheus_client.exposition import generate_latest
        registry = CollectorRegistry()
        registry.register(agg)
        text = generate_latest(registry).decode()
        assert ('kepler_fleet_delivery_latency_seconds_count{'
                'path="fresh"} 4.0') in text
        assert ('kepler_fleet_delivery_latency_seconds_count{'
                'path="replay"} 3.0') in text
        assert ('kepler_fleet_delivery_latency_seconds_bucket{'
                'le="300.0",path="replay"} 3.0') in text
        agent._close_conn()
        spool.close()

    def test_crash_backlog_replays_with_replay_label(self, server,
                                                     tmp_path):
        # records recovered from a PREVIOUS process's spool are replays
        # by construction (structural flag), even with no send failure
        # in the new run and a frozen clock
        now = [2000.0]
        clock = lambda: now[0]  # noqa: E731
        agg = make_agg(server, stale_after=1e9, clock=clock)
        d = str(tmp_path / "sp")
        monitor = FakeMeterMonitor()
        spool = Spool(d, clock=clock)
        agent = make_agent(server, monitor, spool=spool, clock=clock)
        self._emit(monitor, 3)  # never drained: agent "crashes"
        spool.close()
        now[0] += 300.0  # the node was down five minutes
        spool2 = Spool(d, clock=clock)
        rec = spool2.peek()
        assert rec is not None and rec.recovered
        monitor2 = FakeMeterMonitor()
        agent2 = make_agent(server, monitor2, spool=spool2, clock=clock)
        self._emit(monitor2, 1)  # the new run's own window: fresh
        agent2._drain(CancelContext())
        assert agg._delivery_hist["replay"].count == 3
        assert agg._delivery_hist["replay"].sum == pytest.approx(900.0)
        assert agg._delivery_hist["fresh"].count == 1
        agent2._close_conn()
        spool2.close()

    def test_duplicates_never_observe_twice(self, server, tmp_path):
        # a redelivered report is acked but NOT re-measured: the first
        # copy already closed the delivery trace
        agg = make_agg(server, stale_after=1e9)
        monitor = FakeMeterMonitor()
        d = str(tmp_path / "sp")
        spool = Spool(d)
        agent = make_agent(server, monitor, spool=spool)
        self._emit(monitor, 4)
        agent._drain(CancelContext())
        total = (agg._delivery_hist["fresh"].count
                 + agg._delivery_hist["replay"].count)
        assert total == 4
        agent._close_conn()
        spool.close()
        os.unlink(os.path.join(d, "cursor.json"))  # the "crash"
        spool2 = Spool(d)
        agent2 = FleetAgent(monitor, endpoint=agent._endpoint,
                            node_name="dur-node", spool=spool2,
                            jitter_seed=0)
        agent2._run_nonce = agent._run_nonce  # same logical run
        agent2._drain(CancelContext())
        assert agg._stats["duplicates_total"] == 4
        assert (agg._delivery_hist["fresh"].count
                + agg._delivery_hist["replay"].count) == total
        agent2._close_conn()
        spool2.close()

    def test_pre_telemetry_reports_observe_nothing(self, server):
        # a report without emitted_at (older agent) merges fine and
        # records no latency observation
        agg = make_agg(server)
        post_report(server, make_report("old-agent"), seq=1, run="r1")
        assert agg._stats["reports_total"] == 1
        assert agg._delivery_hist["fresh"].count == 0
        assert agg._delivery_hist["replay"].count == 0

    def test_hostile_delivery_headers_are_clamped(self, server):
        # untrusted label/basis values: an unknown delivery_path falls
        # back to "fresh" (no series minting), a non-numeric
        # appended_at falls back to emitted_at, and a skewed emitted_at
        # in the future clamps at 0 rather than going negative
        agg = make_agg(server, stale_after=1e9, clock=lambda: 100.0)
        blob = encode_report(make_report("hostile"), ["package", "dram"],
                             seq=1, run="r1")
        mutated = mutate_header(blob, emitted_at=50.0,
                                delivery_path="evil-label")
        post_raw(server, mutated)
        assert agg._delivery_hist["fresh"].count == 1
        assert "evil-label" not in agg._delivery_hist
        mutated = mutate_header(blob, seq=2, emitted_at=999.0)
        post_raw(server, mutated)
        assert agg._delivery_hist["fresh"].count == 2
        assert agg._delivery_hist["fresh"].sum == pytest.approx(50.0)
        mutated = mutate_header(blob, seq=3, emitted_at=50.0,
                                delivery_path="replay",
                                appended_at="not-a-number")
        post_raw(server, mutated)
        assert agg._delivery_hist["replay"].count == 1
        assert agg._delivery_hist["replay"].sum == pytest.approx(50.0)


@pytest.mark.chaos
class TestCrashReplayChaos:
    def test_sigkill_mid_append_replays_exactly_once(self, server,
                                                     tmp_path):
        """Satellite: SIGKILL an appending process; every window it
        durably appended before dying is delivered to the aggregator
        exactly once — contiguous seqs, zero loss, zero duplicates."""
        spool_dir = str(tmp_path / "sp")
        script = tmp_path / "child.py"
        script.write_text(_CHILD_SCRIPT.format(repo=REPO,
                                               spool_dir=spool_dir))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen([sys.executable, str(script)],
                                stdout=subprocess.PIPE, env=env)
        try:
            assert proc.stdout.readline().strip() == b"ready"
            time.sleep(0.3)  # let it append mid-flight
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        agg = make_agg(server, stale_after=1e9)
        spool = Spool(spool_dir)
        appended = spool.pending_records()
        assert appended >= 1, "child never appended a record"
        monitor = FakeMeterMonitor()
        agent = make_agent(server, monitor, spool=spool)
        agent._drain(CancelContext())
        assert spool.pending_records() == 0
        tracker = agg._seq_trackers["crash-node"]
        # exactly-once: contiguous 1..N, no gaps, no duplicates
        assert tracker.max_seen == appended
        assert agg._stats["reports_total"] == appended
        assert agg._stats["duplicates_total"] == 0
        assert agg._stats["windows_lost_total"] == 0
        assert agg._reports["crash-node"].seq == appended
        agent._close_conn()
        spool.close()


class TestMonitorStatePersistence:
    """Tentpole layer 3 + satellite boundary tests: counter state
    survives restarts (fresh), is ignored when stale/corrupt, and a
    counter wrap across the restart stays wrap-aware."""

    def _monitored(self, tmp_path, **kw):
        from tests.test_monitor import make_monitor

        return make_monitor(state_path=str(tmp_path / "state.json"), **kw)

    def _restart(self, tmp_path, zones, clock, **kw):
        """Second monitor process: same meter zones, same clocks."""
        from tests.test_monitor import ScriptedMeter
        from tests.test_resource import MockReader

        from kepler_tpu.monitor.monitor import PowerMonitor
        from kepler_tpu.resource import ResourceInformer

        informer = ResourceInformer(reader=MockReader([], usage_ratio=0.5))
        mon = PowerMonitor(ScriptedMeter(zones), informer, clock=clock,
                           workload_bucket=8,
                           state_path=str(tmp_path / "state.json"), **kw)
        mon.init()
        return mon

    def test_restart_attributes_across_the_gap(self, tmp_path):
        mon, _, zones, clock = self._monitored(tmp_path)
        for z in zones:
            z.increment = 1_000_000
        mon.refresh()  # seed
        clock.step(5.0)
        mon.refresh()  # window 1
        e1 = mon.snapshot(clone=False).node.energy_uj.copy()
        # restart: 5 s pass while down; counters keep advancing on read
        clock.step(5.0)
        mon2 = self._restart(tmp_path, zones, clock)
        mon2.refresh()  # first refresh is a REAL window, not a seed
        snap = mon2.snapshot(clone=False)
        # window 2's energy (1 read happened while "down" → one increment)
        assert (snap.node.energy_uj > 0).all()
        # no discarded window: combined totals equal an UNINTERRUPTED run
        # with the identical read schedule (seed + 2 windows)
        from tests.test_monitor import make_monitor

        ctrl, _, ctrl_zones, ctrl_clock = make_monitor()
        for z in ctrl_zones:
            z.increment = 1_000_000
        ctrl.refresh()  # seed
        for _ in range(2):
            ctrl_clock.step(5.0)
            ctrl.refresh()
        uninterrupted = ctrl.snapshot(clone=False).node.energy_uj
        np.testing.assert_allclose(e1 + snap.node.energy_uj, uninterrupted)
        # dt spans the restart gap → finite power, not an inf/0 spike
        assert np.isfinite(snap.node.power_uw).all()

    def test_stale_state_ignored(self, tmp_path, caplog):
        mon, _, zones, clock = self._monitored(tmp_path)
        for z in zones:
            z.increment = 1_000_000
        mon.refresh()
        clock.step(5.0)
        mon.refresh()  # persists fresh state
        clock.step(3600.0)  # way past state_max_age (60 s)
        with caplog.at_level("WARNING", logger="kepler.monitor"):
            mon2 = self._restart(tmp_path, zones, clock)
        assert any("seeding counters" in r.message for r in caplog.records)
        mon2.refresh()  # acts as a seed: zero-energy first snapshot
        assert mon2.snapshot(clone=False).node.energy_uj.sum() == 0.0

    def test_state_max_age_zero_means_unbounded(self, tmp_path):
        # review fix: 0 follows the codebase's 0-disables convention
        # (like skewTolerance) — any-age state restores
        mon, _, zones, clock = self._monitored(tmp_path,
                                               state_max_age=0.0)
        for z in zones:
            z.increment = 1_000_000
        mon.refresh()
        clock.step(5.0)
        mon.refresh()
        clock.step(365 * 24 * 3600.0)  # a year later
        mon2 = self._restart(tmp_path, zones, clock, state_max_age=0.0)
        assert mon2._prev_counters != [None, None]  # restored anyway

    def test_future_state_ignored(self, tmp_path):
        mon, _, zones, clock = self._monitored(tmp_path)
        mon.refresh()
        clock.step(5.0)
        mon.refresh()
        clock.t -= 1000.0  # wall clock stepped backwards across restart
        mon2 = self._restart(tmp_path, zones, clock)
        assert mon2._prev_counters == [None, None]

    @pytest.mark.parametrize("garbage", [
        b"{not json",
        b"",
        b'{"v": 99, "saved_at": 1}',
        b'{"v": 1}',
        b'{"v": 1, "saved_at": 1000.0, "zone_names": ["package"], '
        b'"counters": [1, 2]}',  # length mismatch
        b'{"v": 1, "saved_at": 1000.0, "zone_names": ["package", "dram"], '
        b'"counters": [1, "x"]}',  # bad counter type
        b'{"v": 1, "saved_at": true, "zone_names": [], "counters": []}',
    ])
    def test_corrupt_state_never_crashes_startup(self, tmp_path, garbage,
                                                 caplog):
        path = tmp_path / "state.json"
        path.write_bytes(garbage)
        with caplog.at_level("WARNING", logger="kepler.monitor"):
            mon, _, zones, clock = self._monitored(tmp_path)
        assert mon._prev_counters == [None, None]
        assert any("seeding counters" in r.message
                   for r in caplog.records), garbage
        mon.refresh()  # and the monitor still works

    def test_state_from_previous_boot_ignored(self, tmp_path,
                                              monkeypatch):
        # review fix: a reboot RESETS the counters (they did not wrap);
        # adopting a pre-reboot baseline would fabricate up to a full
        # counter range of energy in the first window
        from kepler_tpu.monitor.monitor import PowerMonitor

        mon, _, zones, clock = self._monitored(tmp_path)
        for z in zones:
            z.increment = 1_000_000
        mon.refresh()
        clock.step(5.0)
        mon.refresh()  # persists state with the current boot_id
        monkeypatch.setattr(PowerMonitor, "_boot_id",
                            staticmethod(lambda: "a-different-boot"))
        zones[0].counter = 0  # the reboot reset the counters
        zones[1].counter = 0
        mon2 = self._restart(tmp_path, zones, clock)
        assert mon2._prev_counters == [None, None]  # reseeded
        mon2.refresh()
        assert mon2.snapshot(clone=False).node.energy_uj.sum() == 0.0

    def test_zone_set_change_ignored(self, tmp_path):
        from tests.test_monitor import ScriptedZone

        mon, _, zones, clock = self._monitored(tmp_path)
        mon.refresh()
        clock.step(1.0)
        mon.refresh()
        other = [ScriptedZone("package"), ScriptedZone("psys")]
        mon2 = self._restart(tmp_path, other, clock)
        assert mon2._prev_counters == [None, None]

    def test_counter_wrap_across_restart_is_wrap_aware(self, tmp_path):
        mon, _, zones, clock = self._monitored(tmp_path)
        max_uj = zones[0]._max
        zones[0].counter = max_uj - 500_000  # near the wrap point
        zones[1].counter = 0
        mon.refresh()  # seeds at max-500k (zone 0); persists the baseline
        zones[0].increment = 1_000_000  # the NEXT read wraps past max
        zones[1].increment = 1_000_000
        clock.step(5.0)
        mon2 = self._restart(tmp_path, zones, clock)
        mon2.refresh()
        snap = mon2.snapshot(clone=False)
        # zone 0 wrapped during the restart: delta must be the wrap-aware
        # 1 MJ, not a negative spike or a bogus huge value
        assert snap.node.energy_uj[0] == pytest.approx(1_000_000.0)
        assert (snap.node.energy_uj >= 0).all()

    def test_state_file_is_atomic_json(self, tmp_path):
        mon, _, zones, clock = self._monitored(tmp_path)
        mon.refresh()
        state = json.loads((tmp_path / "state.json").read_text())
        assert state["v"] == 1
        assert state["zone_names"] == ["package", "dram"]
        assert len(state["counters"]) == 2
        assert not (tmp_path / "state.json.tmp").exists()

    def test_no_state_path_writes_nothing(self, tmp_path):
        from tests.test_monitor import make_monitor

        mon, _, zones, clock = make_monitor()
        mon.refresh()
        assert list(tmp_path.iterdir()) == []


class TestServiceWiring:
    def test_create_services_wires_spool_and_state(self, tmp_path):
        from kepler_tpu.cmd.main import create_services
        from kepler_tpu.config.config import Builder

        cfg = Builder().use(f"""
dev: {{fakeCpuMeter: {{enabled: true}}}}
monitor: {{statePath: {tmp_path / 'state.json'}}}
aggregator: {{endpoint: 'http://127.0.0.1:1'}}
agent: {{spool: {{dir: {tmp_path / 'spool'}}}}}
""").build()
        services = create_services(cfg)
        agents = [s for s in services if isinstance(s, FleetAgent)]
        assert len(agents) == 1
        agent = agents[0]
        assert agent._spool is not None
        assert agent.spool_health()["enabled"]
        monitors = [s for s in services
                    if s.__class__.__name__ == "PowerMonitor"]
        assert monitors[0]._state_path.endswith("state.json")
        # the spool probe landed in the health registry
        server = [s for s in services
                  if s.__class__.__name__ == "APIServer"][0]
        ok, components = server.health.check_health()
        assert "fleet-spool" in components
        # the self-telemetry trace endpoint is on the APIServer
        assert "/debug/traces" in server._endpoints
        agent._spool.close()


class TestConfigKnobs:
    def test_yaml_spelling_roundtrip(self):
        from kepler_tpu.config.config import Builder

        cfg = Builder().use("""
monitor: {statePath: /var/lib/kepler/state.json, stateMaxAge: 2m}
aggregator: {dedupWindow: 64}
agent:
  spool:
    dir: /var/lib/kepler/spool
    maxBytes: 1048576
    maxRecords: 128
    segmentBytes: 65536
    fsync: always
    fsyncInterval: 500ms
""").build()
        assert cfg.monitor.state_path == "/var/lib/kepler/state.json"
        assert cfg.monitor.state_max_age == 120.0
        assert cfg.aggregator.dedup_window == 64
        assert cfg.agent.spool.dir == "/var/lib/kepler/spool"
        assert cfg.agent.spool.max_bytes == 1048576
        assert cfg.agent.spool.max_records == 128
        assert cfg.agent.spool.segment_bytes == 65536
        assert cfg.agent.spool.fsync == "always"
        assert cfg.agent.spool.fsync_interval == 0.5
        cfg.validate(skip=("host",))

    def test_validation_rejects_bad_values(self):
        from kepler_tpu.config.config import Builder

        cfg = Builder().use("""
monitor: {stateMaxAge: -1}
aggregator: {dedupWindow: 0}
agent: {spool: {fsync: sometimes, maxBytes: 0}}
""").build()
        with pytest.raises(ValueError) as err:
            cfg.validate(skip=("host",))
        msg = str(err.value)
        for frag in ("stateMaxAge", "dedupWindow", "fsync", "maxBytes"):
            assert frag in msg

    def test_flags_overlay(self):
        from kepler_tpu.config.config import parse_args_and_config

        cfg = parse_args_and_config([
            "--monitor.state-path", "/tmp/state.json",
            "--agent.spool-dir", "/tmp/spool",
            "--aggregator.dedup-window", "99",
        ], skip_validation=("host",))
        assert cfg.monitor.state_path == "/tmp/state.json"
        assert cfg.agent.spool.dir == "/tmp/spool"
        assert cfg.aggregator.dedup_window == 99


class TestHlcHeaderCoercion:
    """Satellite (ISSUE 19): the ``X-Kepler-HLC`` stamp is wire input —
    hardened exactly like run/seq and the ring headers. Hostile text is
    a 400 charged as malformed, never a 500 and NEVER a poisoned clock;
    a *valid* but future-vaulted stamp is clamped by
    ``aggregator.hlcMaxDrift`` (KTL112: laundered, bounded, counted)."""

    @staticmethod
    def post_with_hlc(server, body, hlc_text):
        host, port = server.addresses[0]
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/report", data=body, method="POST",
            headers={"X-Kepler-HLC": hlc_text})
        return urllib.request.urlopen(req, timeout=5)

    @staticmethod
    def make_journaled_agg(server, **kw):
        from kepler_tpu.fleet.journal import EventJournal
        jnl = EventJournal(enabled=True, node="agg-hlc",
                           max_drift_s=kw.pop("max_drift_s", 60.0))
        return make_agg(server, journal=jnl), jnl

    @pytest.mark.parametrize("hostile", [
        "garbage", "True", "1:2", "::", "-1:0:n", "1.5:0:n",
        "1:-1:n", "1:+1:n", "999999999999999999:0:n",   # 18-digit phys
        "1:0:" + "x" * 200,                             # overlong node
        "1:0:a b",                                      # space in node
    ])
    def test_hostile_stamp_is_400_never_500(self, server, hostile):
        agg, jnl = self.make_journaled_agg(server)
        before = jnl.hlc.now()
        blob = encode_report(make_report("hlc-node"),
                             ["package", "dram"], seq=1, run="r1")
        with pytest.raises(urllib.error.HTTPError) as err:
            self.post_with_hlc(server, blob, hostile)
        assert err.value.code == 400
        assert b"X-Kepler-HLC" in err.value.read()
        assert agg._stats["malformed_total"] == 1
        assert "hlc-node" not in agg._reports           # nothing ingested
        # the clock never merged the hostile stamp
        assert jnl.hlc.clamped_total() == 0
        assert jnl.hlc.now().phys_us - before.phys_us < 10_000_000

    def test_future_vaulted_stamp_is_clamped_not_trusted(self, server):
        agg, jnl = self.make_journaled_agg(server, max_drift_s=60.0)
        blob = encode_report(make_report("vault"),
                             ["package", "dram"], seq=1, run="r1")
        vaulted = f"{10**16}:0:evil"                    # ~year 2286
        resp = self.post_with_hlc(server, blob, vaulted)
        assert resp.status == 204                       # valid shape: accepted
        assert "vault" in agg._reports
        assert jnl.hlc.clamped_total() == 1
        # the local clock advanced by at most the drift bound
        assert jnl.hlc.now().phys_us < time.time() * 1e6 + 61 * 1e6
        # the hostile offset is visible for alerting
        assert jnl.hlc.drift_seconds() > 1e6

    def test_valid_stamp_merges_and_reply_carries_hlc(self, server):
        agg, jnl = self.make_journaled_agg(server)
        blob = encode_report(make_report("chain"),
                             ["package", "dram"], seq=1, run="r1")
        peer_us = int(time.time() * 1e6) + 1_000_000    # 1s ahead: legal
        resp = self.post_with_hlc(server, blob, f"{peer_us}:3:peer-a")
        assert resp.status == 204
        assert jnl.hlc.clamped_total() == 0
        assert jnl.hlc.drift_seconds() == pytest.approx(1.0, abs=0.5)
        # accept replies piggyback this replica's stamp for the agent
        got = resp.headers.get("X-Kepler-HLC")
        assert got is not None
        from kepler_tpu.telemetry.hlc import parse_hlc
        stamp = parse_hlc(got)
        assert stamp is not None and stamp.node == "agg-hlc"
        assert stamp.phys_us >= peer_us                 # causally after
        assert "chain" in agg._reports

    def test_absent_header_is_fine(self, server):
        agg, jnl = self.make_journaled_agg(server)
        blob = encode_report(make_report("plain"),
                             ["package", "dram"], seq=1, run="r1")
        assert post_raw(server, blob).status == 204
        assert agg._stats["malformed_total"] == 0

    def test_disabled_journal_ignores_even_hostile_stamps(self, server):
        """Journal off (the default): the HLC seam must cost nothing —
        no parse, no 400, no header on the reply."""
        agg = make_agg(server)
        blob = encode_report(make_report("off"),
                             ["package", "dram"], seq=1, run="r1")
        resp = self.post_with_hlc(server, blob, "total garbage")
        assert resp.status == 204
        assert resp.headers.get("X-Kepler-HLC") is None
        assert "off" in agg._reports

    def test_batch_path_rejects_hostile_stamp(self, server):
        from kepler_tpu.fleet.wire import encode_report_batch

        agg, jnl = self.make_journaled_agg(server)
        blob = encode_report_batch([
            encode_report(make_report("b1"), ["package", "dram"],
                          seq=1, run="r1")])
        host, port = server.addresses[0]
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/reports", data=blob,
            method="POST", headers={"X-Kepler-HLC": "evil"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5)
        assert err.value.code == 400
        assert "b1" not in agg._reports
