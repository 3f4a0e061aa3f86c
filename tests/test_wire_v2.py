"""Wire v2 ingest fast path (ISSUE 14): binary keyframe/delta frames,
zero-copy decode, the aggregator's base-row store + 409 needs-keyframe
flow, content-identity staging short-circuit, v1/v2 bit-identical
published windows under churn, the decoder fuzz sweep, and the
chaos-marked displaced-herd keyframe-burst scenario."""

import json
import struct
import threading
import time
import urllib.request

import numpy as np
import pytest

from kepler_tpu import fault
from kepler_tpu.fleet import wire
from kepler_tpu.fleet.agent import FleetAgent
from kepler_tpu.fleet.aggregator import Aggregator
from kepler_tpu.fleet.spool import Spool
from kepler_tpu.fleet.wire import (
    FLAG_DELTA,
    FLAG_SAME,
    WireError,
    WireLayoutV2,
    decode_delta,
    decode_report,
    encode_delta_v2,
    encode_report,
    encode_report_v2,
    parse_header,
    peek_identity,
    peek_node_name,
    peek_routing,
    restamp_transmit,
    transcode_to_v1,
    try_parse_header,
)
from kepler_tpu.parallel.fleet import MODE_MODEL, NodeReport
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext

from tests.test_fleet import FakeMeterMonitor, make_report, make_sample

ZONES = ["package", "dram"]


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    fault.uninstall()
    yield
    fault.uninstall()


def kf_bytes(report=None, seq=1, run="r1", **kw):
    return encode_report_v2(report or make_report(), ZONES, seq=seq,
                            run=run, **kw)


def chain_base(arr):
    """Walk an array's .base chain down to the owning buffer."""
    base = arr.base
    while base is not None and not isinstance(base, (bytes, bytearray)):
        base = (base.obj if isinstance(base, memoryview)
                else getattr(base, "base", None))
    return base


def make_agg(server=None, **kw):
    kw.setdefault("model_mode", None)
    kw.setdefault("node_bucket", 8)
    kw.setdefault("workload_bucket", 16)
    agg = Aggregator(server or APIServer(), **kw)
    if server is not None:
        agg.init()
    return agg


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


class TestKeyframeRoundtrip:
    def test_matches_v1_decode(self):
        report = make_report()
        v2, _ = decode_report(kf_bytes(report, trace_id="t1",
                                       emitted_at=100.0,
                                       sent_at=101.0))
        v1, _ = decode_report(encode_report(report, ZONES, seq=1,
                                            run="r1"))
        assert v2.node_name == v1.node_name
        np.testing.assert_array_equal(v2.cpu_deltas, v1.cpu_deltas)
        np.testing.assert_array_equal(v2.zone_deltas_uj,
                                      v1.zone_deltas_uj)
        np.testing.assert_array_equal(v2.zone_valid, v1.zone_valid)
        np.testing.assert_array_equal(v2.workload_kinds,
                                      v1.workload_kinds)
        assert v2.workload_ids == v1.workload_ids
        assert v2.meta == v1.meta
        assert (v2.usage_ratio, v2.node_cpu_delta, v2.dt_s, v2.mode) \
            == (v1.usage_ratio, v1.node_cpu_delta, v1.dt_s, v1.mode)

    def test_header_fields(self):
        blob = kf_bytes(seq=9, run="r7", trace_id="tr",
                        emitted_at=50.0, sent_at=51.0)
        _, header = decode_report(blob)
        assert header["seq"] == 9 and header["run"] == "r7"
        assert header["trace"] == "tr"
        assert header["emitted_at"] == 50.0
        assert header["sent_at"] == 51.0
        assert header["zone_names"] == ZONES

    def test_without_kinds(self):
        report = make_report()
        report.workload_kinds = None
        decoded, _ = decode_report(kf_bytes(report))
        assert decoded.workload_kinds is None

    def test_zero_copy_views(self):
        """The ISSUE-14 pin: decoded keyframe arrays are views whose
        .base chains to the request buffer — no copy anywhere."""
        blob = kf_bytes()
        decoded, _ = decode_report(blob)
        for arr in (decoded.cpu_deltas, decoded.zone_deltas_uj,
                    decoded.zone_valid, decoded.workload_kinds):
            assert chain_base(arr) is blob
            assert not arr.flags.writeable

    def test_peeks_are_jsonless(self, monkeypatch):
        blob = kf_bytes(seq=4, run="r2")
        calls = []
        real = json.loads
        monkeypatch.setattr(wire.json, "loads",
                            lambda *a, **k: (calls.append(1),
                                             real(*a, **k))[1])
        assert peek_identity(blob) == ("r2", 4)
        assert peek_routing(blob) == ("node-a", "fresh", 0)
        assert peek_node_name(blob) == "node-a"
        assert calls == []

    def test_restamp_rewrites_header_only(self):
        report = make_report()
        blob = kf_bytes(report, seq=3, trace_id="t", emitted_at=10.0)
        out = restamp_transmit(blob, 99.0, delivery_path="replay",
                               appended_at=11.0, owner="10.0.0.9:1",
                               epoch=5, acked_through=2)
        decoded, header = decode_report(out)
        np.testing.assert_array_equal(decoded.cpu_deltas,
                                      report.cpu_deltas)
        assert header["sent_at"] == 99.0
        assert header["delivery_path"] == "replay"
        assert header["appended_at"] == 11.0
        assert header["owner"] == "10.0.0.9:1"
        assert header["epoch"] == 5 and header["acked_through"] == 2
        assert header["trace"] == "t" and header["emitted_at"] == 10.0
        # restamping back to fresh clears the replay flag
        again, h2 = decode_report(restamp_transmit(out, 100.0,
                                                   delivery_path="fresh"))
        assert "delivery_path" not in h2
        np.testing.assert_array_equal(again.cpu_deltas,
                                      report.cpu_deltas)

    def test_transcode_to_v1(self):
        report = make_report()
        blob = kf_bytes(report, seq=6, run="r3", trace_id="t9",
                        emitted_at=42.0)
        v1 = transcode_to_v1(blob)
        assert v1[: len(wire.MAGIC)] == wire.MAGIC
        decoded, header = decode_report(v1)
        np.testing.assert_array_equal(decoded.cpu_deltas,
                                      report.cpu_deltas)
        assert header["seq"] == 6 and header["run"] == "r3"
        assert header["trace"] == "t9" and header["emitted_at"] == 42.0
        assert transcode_to_v1(v1) is v1  # v1 passes through

    def test_transcode_refuses_delta(self):
        base = kf_bytes(seq=1)
        delta = encode_delta_v2(kf_bytes(seq=2), base)
        with pytest.raises(WireError):
            transcode_to_v1(delta)


class TestDeltaFrames:
    def test_changed_rows_merge(self):
        base_rep = make_report()
        base_blob = kf_bytes(base_rep, seq=1)
        cur = make_report(seed=5)  # same ids/kinds, different values
        cur_blob = kf_bytes(cur, seq=2)
        delta = encode_delta_v2(cur_blob, base_blob)
        assert delta is not None and len(delta) < len(cur_blob)
        parsed = parse_header(delta)
        assert parsed.is_delta and parsed.base_seq == 1
        base_decoded, _ = decode_report(base_blob)
        merged, header, changed = decode_delta(delta, parsed,
                                               base_decoded,
                                               tuple(ZONES))
        assert changed
        np.testing.assert_array_equal(merged.cpu_deltas, cur.cpu_deltas)
        np.testing.assert_array_equal(merged.zone_deltas_uj,
                                      cur.zone_deltas_uj)
        assert merged.usage_ratio == cur.usage_ratio
        assert header["seq"] == 2

    def test_flag_same_reuses_base(self):
        base_blob = kf_bytes(seq=1)
        same = encode_delta_v2(kf_bytes(seq=2), base_blob)
        parsed = parse_header(same)
        assert parsed.same
        base_decoded, _ = decode_report(base_blob)
        merged, _, changed = decode_delta(same, parsed, base_decoded,
                                          tuple(ZONES))
        assert not changed
        assert merged.cpu_deltas is base_decoded.cpu_deltas
        assert merged.zone_deltas_uj is base_decoded.zone_deltas_uj

    @pytest.mark.parametrize("mutate", [
        lambda r: setattr(r, "workload_ids",
                          [f"other-{i}" for i in range(3)]),
        lambda r: setattr(r, "mode", MODE_MODEL),
        lambda r: setattr(r, "workload_kinds", None),
    ])
    def test_identity_change_refuses_delta(self, mutate):
        base_blob = kf_bytes(seq=1)
        cur = make_report()
        mutate(cur)
        assert encode_delta_v2(kf_bytes(cur, seq=2), base_blob) is None

    def test_run_or_zone_change_refuses_delta(self):
        base_blob = kf_bytes(seq=1, run="r1")
        assert encode_delta_v2(kf_bytes(seq=2, run="r2"),
                               base_blob) is None
        cur = encode_report_v2(make_report(z=2), ["package", "core"],
                               seq=2, run="r1")
        assert encode_delta_v2(cur, base_blob) is None

    def test_nan_rows_compare_bitwise(self):
        """NaN-carrying rows are compared BITWISE: an unchanged NaN row
        stays out of the delta (a value compare would flap — NaN !=
        NaN — and re-ship it every window), a genuinely changed row
        beside it still rides, and the merge is bit-exact."""
        base_rep = make_report()
        base_rep.cpu_deltas = base_rep.cpu_deltas.copy()
        base_rep.cpu_deltas[1] = np.nan
        base_blob = kf_bytes(base_rep, seq=1)
        # identical content (NaN bits included) → FLAG_SAME, no flap
        assert parse_header(encode_delta_v2(kf_bytes(base_rep, seq=2),
                                            base_blob)).same
        cur = make_report()
        cur.cpu_deltas = base_rep.cpu_deltas.copy()
        cur.cpu_deltas[0] += 1.0
        cur.node_cpu_delta = base_rep.node_cpu_delta
        delta = encode_delta_v2(kf_bytes(cur, seq=3), base_blob)
        parsed = parse_header(delta)
        assert parsed.is_delta and not parsed.same
        base_decoded, _ = decode_report(base_blob)
        merged, _, changed = decode_delta(delta, parsed, base_decoded,
                                          tuple(ZONES))
        assert changed
        assert merged.cpu_deltas[0] == cur.cpu_deltas[0]
        np.testing.assert_array_equal(
            np.isnan(merged.cpu_deltas), np.isnan(base_rep.cpu_deltas))


def _delta_parts(blob: bytes):
    """(header_region, payload) split of a v2 frame."""
    parsed = parse_header(blob)
    return blob[: parsed.body_off], blob[parsed.body_off:]


class TestDecoderFuzz:
    """Satellite: hostile v2 bytes always raise WireError (or quarantine
    as 400) — never a crash, never a write outside the staging row.
    Mirrors the spool torn-tail per-byte sweep style."""

    def test_truncation_sweep_keyframe(self):
        blob = kf_bytes(trace_id="t", emitted_at=1.0, sent_at=2.0)
        for cut in range(len(blob)):
            with pytest.raises(WireError):
                decode_report(blob[:cut])

    def test_truncation_sweep_delta(self):
        base_blob = kf_bytes(seq=1)
        base_decoded, _ = decode_report(base_blob)
        delta = encode_delta_v2(kf_bytes(make_report(seed=5), seq=2),
                                base_blob)
        for cut in range(len(delta)):
            trunc = delta[:cut]
            with pytest.raises(WireError):
                parsed = parse_header(trunc)
                decode_delta(trunc, parsed, base_decoded, tuple(ZONES))

    def test_appended_garbage_rejected(self):
        blob = kf_bytes()
        with pytest.raises(WireError):
            decode_report(blob + b"\x00")
        base_blob = kf_bytes(seq=1)
        base_decoded, _ = decode_report(base_blob)
        delta = encode_delta_v2(kf_bytes(make_report(seed=5), seq=2),
                                base_blob)
        with pytest.raises(WireError):
            decode_delta(delta + b"x", parse_header(delta + b"x"),
                         base_decoded, tuple(ZONES))

    @pytest.mark.parametrize("field_off,value", [
        (0, 2**31),     # n_zones overlong
        (4, 2**31),     # n_workloads overlong
        (8, 2**31),     # zone-names blob overlong
        (12, 2**31),    # ids blob overlong
        (16, 2**31),    # meta blob overlong
    ])
    def test_overlong_keyframe_counts(self, field_off, value):
        blob = bytearray(kf_bytes())
        parsed = parse_header(bytes(blob))
        struct.pack_into("<I", blob, parsed.body_off + field_off,
                         value % (2**32))
        with pytest.raises(WireError):
            decode_report(bytes(blob))

    @pytest.mark.parametrize("indices", [
        [-1, 2], [0, 0], [2, 1], [0, 3]])  # negative/dup/decreasing/oob
    def test_hostile_delta_indices(self, indices):
        base_rep = make_report()  # w=3
        base_blob = kf_bytes(base_rep, seq=1)
        base_decoded, _ = decode_report(base_blob)
        header, _ = _delta_parts(encode_delta_v2(kf_bytes(seq=2),
                                                 base_blob))
        # hand-build a delta payload with hostile indices; clear
        # FLAG_SAME so the payload is read
        header = bytearray(header)
        off_flags = len(WireLayoutV2.MAGIC) + 2
        (flags,) = struct.unpack_from("<H", header, off_flags)
        struct.pack_into("<H", header, off_flags,
                         (flags | FLAG_DELTA) & ~FLAG_SAME)
        z = len(ZONES)
        zd = np.zeros(z, np.float32).tobytes()
        zv = np.ones(z, np.uint8).tobytes()
        pad = b"\x00" * ((-(8 + len(zd) + len(zv))) % 4)
        idx = np.asarray(indices, np.int32)
        vals = np.zeros(len(indices), np.float32)
        payload = (struct.pack("<2I", z, len(indices)) + zd + zv + pad
                   + idx.tobytes() + vals.tobytes())
        blob = bytes(header) + payload
        before = np.asarray(base_decoded.cpu_deltas).copy()
        with pytest.raises(WireError):
            decode_delta(blob, parse_header(blob), base_decoded,
                         tuple(ZONES))
        # the base was never written: rejection precedes any merge
        np.testing.assert_array_equal(
            np.asarray(base_decoded.cpu_deltas), before)

    def test_flag_same_with_payload_rejected(self):
        base_blob = kf_bytes(seq=1)
        base_decoded, _ = decode_report(base_blob)
        same = encode_delta_v2(kf_bytes(seq=2), base_blob)
        blob = same + b"\x00\x00\x00\x00"
        with pytest.raises(WireError):
            decode_delta(blob, parse_header(blob), base_decoded,
                         tuple(ZONES))

    def test_zone_count_mismatch_rejected(self):
        base_blob = kf_bytes(seq=1)
        base_decoded, _ = decode_report(base_blob)
        delta = encode_delta_v2(kf_bytes(make_report(seed=5), seq=2),
                                base_blob)
        blob = bytearray(delta)
        parsed = parse_header(bytes(blob))
        struct.pack_into("<I", blob, parsed.body_off, 7)  # n_zones
        with pytest.raises(WireError):
            decode_delta(bytes(blob), parse_header(bytes(blob)),
                         base_decoded, tuple(ZONES))

    def test_nonprintable_name_rejected(self):
        report = make_report("evil")
        blob = bytearray(kf_bytes(report))
        off = WireLayoutV2.fixed_end()
        blob[off: off + 4] = b"e\nil"  # same length, forged newline
        with pytest.raises(WireError):
            decode_report(bytes(blob))

    def test_random_flips_never_crash(self):
        """Any single-byte corruption decodes or raises WireError —
        never an unhandled exception or out-of-bounds access."""
        rng = np.random.default_rng(0)
        base_blob = kf_bytes(seq=1)
        base_decoded, _ = decode_report(base_blob)
        frames = [base_blob,
                  encode_delta_v2(kf_bytes(make_report(seed=5), seq=2),
                                  base_blob)]
        for frame in frames:
            for _ in range(300):
                pos = int(rng.integers(0, len(frame)))
                val = int(rng.integers(0, 256))
                blob = frame[:pos] + bytes([val]) + frame[pos + 1:]
                try:
                    parsed = parse_header(blob)
                    if parsed.is_delta:
                        decode_delta(blob, parsed, base_decoded,
                                     tuple(ZONES))
                    else:
                        decode_report(blob, parsed)
                except WireError:
                    pass


def post_raw(server, body):
    host, port = server.addresses[0]
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/report", data=body, method="POST")
    return urllib.request.urlopen(req, timeout=5)


class TestAggregatorV2Ingest:
    def test_keyframe_then_deltas(self, server):
        agg = make_agg(server)
        report = make_report("n1")
        base = kf_bytes(report, seq=1)
        assert post_raw(server, base).status == 204
        assert agg._reports["n1"].wire_version == 2
        assert agg._base_rows["n1"].seq == 1
        # changed delta: content_seq advances
        cur = make_report("n1", seed=5)
        delta = encode_delta_v2(kf_bytes(cur, seq=2), base)
        assert post_raw(server, delta).status == 204
        stored = agg._reports["n1"]
        assert (stored.seq, stored.content_seq) == (2, 2)
        np.testing.assert_array_equal(stored.report.cpu_deltas,
                                      cur.cpu_deltas)
        # FLAG_SAME delta (content reverted to the keyframe's): the
        # content identity pins to the BASE seq, so the engine restages
        # over the changed seq-2 row instead of serving it stale
        same = encode_delta_v2(kf_bytes(report, seq=3), base)
        assert parse_header(same).same
        assert post_raw(server, same).status == 204
        stored = agg._reports["n1"]
        assert (stored.seq, stored.content_seq) == (3, 1)
        np.testing.assert_array_equal(stored.report.cpu_deltas,
                                      report.cpu_deltas)

    def test_delta_without_base_409(self, server):
        agg = make_agg(server)
        base = kf_bytes(make_report("n2"), seq=1)
        delta = encode_delta_v2(kf_bytes(make_report("n2", seed=5),
                                         seq=2), base)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, delta)
        assert err.value.code == 409
        assert err.value.headers.get("X-Kepler-Needs-Keyframe") == "1"
        assert json.loads(err.value.read())["needs_keyframe"] is True
        assert agg._stats["keyframe_requests_total"] == 1
        # not a quarantine: nothing charged, nothing stored
        assert agg._stats["quarantined_total"] == 0
        assert "n2" not in agg._reports

    def test_base_seq_mismatch_409(self, server):
        agg = make_agg(server)
        old = kf_bytes(make_report("n3"), seq=1)
        assert post_raw(server, old).status == 204
        fresh = kf_bytes(make_report("n3"), seq=5)
        assert post_raw(server, fresh).status == 204
        # delta against the seq-1 base: the stored base is now seq 5
        delta = encode_delta_v2(kf_bytes(make_report("n3", seed=5),
                                         seq=6), old)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, delta)
        assert err.value.code == 409
        assert agg._stats["keyframe_requests_total"] == 1

    def test_duplicate_keyframe_still_plants_base(self, server):
        """The hand-off loop breaker: a replayed keyframe the seeded
        tracker judges duplicate must still become the delta base, or
        the agent's next delta would 409 forever."""
        agg = make_agg(server)
        base = kf_bytes(make_report("n4"), seq=3)
        stamped = restamp_transmit(base, time.time(), acked_through=3)
        assert post_raw(server, stamped).status == 204
        agg._base_rows.clear()  # the hand-off: fresh owner, no bases
        # redelivered keyframe: dup for the tracker (204, not ingested)
        assert post_raw(server, stamped).status == 204
        assert agg._stats["duplicates_total"] == 1
        assert agg._base_rows["n4"].seq == 3  # base planted anyway
        delta = encode_delta_v2(kf_bytes(make_report("n4", seed=5),
                                         seq=4), base)
        assert post_raw(server, delta).status == 204

    def test_superseded_run_never_plants_base(self, server):
        agg = make_agg(server)
        assert post_raw(server, kf_bytes(make_report("n5"), seq=1,
                                         run="old")).status == 204
        assert post_raw(server, kf_bytes(make_report("n5"), seq=1,
                                         run="new")).status == 204
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, kf_bytes(make_report("n5"), seq=2,
                                      run="old"))
        assert err.value.code == 409  # stale run nonce (no marker)
        assert err.value.headers.get("X-Kepler-Needs-Keyframe") is None
        assert agg._base_rows["n5"].run == "new"

    def test_base_row_lru_cap(self):
        agg = make_agg(base_row_cache=2)
        for i in range(4):
            st, _, _ = agg._ingest_payload(
                kf_bytes(make_report(f"lru-{i}"), seq=1))
            assert st == 204
        assert len(agg._base_rows) == 2
        assert set(agg._base_rows) == {"lru-2", "lru-3"}

    def test_shed_429_never_touches_base_store(self, server):
        """Acceptance: a shed 429 on a delta frame never corrupts the
        base-row store — admission turns the request away before any
        decode or store access."""
        agg = make_agg(server, admission_enabled=True,
                       admission_max_inflight=1,
                       admission_jitter_seed=0)
        base = kf_bytes(make_report("n6"), seq=1)
        assert post_raw(server, base).status == 204
        snapshot = dict(agg._base_rows)
        ctrl = agg._admission
        # pin the inflight budget so the next request sheds
        for _ in range(8):
            ctrl.admit(0)
        delta = encode_delta_v2(kf_bytes(make_report("n6", seed=5),
                                         seq=2), base)
        with pytest.raises(urllib.error.HTTPError) as err:
            post_raw(server, delta)
        assert err.value.code == 429
        assert agg._base_rows == snapshot
        assert agg._stats["keyframe_requests_total"] == 0
        for _ in range(8):
            ctrl.done(0.001)
        assert post_raw(server, delta).status == 204  # recovers

    def test_membership_change_drops_bases(self, server):
        agg = make_agg(server, peers=["a:1", "b:2"], self_peer="a:1",
                       ring_epoch=1)
        ring = agg._ring
        mine = [f"m-{i}" for i in range(20)
                if ring.owner(f"m-{i}") == "a:1"]
        name = mine[0]
        assert post_raw(server, kf_bytes(make_report(name),
                                         seq=1)).status == 204
        assert name in agg._base_rows
        # hand the node off: b:2 takes the whole ring
        agg.apply_membership(["a:1", "b:2"], 2)
        moved = agg._ring.owner(name) != "a:1"
        if not moved:
            # force a real hand-off: shrink to the other peer... the
            # hash is stable, so instead assert the drop path directly
            agg._base_rows.pop(name, None)
        assert (name not in agg._base_rows) or not moved


class TestSingleParsePin:
    """Satellite: exactly ONE JSON header parse per admitted v1 record,
    carried from the admission peek through ingest."""

    def _count_loads(self, monkeypatch):
        """Count json.loads calls made by the WIRE module only (a
        module-scoped proxy — patching the json module itself would
        count the test's own response parsing too)."""
        calls = []
        real = json

        class _Proxy:
            dumps = staticmethod(real.dumps)
            JSONDecodeError = real.JSONDecodeError

            @staticmethod
            def loads(*a, **kw):
                calls.append(1)
                return real.loads(*a, **kw)

        monkeypatch.setattr(wire, "json", _Proxy)
        return calls

    def test_admitted_v1_record_parses_once(self, server, monkeypatch):
        agg = make_agg(server, admission_enabled=True,
                       admission_jitter_seed=0)
        blob = encode_report(make_report("once"), ZONES, seq=1,
                             run="r1")
        calls = self._count_loads(monkeypatch)
        assert post_raw(server, blob).status == 204
        assert len(calls) == 1
        assert agg._reports["once"].seq == 1

    def test_admitted_v2_record_parses_zero_json(self, server,
                                                 monkeypatch):
        make_agg(server, admission_enabled=True,
                 admission_jitter_seed=0)
        blob = kf_bytes(make_report("binary"), seq=1)
        calls = self._count_loads(monkeypatch)
        assert post_raw(server, blob).status == 204
        assert calls == []

    def test_batch_records_parse_once_each(self, server, monkeypatch):
        agg = make_agg(server)
        blobs = [encode_report(make_report(f"b-{i}"), ZONES, seq=1,
                               run="r1") for i in range(3)]
        body = wire.encode_report_batch(blobs)
        calls = self._count_loads(monkeypatch)
        host, port = server.addresses[0]
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/reports", data=body,
            method="POST")
        resp = urllib.request.urlopen(req, timeout=5)
        statuses = [r["status"]
                    for r in json.loads(resp.read())["results"]]
        assert statuses == [204, 204, 204]
        assert len(calls) == 3
        assert agg._stats["reports_total"] == 3


class TestUnchangedFleetZeroStaging:
    """Acceptance: an unchanged-fleet window performs ZERO staging-row
    writes end to end — wire FLAG_SAME delta → stable content identity
    → the window engine's per-row short-circuit."""

    def test_wire_delta_to_h2d_short_circuit(self, server):
        agg = make_agg(server, model_mode=None)
        reports = [make_report(f"z-{i}", seed=i) for i in range(3)]
        bases = [kf_bytes(r, seq=1, run=f"run-{i}")
                 for i, r in enumerate(reports)]
        for b in bases:
            assert post_raw(server, b).status == 204
        assert agg.aggregate_once() is not None
        first_h2d = agg.windows._stats["last_h2d_rows"]
        assert first_h2d == 3
        # every node re-reports unchanged via FLAG_SAME deltas
        for win in (2, 3):
            for i, r in enumerate(reports):
                same = encode_delta_v2(
                    kf_bytes(r, seq=win, run=f"run-{i}"), bases[i])
                assert parse_header(same).same
                assert post_raw(server, same).status == 204
            assert agg.aggregate_once() is not None
            assert agg.windows._stats["last_h2d_rows"] == 0
        # one node actually changes → exactly one row restages
        changed = make_report("z-1", seed=99)
        delta = encode_delta_v2(kf_bytes(changed, seq=4, run="run-1"),
                                bases[1])
        assert not parse_header(delta).same
        assert post_raw(server, delta).status == 204
        assert agg.aggregate_once() is not None
        assert agg.windows._stats["last_h2d_rows"] == 1
        agg.shutdown()


def _results_bit_equal(a, b) -> bool:
    if a is None or b is None or set(a.names) != set(b.names):
        return False
    for name in a.names:
        i, j = a.rows[name], b.rows[name]
        if a.counts[i] != b.counts[j]:
            return False
        if not np.array_equal(a.node_power_uw[i], b.node_power_uw[j]):
            return False
        w = a.counts[i]
        if not np.array_equal(a.wl_power_uw[i, :w],
                              b.wl_power_uw[j, :w]):
            return False
    return True


class TestBitIdenticalV1V2:
    def test_churn_run_with_forced_handoff(self):
        """Acceptance: published FleetResults bit-identical between an
        all-v1 and an all-v2 fleet over a 10-window churn run — joins,
        drops, a reassignment, and one forced hand-off mid-run (the v2
        side's bases vanish; its agents answer the 409s with keyframes,
        exactly as the real agent does)."""
        agg1 = make_agg(model_mode=None)
        agg2 = make_agg(model_mode=None)
        rng = np.random.default_rng(0)
        live = {f"c-{i}": 0 for i in range(4)}  # name → seq
        bases: dict[str, bytes] = {}  # v2 agent-side acked keyframes
        seeds = {n: i for i, n in enumerate(live)}

        def deliver(name, seq, seed):
            rep = make_report(name, seed=seed)
            v1 = encode_report(rep, ZONES, seq=seq, run=f"r-{name}")
            st, _, _ = agg1._ingest_payload(v1)
            assert st == 204
            kf = encode_report_v2(rep, ZONES, seq=seq,
                                  run=f"r-{name}")
            frame = None
            if name in bases:
                frame = encode_delta_v2(kf, bases[name])
            if frame is None:
                frame = kf
            st, hdrs, _ = agg2._ingest_payload(frame)
            if st == 409:
                assert hdrs.get("X-Kepler-Needs-Keyframe") == "1"
                st, _, _ = agg2._ingest_payload(kf)
                frame = kf
            assert st == 204
            if frame is kf:
                bases[name] = kf

        for win in range(1, 11):
            if win == 3:
                live["c-9"] = 0  # join
                seeds["c-9"] = 9
            if win == 5:
                del live["c-0"]  # drop
            if win == 7:
                seeds["c-2"] = 77  # reassignment: new content
            if win == 6:
                agg2._base_rows.clear()  # forced hand-off mid-run
            for name in sorted(live):
                live[name] += 1
                # half the fleet keeps its exact content (FLAG_SAME
                # path), the rest drifts
                seed = seeds[name] + (win if int(
                    rng.integers(0, 2)) else 0)
                deliver(name, live[name], seed)
            r1 = agg1.aggregate_once()
            r2 = agg2.aggregate_once()
            assert _results_bit_equal(r1, r2), f"window {win} diverged"
        assert agg2._stats["keyframe_requests_total"] >= 1
        agg1.shutdown()
        agg2.shutdown()


@pytest.mark.chaos
class TestDisplacedHerdKeyframeBurst:
    """ISSUE 14 chaos (make chaos): kill one of three ring replicas
    mid-steady-state with all-v2 delta-sending agents, then restart a
    surviving owner in place (fresh process: no base rows). The
    displaced herd replays, the fresh owner answers the next fresh
    deltas with a 409 needs-keyframe BURST (visible in the new
    counter), every agent resends full, and the fleet converges with
    ZERO windows lost."""

    def test_kill_rebalance_then_fresh_owner(self, tmp_path):
        from tests.test_ring_handoff import (
            drive_interval,
            kill_replica,
            make_agent as make_ring_agent,
            make_tier,
            names_owned_by,
            shutdown_tier,
        )

        servers, aggs, peers, ctxs = make_tier(3)
        dead = set()
        try:
            owned = names_owned_by(aggs[0]._ring, peers, per_peer=2)
            agents = [make_ring_agent(n, peers,
                                      tmp_path / f"sp-{n}")
                      for p in peers for n in owned[p]]
            try:
                ts = 100.0
                for _ in range(4):
                    drive_interval(agents, aggs, (0, 1, 2), ts)
                    ts += 5.0
                # steady state: the whole fleet is on the delta path
                assert all(a._stats["deltas_sent"] >= 2
                           for a in agents)
                assert all(a._stats["keyframes_sent"] == 1
                           for a in agents)

                # kill replica 0, rebalance the survivors
                kill_replica(servers, aggs, ctxs, 0)
                dead.add(0)
                survivors = [peers[1], peers[2]]
                for i in (1, 2):
                    aggs[i].apply_membership(survivors, 2)
                for _ in range(3):
                    drive_interval(agents, aggs, (1, 2), ts)
                    ts += 5.0

                # restart replica 1 in place: a FRESH owner — same
                # address, empty base-row store, trackers seeded only
                # by the agents' acked_through watermarks
                aggs[1].shutdown()
                aggs[1] = Aggregator(
                    servers[1], model_mode=None, node_bucket=8,
                    workload_bucket=16, peers=survivors,
                    self_peer=peers[1], ring_epoch=2)
                aggs[1].init()
                for _ in range(3):
                    drive_interval(agents, aggs, (1, 2), ts)
                    ts += 5.0

                # the keyframe-request burst fired on the fresh owner:
                # one 409 per delta-sending node it owns
                fresh_owned = [n for p in peers for n in owned[p]
                               if aggs[1]._ring.owner(n) == peers[1]]
                assert fresh_owned  # the ring gives it a share
                burst = aggs[1]._stats["keyframe_requests_total"]
                assert burst >= len(fresh_owned)
                assert sum(a._stats["keyframe_resends"]
                           for a in agents) >= len(fresh_owned)

                # ZERO windows lost across the kill AND the restart
                # (acked_through seeding + spool replay + dedup)
                lost = sum(aggs[i]._stats["windows_lost_total"]
                           for i in (1, 2))
                assert lost == 0
                # fully converged: every node current on its owner at
                # the final seq, every agent drained, breakers closed
                for p in peers:
                    for name in owned[p]:
                        owner_idx = peers.index(
                            aggs[1]._ring.owner(name))
                        stored = aggs[owner_idx]._reports[name]
                        assert stored.seq == 10
                        assert stored.wire_version == 2
                for agent in agents:
                    assert agent.backlog() == 0
                    assert agent._breaker_state == "closed"
            finally:
                for agent in agents:
                    agent.shutdown()
        finally:
            shutdown_tier(servers, aggs, ctxs, dead=tuple(dead))


class TestAgentWireV2:
    def _pair(self, server, **agent_kw):
        agg = make_agg(server)
        host, port = server.addresses[0]
        agent_kw.setdefault("jitter_seed", 0)
        agent = FleetAgent(FakeMeterMonitor(),
                           endpoint=f"http://{host}:{port}",
                           node_name="wv2-node", **agent_kw)
        agent.init()
        return agg, agent

    def test_delta_steady_state_and_keyframe_cadence(self, server):
        agg, agent = self._pair(server, keyframe_every=4)
        s = make_sample()
        for _ in range(6):
            agent._on_window(s)
            agent._drain(None)
        st = agent._stats
        assert st["sent_total"] == 6
        assert st["keyframes_sent"] == 2  # windows 1 and 5
        assert st["deltas_sent"] == 4
        stored = agg._reports["wv2-node"]
        assert stored.seq == 6 and stored.content_seq == 5
        agent.shutdown()

    def test_409_resends_keyframe_without_failure(self, server):
        agg, agent = self._pair(server)
        s = make_sample()
        for _ in range(2):
            agent._on_window(s)
            agent._drain(None)
        agg._base_rows.clear()  # fresh owner
        agent._on_window(s)
        agent._drain(None)
        st = agent._stats
        assert st["keyframe_resends"] == 1
        assert agg._stats["keyframe_requests_total"] == 1
        assert st["send_failures"] == 0
        assert agent._breaker_state == "closed"
        assert agg._reports["wv2-node"].seq == 3
        agent.shutdown()

    def test_wire_version_1_pins_legacy(self, server):
        agg, agent = self._pair(server, wire_version=1)
        agent._on_window(make_sample())
        agent._drain(None)
        assert agg._reports["wv2-node"].wire_version == 1
        assert agent._stats["keyframes_sent"] == 0
        agent.shutdown()

    def test_spool_records_are_keyframes(self, server, tmp_path):
        spool = Spool(str(tmp_path / "spool"))
        agg, agent = self._pair(server, spool=spool)
        s = make_sample()
        agent._on_window(s)
        rec = spool.peek()
        assert rec.payload[: len(WireLayoutV2.MAGIC)] \
            == WireLayoutV2.MAGIC
        assert not parse_header(rec.payload).is_delta
        agent._drain(None)
        assert spool.pending_records() == 0
        agent.shutdown()
