"""Elastic fleet membership (ISSUE 16): coordinator-lease succession
properties, runtime join/leave over the /v1/membership plane, the
equal-epoch split-brain detector, and the autoscale hysteresis policy.

The succession properties have ONE source of truth since ISSUE 17: the
kepmc lease model (`kepler_tpu/analysis/protocol`) drives the SAME
pure functions — `plan_succession`, `plan_membership_apply`,
`CoordinatorLease.adopt` — through EVERY interleaving of crash, leave,
false-suspect probing, duplicate/reordered delivery and restart at the
declared scopes, and the KTL130 invariants (no split-brain,
holder-in-peers, contiguous epochs, no await-wedge) are checked in
every reachable state. This suite asserts against that explored state
space; the hand-rolled 5-peer subset sweeps remain as concrete
regression anchors on the pure functions. The aggregator tier runs
five REAL aggregators wired through injected liveness/delivery seams
(no sockets), so the "exactly one survivor bumps the epoch" pin covers
the actual `_demote_mesh` → `apply_membership` → broadcast code path.
"""

from __future__ import annotations

import itertools
import json

import pytest

from kepler_tpu.fleet.aggregator import Aggregator
from kepler_tpu.fleet.membership import (
    AutoscaleDecision,
    AutoscalePolicy,
    AutoscaleSignals,
    CoordinatorLease,
    MembershipError,
    elect_successor,
    lease_id_of,
    plan_succession,
    sanitize_lease_id,
    validate_membership_payload,
)
from kepler_tpu.server.http import APIServer

PEERS5 = [f"10.0.0.{i}:28283" for i in range(1, 6)]


class FakeRequest:
    command = "POST"

    def __init__(self, body: bytes):
        self.body = body


# ---------------------------------------------------------------------------
# Succession properties
# ---------------------------------------------------------------------------


def every_subset(peers):
    for n in range(1, len(peers) + 1):
        yield from itertools.combinations(peers, n)


class TestSuccessionProperties:
    """Universal claims are model-checked (kepmc explores every
    interleaving, not a subset sweep); the 5-peer pins below anchor the
    pure functions against concrete inputs."""

    @staticmethod
    def _explored(spec_name):
        from kepler_tpu.analysis.protocol import (explore_case,
                                                  spec_by_name)

        spec = spec_by_name(spec_name)
        return spec, [(case, explore_case(spec, case).result)
                      for case in spec.cases]

    def test_succession_state_space_has_no_counterexamples(self):
        """The former exactly-one-leader / concurrent-deaths-converge /
        no-self-elect sweeps, generalized: over EVERY reachable
        interleaving of the lease model (crash, leave, delivery in any
        order and multiplicity, restart), the KTL130 invariant set
        holds. A regression in plan_succession or the lease adopt rules
        surfaces here as a minimal counterexample trace."""
        spec, runs = self._explored("lease.succession")
        assert {"no-split-brain", "holder-in-peers",
                "contiguous-epochs", "no-await-wedge"} \
            <= set(spec.invariants)
        for case, result in runs:
            assert result.ok, "\n\n".join(
                cex.format() for cex in result.counterexamples)
            # exhaustive exploration, not a smoke probe: the N=3 case
            # must visit thousands of states
            assert result.states >= 50, (case.name, result.states)

    def test_partitioned_probe_state_space_has_no_counterexamples(self):
        """False-suspect probing (a partitioned prober declares the
        live holder dead and mints a competing lease): transient dual
        holders are legal there, but the holder stays a member of its
        own peer set and epochs stay contiguous — the equal-epoch
        conflict rejection does the rest (pinned directly below)."""
        spec, runs = self._explored("lease.partitioned")
        for case, result in runs:
            assert result.ok, "\n\n".join(
                cex.format() for cex in result.counterexamples)
            assert result.states >= 1000, (case.name, result.states)

    def test_every_subset_elects_exactly_one_leader(self):
        """For EVERY non-empty subset of a 5-peer set, every survivor
        computes the same single issuer — the "exactly one writer"
        property succession rests on."""
        for subset in every_subset(PEERS5):
            # the holder is dead (not in the subset) unless the subset
            # is the full set; either way every survivor must agree
            for holder in PEERS5 + [""]:
                issuers = {plan_succession(holder, subset)
                           for _ in subset}
                assert len(issuers) == 1
                issuer = issuers.pop()
                assert issuer in subset
                if holder in subset:
                    assert issuer == holder  # incumbent retained
                else:
                    assert issuer == min(subset)  # lowest survivor

    def test_concurrent_deaths_converge(self):
        """Two hosts dying in the same window: every survivor probes
        the same survivor set and therefore computes the same issuer —
        no coordination round needed."""
        for dead in itertools.combinations(PEERS5, 2):
            survivors = [p for p in PEERS5 if p not in dead]
            holder = PEERS5[0]
            issuers = {plan_succession(holder, survivors)
                       for _ in survivors}
            assert len(issuers) == 1
            expected = holder if holder in survivors else min(survivors)
            assert issuers == {expected}

    def test_rejoining_peer_never_self_elects_over_live_lease(self):
        """The rejoiner sorts LOWEST, but the incumbent holder is
        alive: succession keeps the incumbent, and the lease's
        equal-epoch conflict check rejects the rejoiner claiming the
        same epoch for itself."""
        rejoiner = "10.0.0.0:28283"  # sorts before every PEERS5 entry
        holder = PEERS5[1]
        survivors = [rejoiner] + PEERS5
        assert plan_succession(holder, survivors) == holder
        lease = CoordinatorLease(holder, epoch=4)
        with pytest.raises(MembershipError) as err:
            lease.adopt(rejoiner, 4)
        assert err.value.reason == "equal_epoch_conflict"
        assert lease.holder == holder  # belief unchanged

    def test_empty_survivor_set_raises(self):
        with pytest.raises(MembershipError) as err:
            elect_successor([])
        assert err.value.reason == "no_survivors"

    def test_two_writers_same_epoch_cannot_both_win(self):
        """Even if a partitioned prober produced two issuers, the
        lease admits only ONE holder per epoch — the second adopt is a
        loud conflict, never a silent overwrite."""
        lease = CoordinatorLease(PEERS5[0], epoch=1)
        lease.adopt(PEERS5[1], 2)
        with pytest.raises(MembershipError) as err:
            lease.adopt(PEERS5[2], 2)
        assert err.value.reason == "equal_epoch_conflict"
        # the SAME holder re-asserting the epoch is an idempotent adopt
        lease.adopt(PEERS5[1], 2)
        assert lease.holder == PEERS5[1]


class TestLease:
    def test_monotonic_epoch(self):
        lease = CoordinatorLease(PEERS5[0], epoch=3)
        with pytest.raises(MembershipError) as err:
            lease.adopt(PEERS5[1], 2)
        assert err.value.reason == "stale_epoch"
        lease.adopt(PEERS5[1], 5)
        assert (lease.holder, lease.epoch) == (PEERS5[1], 5)
        assert lease.lease_id == f"5:{PEERS5[1]}"

    def test_issuer_for_uses_incumbent_rule(self):
        lease = CoordinatorLease(PEERS5[2], epoch=1)
        assert lease.issuer_for(PEERS5) == PEERS5[2]
        assert lease.issuer_for(PEERS5[3:]) == PEERS5[3]

    @pytest.mark.parametrize("bad", [
        None, 42, "", "no-separator", "x:holder", "-1:holder",
        "3:", "3:bad\nname", "3:" + "x" * 300, "2.5:holder",
    ])
    def test_sanitize_lease_id_rejects(self, bad):
        assert sanitize_lease_id(bad) is None

    def test_sanitize_lease_id_roundtrip(self):
        lid = lease_id_of(PEERS5[0], 7)
        assert sanitize_lease_id(lid) == lid
        # holder may itself contain colons (host:port)
        assert sanitize_lease_id("7:10.0.0.1:28283") == "7:10.0.0.1:28283"

    @pytest.mark.parametrize("holder,epoch", [
        ("bad\x01peer", 1), ("", 1), (PEERS5[0], 0), (PEERS5[0], True),
    ])
    def test_ctor_rejects_bad_inputs(self, holder, epoch):
        with pytest.raises(MembershipError):
            CoordinatorLease(holder, epoch=epoch)


class TestPayloadLaundering:
    """Equal/stale/hostile-field boundary tests for the wire payload
    chokepoint, `validate_membership_payload` (the `/v1/membership`
    analog of the ring-header coercion suite)."""

    @pytest.mark.parametrize("payload,reason", [
        (None, "bad_payload"),
        ([], "bad_payload"),
        ("{}", "bad_payload"),
        ({"op": "takeover"}, "bad_op"),
        ({"op": 42}, "bad_op"),
        ({"peers": "not-a-list"}, "bad_peer"),
        ({"peers": [42]}, "bad_peer"),
        ({"peers": ["ok:1", "evil\nname"]}, "bad_peer"),
        ({"peers": ["x" * 300]}, "bad_peer"),
        ({"peer": 42}, "bad_peer"),
        ({"issuer": "bad\x7fissuer"}, "bad_peer"),
        ({"holder": ["a"]}, "bad_peer"),
        ({"epoch": "abc"}, "bad_epoch"),
        ({"epoch": -1}, "bad_epoch"),
        ({"epoch": True}, "bad_epoch"),
        ({"epoch": 2.5}, "bad_epoch"),
        ({"lease": "no-separator"}, "bad_lease"),
        ({"lease": 42}, "bad_lease"),
    ])
    def test_hostile_fields_rejected(self, payload, reason):
        with pytest.raises(MembershipError) as err:
            validate_membership_payload(payload)
        assert err.value.reason == reason

    def test_good_payload_normalized(self):
        out = validate_membership_payload({
            "op": "apply", "peers": list(PEERS5), "epoch": 3,
            "issuer": PEERS5[0], "lease": f"3:{PEERS5[0]}",
            "mesh": True})
        assert out["op"] == "apply"
        assert out["peers"] == list(PEERS5)
        assert out["epoch"] == 3
        assert out["issuer"] == PEERS5[0]
        assert out["mesh"] is True

    @pytest.mark.parametrize("mesh", ["yes", 1, [True], None])
    def test_mesh_flag_clamped_to_bool(self, mesh):
        assert validate_membership_payload({"mesh": mesh})["mesh"] is False


# ---------------------------------------------------------------------------
# Autoscale policy
# ---------------------------------------------------------------------------


def sig(load=0.0, shed=0, replicas=2, flagged=0):
    return AutoscaleSignals(load=load, shed_delta=shed,
                            replicas=replicas, flagged_nodes=flagged)


class TestAutoscalePolicy:
    def test_scale_up_after_consecutive_overload(self):
        policy = AutoscalePolicy(up_windows=3)
        assert policy.observe(sig(load=1.5)).direction == "hold"
        assert policy.observe(sig(load=1.2)).direction == "hold"
        dec = policy.observe(sig(load=1.1))
        assert (dec.direction, dec.replicas) == ("up", 3)
        # the streak reset: the next step needs fresh evidence
        assert policy.observe(sig(load=1.5)).direction == "hold"

    def test_shedding_counts_as_overload(self):
        policy = AutoscalePolicy(up_windows=2)
        policy.observe(sig(load=0.1, shed=5))
        dec = policy.observe(sig(load=0.1, shed=1))
        assert dec.direction == "up"

    def test_scale_down_after_consecutive_idle(self):
        policy = AutoscalePolicy(down_windows=3)
        for _ in range(2):
            assert policy.observe(sig(load=0.1)).direction == "hold"
        dec = policy.observe(sig(load=0.1))
        assert (dec.direction, dec.replicas) == ("down", 1)

    def test_dead_band_preserves_streaks(self):
        """A mid-band window neither advances nor erases evidence."""
        policy = AutoscalePolicy(up_windows=2)
        policy.observe(sig(load=1.5))
        policy.observe(sig(load=0.5))  # dead band: streak survives
        dec = policy.observe(sig(load=1.5))
        assert dec.direction == "up"

    def test_overload_erases_down_streak_and_vice_versa(self):
        policy = AutoscalePolicy(up_windows=2, down_windows=2)
        policy.observe(sig(load=0.1))
        policy.observe(sig(load=1.5))  # resets down streak
        dec = policy.observe(sig(load=0.1))
        assert dec.direction == "hold"

    def test_flagged_nodes_block_scale_down(self):
        """An unhealthy scoreboard is evidence AGAINST shrinking even
        at idle load."""
        policy = AutoscalePolicy(down_windows=2)
        policy.observe(sig(load=0.1, flagged=1))
        policy.observe(sig(load=0.1, flagged=1))
        assert policy.observe(sig(load=0.1, flagged=1)).direction == "hold"

    def test_min_and_max_bounds(self):
        policy = AutoscalePolicy(up_windows=1, down_windows=1,
                                 min_replicas=2, max_replicas=3)
        assert policy.observe(sig(load=1.5, replicas=3)).direction == "hold"
        assert policy.observe(sig(load=0.1, replicas=2)).direction == "hold"
        assert policy.observe(sig(load=1.5, replicas=2)).direction == "up"

    def test_default_cap_is_one_step_up(self):
        policy = AutoscalePolicy(up_windows=1, max_replicas=0)
        dec = policy.observe(sig(load=1.5, replicas=4))
        assert (dec.direction, dec.replicas) == ("up", 5)

    def test_replay_determinism(self):
        """A pure function of the observation sequence: feeding the
        same recorded trace to a fresh policy reproduces the same
        decisions — autoscale is auditable from metrics alone."""
        trace = ([sig(load=1.5)] * 4 + [sig(load=0.5)] * 3
                 + [sig(load=0.1)] * 15 + [sig(load=1.2, shed=2)] * 3)
        runs = []
        for _ in range(2):
            policy = AutoscalePolicy(up_windows=3, down_windows=12)
            runs.append([policy.observe(s) for s in trace])
        assert runs[0] == runs[1]
        assert any(d.direction != "hold" for d in runs[0])

    def test_ctor_validation(self):
        with pytest.raises(ValueError):
            AutoscalePolicy(scale_up_load=0)
        with pytest.raises(ValueError):
            AutoscalePolicy(scale_down_load=1.5, scale_up_load=1.0)
        with pytest.raises(ValueError):
            AutoscalePolicy(up_windows=0)
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=0)


# ---------------------------------------------------------------------------
# Five-host aggregator tier (injected seams, real code path)
# ---------------------------------------------------------------------------


class FiveHostFleet:
    """Five real aggregators sharing one ring, wired through in-process
    liveness and delivery seams: `deliver` routes membership POSTs to
    the target aggregator's actual `/v1/membership` handler."""

    def __init__(self, **agg_kw):
        self.alive = set(PEERS5)
        self.deliveries: list[tuple[str, str, dict]] = []
        self.aggs: dict[str, Aggregator] = {}
        for i, peer in enumerate(PEERS5):
            self.aggs[peer] = self._make(i, peer, **agg_kw)

    def _make(self, i, peer, **agg_kw):
        def deliver(target, payload, _self=peer):
            self.deliveries.append((_self, target, dict(payload)))
            if target not in self.alive:
                raise OSError("connection refused")
            status, _, body = self.aggs[target]._handle_membership(
                FakeRequest(json.dumps(payload).encode()))
            return json.loads(body)

        kw = dict(model_mode=None, node_bucket=8, workload_bucket=8,
                  stale_after=1e9)
        kw.update(agg_kw)
        agg = Aggregator(
            APIServer(), peers=list(PEERS5), self_peer=peer,
            membership_topology={
                "peer_alive": lambda p: p in self.alive,
                "deliver": deliver,
            }, **kw)
        agg.init()
        return agg

    def kill(self, peer):
        self.alive.discard(peer)

    def survivors(self):
        return [self.aggs[p] for p in PEERS5 if p in self.alive]

    def shutdown(self):
        for agg in self.aggs.values():
            agg.shutdown()


@pytest.fixture()
def fleet():
    f = FiveHostFleet()
    yield f
    f.shutdown()


class TestFiveHostSuccession:
    def test_exactly_one_survivor_bumps_epoch_on_single_death(self, fleet):
        """The acceptance pin: a single host death on a 5-peer ring —
        every survivor runs the demotion path, EXACTLY ONE issues the
        membership; the broadcast converges the rest."""
        dead = PEERS5[2]
        fleet.kill(dead)
        for agg in fleet.survivors():
            agg._on_mesh_lost("host_dead")
        issuers = [p for p in PEERS5 if p in fleet.alive
                   and fleet.aggs[p]._membership_applied.get("succession")]
        assert issuers == [PEERS5[0]]  # the incumbent holder, alive
        # every survivor converged on the same membership + lease
        for agg in fleet.survivors():
            assert agg._ring.epoch == 2
            assert set(agg._ring.peers) == fleet.alive
            assert agg._lease.holder == PEERS5[0]
            assert agg._awaiting_membership is False

    def test_holder_death_elects_lowest_survivor(self, fleet):
        fleet.kill(PEERS5[0])
        for agg in fleet.survivors():
            agg._on_mesh_lost("host_dead")
        issuers = [p for p in PEERS5 if p in fleet.alive
                   and fleet.aggs[p]._membership_applied.get("succession")]
        assert issuers == [PEERS5[1]]  # lowest surviving peer
        for agg in fleet.survivors():
            assert agg._ring.epoch == 2
            assert agg._lease.holder == PEERS5[1]

    def test_concurrent_two_host_death_converges(self, fleet):
        fleet.kill(PEERS5[0])
        fleet.kill(PEERS5[3])
        for agg in fleet.survivors():
            agg._on_mesh_lost("host_dead")
        epochs = {a._ring.epoch for a in fleet.survivors()}
        assert epochs == {2}
        for agg in fleet.survivors():
            assert set(agg._ring.peers) == fleet.alive
            assert agg._lease.holder == PEERS5[1]

    def test_takeover_disabled_awaits_operator(self):
        fleet = FiveHostFleet(multihost_takeover=False)
        try:
            fleet.kill(PEERS5[4])
            for agg in fleet.survivors():
                agg._on_mesh_lost("host_dead")
            for agg in fleet.survivors():
                assert agg._ring.epoch == 1  # untouched
                assert agg._awaiting_membership is True
                assert agg.ring_health()["ok"] is False
        finally:
            fleet.shutdown()

    def test_equal_epoch_conflict_rejected_loudly(self, fleet):
        agg = fleet.aggs[PEERS5[0]]
        agg.apply_membership(PEERS5[:4], 2)
        with pytest.raises(MembershipError) as err:
            agg.apply_membership(PEERS5[:3], 2)
        assert err.value.reason == "equal_epoch_conflict"
        assert agg._membership_rejected["equal_epoch_conflict"] == 1
        # idempotent replay of the SAME set is NOT a conflict
        assert agg.apply_membership(PEERS5[:4], 2) == 0

    def test_operator_cannot_exclude_self(self, fleet):
        agg = fleet.aggs[PEERS5[0]]
        with pytest.raises(MembershipError) as err:
            agg.apply_membership(PEERS5[1:], 2)
        assert err.value.reason == "self_excluded"

    def test_wire_membership_excluding_self_retires(self, fleet):
        """A broadcast that excludes this replica is the scale-down
        path: adopt the ring anyway, own nothing, redirect everything."""
        agg = fleet.aggs[PEERS5[4]]
        agg.apply_membership(PEERS5[:4], 2, source="wire",
                             issuer=PEERS5[0])
        assert agg._ring.epoch == 2
        assert PEERS5[4] not in agg._ring.peers
        assert agg._ring.owner("any-node") != PEERS5[4]


class TestJoinLeave:
    def test_rejoin_takes_shards_back_without_reelection(self, fleet):
        """The rejoin story: host dies, succession heals the ring,
        the host comes back and registers with the lease holder — it
        adopts the INCUMBENT lease (never self-elects) and owns keys
        again."""
        dead = PEERS5[1]
        fleet.kill(dead)
        for agg in fleet.survivors():
            agg._on_mesh_lost("host_dead")
        holder_before = fleet.aggs[PEERS5[0]]._lease.holder
        # the host returns: fresh process, stale ring at epoch 1
        fleet.alive.add(dead)
        rejoiner = fleet.aggs[dead]
        reply = rejoiner.request_join()
        assert reply["ok"] is True
        for peer in fleet.alive:
            agg = fleet.aggs[peer]
            assert set(agg._ring.peers) == set(PEERS5)
            assert agg._ring.epoch == 3  # death bump + join bump
            assert agg._lease.holder == holder_before  # no re-election
        # the rejoiner owns keys again
        owned = [n for n in ("n1", "n2", "n3", "n4", "n5", "n6", "n7",
                             "n8", "n9", "n10", "n11", "n12")
                 if rejoiner._ring.owner(n) == dead]
        assert owned  # vnode ring: 1/5 of a 12-key sample is ~2+ keys

    def test_join_registration_is_idempotent(self, fleet):
        agg = fleet.aggs[PEERS5[1]]
        reply = agg.request_join()
        assert reply["ok"] is True
        assert reply.get("already_member") is True
        assert agg._ring.epoch == 1  # nothing changed

    def test_join_redirected_from_non_holder(self, fleet):
        """A joiner that asks the WRONG replica gets the membership
        plane's 421 — a structured not_leader naming the holder — and
        follows it."""
        dead = PEERS5[3]
        fleet.kill(dead)
        for agg in fleet.survivors():
            agg._on_mesh_lost("host_dead")
        fleet.alive.add(dead)
        rejoiner = fleet.aggs[dead]
        reply = rejoiner.request_join(via=PEERS5[4])  # not the holder
        assert reply["ok"] is True
        assert set(rejoiner._ring.peers) == set(PEERS5)
        # the first delivery went to the wrong replica and bounced
        bounced = [(f, t) for f, t, p in fleet.deliveries
                   if f == dead and t == PEERS5[4]
                   and p.get("op") == "join"]
        assert bounced

    def test_graceful_leave_retires_the_leaver(self, fleet):
        holder = fleet.aggs[PEERS5[0]]
        status, _, body = holder._handle_membership(FakeRequest(
            json.dumps({"op": "leave", "peer": PEERS5[4]}).encode()))
        assert status == 200
        reply = json.loads(body)
        assert PEERS5[4] not in reply["peers"]
        for peer in PEERS5:
            agg = fleet.aggs[peer]
            assert agg._ring.epoch == 2
            assert set(agg._ring.peers) == set(PEERS5[:4])
        # the leaver itself was told (extra broadcast) and retired
        leaver = fleet.aggs[PEERS5[4]]
        assert leaver._ring.owner("anything") != PEERS5[4]

    def test_holder_leaving_hands_over_the_lease(self, fleet):
        holder = fleet.aggs[PEERS5[0]]
        status, _, body = holder._handle_membership(FakeRequest(
            json.dumps({"op": "leave", "peer": PEERS5[0]}).encode()))
        assert status == 200
        assert json.loads(body)["holder"] == PEERS5[1]
        for peer in PEERS5[1:]:
            assert fleet.aggs[peer]._lease.holder == PEERS5[1]

    def test_join_leave_on_non_holder_answers_not_leader(self, fleet):
        agg = fleet.aggs[PEERS5[2]]
        status, _, body = agg._handle_membership(FakeRequest(
            json.dumps({"op": "join", "peer": "10.9.9.9:1"}).encode()))
        assert status == 421
        reply = json.loads(body)
        assert reply["reason"] == "not_leader"
        assert reply["holder"] == PEERS5[0]

    def test_join_with_no_reachable_holder_fails_structured(self, fleet):
        # the whole fleet is down: every candidate is a transport
        # error, so the join fails with a STRUCTURED reason (and the
        # counter), never a hang or a self-election
        for peer in PEERS5:
            fleet.kill(peer)
        joiner = fleet.aggs[PEERS5[0]]
        with pytest.raises(MembershipError) as err:
            joiner.request_join()
        assert err.value.reason == "join_failed"
        assert joiner._membership_rejected["join_failed"] == 1
        assert joiner._ring.epoch == 1  # nothing adopted
        assert joiner._lease.holder == PEERS5[0]  # no self-election


class TestAutoscaleIntegration:
    class StubAdmission:
        def __init__(self, load=0.0, shed=0, latency=0.0):
            self._load, self._shed, self._lat = load, shed, latency

        def load(self):
            return self._load

        def shed_by_reason(self):
            return {"overload": self._shed}

        def latency_ewma(self):
            return self._lat

    def make_fleet(self, **kw):
        kw.setdefault("membership_autoscale", True)
        kw.setdefault("membership_up_windows", 2)
        kw.setdefault("membership_down_windows", 2)
        return FiveHostFleet(**kw)

    def test_recommendation_surfaced_without_auto_apply(self):
        """autoApply=false: decisions are recorded and surfaced, the
        ring is NEVER touched — operator behavior byte-for-byte."""
        fleet = self.make_fleet()
        try:
            agg = fleet.aggs[PEERS5[0]]
            agg._admission = self.StubAdmission(load=2.0)
            agg._autoscale_tick()
            agg._autoscale_tick()  # up_windows=2: this one fires
            assert agg._autoscale_last.direction == "up"
            assert agg._autoscale_decisions["up"] == 1
            assert agg._ring.epoch == 1  # untouched
            assert set(agg._ring.peers) == set(PEERS5)
            assert "autoscale" not in agg._membership_applied
        finally:
            fleet.shutdown()

    def test_auto_apply_scale_up_promotes_standby(self):
        standby = "10.0.1.1:28283"
        fleet = self.make_fleet(membership_auto_apply=True,
                                membership_standby_peers=[standby])
        try:
            agg = fleet.aggs[PEERS5[0]]  # the lease holder
            agg._admission = self.StubAdmission(load=2.0)
            agg._autoscale_tick()
            agg._autoscale_tick()
            assert agg._ring.epoch == 2
            assert standby in agg._ring.peers
            assert agg._membership_applied["autoscale"] == 1
            # the change was broadcast to every original member
            for peer in PEERS5[1:]:
                assert standby in fleet.aggs[peer]._ring.peers
        finally:
            fleet.shutdown()

    def test_auto_apply_scale_down_retires_highest_non_holder(self):
        fleet = self.make_fleet(membership_auto_apply=True)
        try:
            agg = fleet.aggs[PEERS5[0]]
            agg._admission = self.StubAdmission(load=0.0)
            agg._autoscale_tick()
            agg._autoscale_tick()
            assert agg._ring.epoch == 2
            assert PEERS5[4] not in agg._ring.peers  # highest-sorted
            assert PEERS5[0] in agg._ring.peers  # never the holder
            # the victim was told and retired
            assert PEERS5[4] not in fleet.aggs[PEERS5[4]]._ring.peers
        finally:
            fleet.shutdown()

    def test_non_holder_never_enacts(self):
        fleet = self.make_fleet(membership_auto_apply=True)
        try:
            agg = fleet.aggs[PEERS5[2]]  # not the holder
            agg._admission = self.StubAdmission(load=0.0)
            for _ in range(4):
                agg._autoscale_tick()
            assert agg._autoscale_last.direction in ("down", "hold")
            assert agg._ring.epoch == 1
        finally:
            fleet.shutdown()

    def test_scale_up_without_standby_stands_pat(self):
        fleet = self.make_fleet(membership_auto_apply=True)
        try:
            agg = fleet.aggs[PEERS5[0]]
            agg._admission = self.StubAdmission(load=2.0)
            agg._autoscale_tick()
            agg._autoscale_tick()
            assert agg._autoscale_last.direction == "up"
            assert agg._ring.epoch == 1  # nothing to promote
        finally:
            fleet.shutdown()

    def test_autoscale_off_is_inert(self, fleet):
        agg = fleet.aggs[PEERS5[0]]
        assert agg._autoscale is None
        agg._autoscale_tick()  # no-op, no error
        assert agg._autoscale_last is None
