"""Cluster aggregator: ingest node reports, attribute the whole fleet on TPU.

The aggregator half of the DCN plane (BASELINE.json north star, SURVEY §7
step 9): node agents POST per-window feature rows; every ``interval`` the
aggregator runs one fleet window over the latest report from each node
and publishes:

- ``GET /v1/results[?node=…]`` — attributed watts scattered back per node
  (JSON), the pull leg for non-RAPL nodes that want their estimates;
- ``GET /metrics`` — cluster-level Prometheus families
  (``kepler_fleet_…``), the same scrape plane the reference leans on.

``Aggregator`` is ingest, the report store with its per-node history,
membership and the HTTP surfaces. The window itself — ladder, engines,
windows in flight, publication, results — is the ``WindowScheduler`` it
owns as ``self.windows`` (``fleet/scheduler.py``), which it only calls
down into: ``run``/``aggregate_once`` snapshot the store and hand the live
reports to ``windows.step``.

Late/missing nodes: a node whose latest report is older than
``stale_after`` falls out of the batch (its row just isn't assembled) —
the batched analog of the reference's per-zone skip-on-error.
"""

from __future__ import annotations

import hashlib
import json
import logging
import threading
import time as _time
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable, Iterator, Mapping, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from kepler_tpu import fault, telemetry
from kepler_tpu.fleet.admission import (
    PRIORITY_FRESH_GROUND,
    PRIORITY_FRESH_MODEL,
    PRIORITY_REPLAY_GROUND,
    AdmissionController,
)
from kepler_tpu.fleet.delivery import (
    SeqTracker,
    delta_base_matches,
    reseed_on_ownership_return,
    seed_fresh_tracker,
)
from kepler_tpu.fleet.journal import (
    EventJournal,
    canonical_json,
    make_journal_handler,
)
from kepler_tpu.fleet.membership import (
    AutoscaleDecision,
    AutoscalePolicy,
    AutoscaleSignals,
    CoordinatorLease,
    MembershipError,
    elect_successor,
    plan_membership_apply,
    plan_succession,
    validate_membership_payload,
)
from kepler_tpu.fleet.ring import (HashRing, RingError, coerce_epoch,
                                   ring_from_mesh, sanitize_peer)
from kepler_tpu.fleet.wire import (
    ParsedHeader,
    WireError,
    decode_delta,
    decode_report,
    decode_report_batch,
    peek_node_name,
    peek_routing,
    sanitize_node_name,
    try_parse_header,
)
from kepler_tpu.fleet.scheduler import WindowScheduler
from kepler_tpu.fleet.scoreboard import STATE_NAMES, FleetScoreboard
from kepler_tpu.fleet.window_record import records_json
from kepler_tpu.monitor.history import HistoryBuffer
from kepler_tpu.telemetry import DEFAULT_DELIVERY_BUCKETS, Histogram
from kepler_tpu.parallel.fleet import MODE_MODEL, NodeReport
from kepler_tpu.parallel.mesh import submesh_for_processes
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext

if TYPE_CHECKING:
    from kepler_tpu.fleet.scheduler import FleetResults

log = logging.getLogger("kepler.fleet.aggregator")

# upper bound for one report POST (64 MiB ≫ any real fleet window: 10k
# workloads ≈ 50 KiB of arrays + ids) — enforced by the server before the
# body is buffered
MAX_REPORT_BYTES = 64 << 20

# TEST-ONLY chaos regression seed: when flipped (monkeypatched by the
# kepchaos shrinking-proof test, never set in production code), the
# membership fan-out stamps this replica as the issuer instead of the
# current lease holder — the historical holder-self-leave bug, where
# receivers adopt the DEPARTED peer as lease holder. kepchaos must
# catch this from a randomized schedule and shrink it to the minimal
# repro; see tests/test_chaos_conductor.py.
_BUG_BROADCAST_SELF_ISSUER = False


@dataclass
class _Stored:
    report: NodeReport
    zone_names: tuple[str, ...]
    received: float
    seq: int
    run: str = ""  # agent-run nonce (empty for pre-nonce agents)
    # seq at which the report CONTENT last changed (wire v2 FLAG_SAME
    # deltas bump seq but keep this, so the window engine's per-row
    # identity short-circuits to zero staged bytes for unchanged nodes);
    # 0 = unknown → fall back to seq (v1 agents restage every window)
    content_seq: int = 0
    wire_version: int = 1


@dataclass
class _BaseRow:
    """One node's resident delta base: the last v2 keyframe accepted
    from it (count-capped LRU beside the seq trackers). Immutable once
    stored — replaced wholesale by the next keyframe, so delta merges
    read it without the store lock."""

    run: str
    seq: int
    report: NodeReport
    zone_names: tuple[str, ...]


def _no_clock() -> float:
    """In the place of ``time.monotonic`` where telemetry is off: the
    ingest sums read no clock then."""
    return 0.0


def _report_power_w(report: NodeReport) -> float:
    """The node's self-reported power this window (valid zone energy
    over the window interval), the scoreboard's anomaly signal. Returns
    NaN when the report carries no usable window (the scoreboard skips
    non-finite magnitudes)."""
    dt = float(report.dt_s)
    if dt <= 0.0:
        return float("nan")
    valid = np.asarray(report.zone_valid, bool)
    deltas = np.asarray(report.zone_deltas_uj, np.float64)
    if valid.shape != deltas.shape or not valid.any():
        return float("nan")
    return float(deltas[valid].sum()) / dt / 1e6


# the dedup/gap tracker moved to the PURE decision layer
# (fleet/delivery.py) so the kepmc protocol checker drives the exact
# observe/seed transitions this ingest path runs; the old private name
# stays as the module-local spelling
_SeqTracker = SeqTracker


class Aggregator:
    """Service: report store + periodic sharded attribution."""

    # keplint: protocol-transition — ingest-state birth
    def __init__(
        self,
        server: APIServer,
        interval: float = 5.0,
        stale_after: float = 15.0,
        model_mode: str | None = "mlp",
        model_params: Mapping[str, np.ndarray] | None = None,
        node_bucket: int = 8,
        workload_bucket: int = 256,
        backend: str = "einsum",
        accuracy_mode: bool = False,
        history_window: int = 16,
        training_dump_dir: str = "",
        training_dump_max_files: int = 1000,
        skew_tolerance: float = 120.0,
        degraded_ttl: float = 60.0,
        dedup_window: int = 1024,
        delivery_buckets: Sequence[float] | None = None,
        pipeline_depth: int = 1,
        fused_window_k: int = 1,
        bucket_shrink_after: int = 16,
        fallback_enabled: bool = True,
        repromote_after: int = 8,
        dispatch_timeout: float = 30.0,
        mesh_shape: Sequence[int] | None = None,
        mesh_axes: Sequence[str] | None = None,
        multihost_enabled: bool = False,
        multihost_takeover: bool = True,
        multihost_topology: Mapping[str, Any] | None = None,
        membership_auto_apply: bool = False,
        membership_autoscale: bool = False,
        membership_scale_up_load: float = 1.0,
        membership_scale_down_load: float = 0.25,
        membership_up_windows: int = 3,
        membership_down_windows: int = 12,
        membership_min_replicas: int = 1,
        membership_max_replicas: int = 0,
        membership_standby_peers: Sequence[str] | None = None,
        membership_probe_timeout: float = 2.0,
        membership_topology: Mapping[str, Any] | None = None,
        scoreboard_cap: int = 1024,
        anomaly_z: float = 4.0,
        peers: Sequence[str] | None = None,
        self_peer: str = "",
        ring_epoch: int = 1,
        ring_vnodes: int = 64,
        admission_enabled: bool = False,
        admission_max_inflight: int = 64,
        admission_latency_budget: float = 0.25,
        admission_retry_after: float = 1.0,
        admission_retry_after_max: float = 30.0,
        admission_jitter_seed: int | None = None,
        base_row_cache: int = 1024,
        clock: Callable[[], float] | None = None,
        mesh: Any = None,
        journal: EventJournal | None = None,
        hlc_max_drift: float = 60.0,
    ) -> None:
        self._server = server
        self._interval = interval
        self._stale_after = stale_after
        self._model_mode = model_mode
        self._clock = clock or _time.time
        # fleet black box: every state transition below goes through the
        # journal chokepoint; the default is a disabled per-instance
        # journal (one attribute check per emission) on this replica's
        # clock seam, so library/test construction costs nothing and
        # chaos replicas never share clocks
        self._journal = journal if journal is not None else EventJournal(
            enabled=False, node=str(self_peer or ""), clock=self._clock,
            max_drift_s=hlc_max_drift)
        # admission-shed ONSET edge (False→True) is a journal event; the
        # return to admitting resets the edge detector — steady-state
        # shedding emits nothing (the journal records transitions, rates
        # live in the admission controller's own counters)
        self._shedding = False  # keplint: guarded-by=_lock
        # /debug/bundle stamps a config fingerprint so two bundles from
        # "the same fleet" are checkably from the same rollout
        self._config_fingerprint = hashlib.sha256(canonical_json({
            "self_peer": str(self_peer or ""),
            "interval": float(interval),
            "stale_after": float(stale_after),
            "model_mode": str(model_mode or ""),
            "multihost": bool(multihost_enabled),
            "hlc_max_drift": float(hlc_max_drift),
        })).hexdigest()[:16]
        # multi-host SPMD tier: on a mesh spanning > 1 process the
        # scheduler's rung 0 runs the multi-host engine, and ingest
        # ownership derives from the mesh shard map (ring_from_mesh). When
        # a cross-host failure costs the scheduler that mesh, the epoch is
        # bumped here so displaced agents follow 421s to the new owner
        # (_on_mesh_lost).
        self._multihost_enabled = bool(multihost_enabled)
        self._multihost_takeover = bool(multihost_takeover)
        # temporal mode: per-node feature-history ring buffers, fed on
        # report receipt so the window advances at each node's own cadence.
        # Each node's buffer carries its OWN lock: ingest for node A never
        # stalls on the [N, W, T, F] assembly reading node B, and the
        # assembly never holds the report-store lock at all (VERDICT r3
        # weak #4: history assembly used to stall every /v1/report POST).
        self._history_window = history_window
        self._history: dict[str, tuple[threading.Lock, "HistoryBuffer"]] = {}
        # training-data capture (the scheduler writes the files): ratio
        # nodes' history windows are then pushed too, as training data
        self._dump_dir = training_dump_dir

        # report quarantine: a malformed or clock-skewed report is rejected
        # BEFORE it can poison the batch, and the offense is charged to the
        # sending node so operators see WHICH node degrades (the reference
        # only ages bad nodes out silently). Entries decay after
        # ``degraded_ttl`` of good behavior.
        self._skew_tolerance = skew_tolerance
        self._degraded_ttl = degraded_ttl
        self._degraded: dict[str, dict] = {}
        # names come from (possibly hostile) malformed payloads: bound the
        # table (oldest offender evicted) and the per-name length so a
        # garbage flood can't grow memory or log volume without limit
        self._degraded_cap = 64
        self._degraded_name_cap = 128

        self._lock = threading.Lock()
        self._reports: dict[str, _Stored] = {}  # keplint: guarded-by=_lock
        # per-node run nonces superseded by restarts: a network-delayed
        # straggler from ANY previous agent run must not be re-classified
        # as yet another restart (that would overwrite the fresher run's
        # report, push a spurious temporal history window, and mark the
        # LIVE run as superseded — going dark until the next restart).
        # A bounded per-node list (oldest dropped) keeps memory O(nodes).
        self._superseded_runs: dict[str, list[str]] = {}
        self._superseded_cap = 16
        # idempotent ingest + loss accounting: per-node seq trackers for
        # the CURRENT run (spool replays dedupe; seq jumps become
        # kepler_fleet_windows_lost_total). Trackers deliberately OUTLIVE
        # batch staleness: a partition longer than stale_after followed
        # by a spool replay must resume from max_seen, not fabricate a
        # loss spike and re-ingest delivered windows. Bounded by count
        # instead (least-recently-observed evicted at the cap), like the
        # cumulative loss table.
        self._dedup_window = max(1, dedup_window)
        # end-to-end delivery latency: the agent stamps a trace id +
        # emitted_at at window emit; the accepted (non-duplicate) ingest
        # closes the trace here. Replays measure from the spool's
        # original appended_at under their own label so outage backlogs
        # never pollute the fresh-delivery signal.
        self._delivery_hist: dict[str, Histogram] = {  # keplint: guarded-by=_lock
            path: Histogram(delivery_buckets or DEFAULT_DELIVERY_BUCKETS)
            for path in ("fresh", "replay")}
        self._seq_trackers: dict[str, _SeqTracker] = {}  # keplint: guarded-by=_lock
        self._tracker_cap = 512
        # wire v2 delta bases: per-node last accepted keyframe, the
        # state a delta frame merges against. Count-capped LRU (dict
        # order = recency; oldest evicted) beside the seq trackers — a
        # delta whose base was evicted is answered with a structured
        # 409 needs-keyframe and the agent resends full, so eviction is
        # a round-trip, never corruption or loss.
        self._base_rows: dict[str, _BaseRow] = {}  # keplint: guarded-by=_lock
        self._base_row_cache = max(1, int(base_row_cache))
        self._lost_by_node: dict[str, int] = {}  # keplint: guarded-by=_lock
        self._lost_node_cap = 256
        # fleet scoreboard: one synthesized health row per node (state
        # machine + rolling power z-score), LRU-capped, updated at ingest
        # and served via /debug/fleet + kepler_fleet_node_state
        self._scoreboard = FleetScoreboard(  # keplint: guarded-by=_lock
            cap=scoreboard_cap, anomaly_z=anomaly_z,
            flag_ttl=degraded_ttl)
        # HA ingest ring (ISSUE 11): with peers configured, this replica
        # accepts only the nodes the consistent-hash ring assigns it and
        # answers everyone else with a structured 421 owner redirect.
        # The ring object is IMMUTABLE — a membership change swaps in a
        # new one wholesale (apply_membership), so the ingest hot path
        # reads it without the store lock.
        self._ring: HashRing | None = None
        self._self_peer = str(self_peer or "")
        self._ring_vnodes = max(1, int(ring_vnodes))
        # config-ORDER peer list (HashRing sorts; the mesh ring needs
        # process-index order: peers[p] = process p's endpoint)
        self._config_peers = list(peers or [])
        self._ring_epoch_cfg = max(1, int(ring_epoch))
        if peers:
            if not self._self_peer:
                raise ValueError(
                    "aggregator.selfPeer must name this replica when "
                    "aggregator.peers is set")
            self._ring = HashRing(peers, epoch=max(1, int(ring_epoch)),
                                  vnodes=self._ring_vnodes)
            if self._self_peer not in self._ring:
                raise ValueError(
                    f"aggregator.selfPeer {self_peer!r} is not in "
                    f"aggregator.peers {list(self._ring.peers)!r}")
        self._last_redirect_at: float | None = None  # keplint: guarded-by=_lock
        self._last_membership_at: float | None = None  # keplint: guarded-by=_lock
        # -- elastic membership (ISSUE 16): coordinator lease +
        # deterministic succession + runtime join/leave + autoscale.
        # The lease is DERIVED state, advanced in lock-step with the
        # ring epoch by apply_membership; its initial holder is the
        # lowest configured peer, so every replica starts agreeing.
        # Succession (plan_succession) replaces the old 2-host-only
        # takeover gate: on a host death at ANY mesh size exactly one
        # survivor — the incumbent holder while it lives, else the
        # lowest surviving peer — issues the survivor membership.
        self._lease: CoordinatorLease | None = None
        if self._ring is not None:
            self._lease = CoordinatorLease(
                elect_successor(self._config_peers),
                epoch=self._ring.epoch)
        mtopo = dict(membership_topology or {})
        # test seams for the liveness probe and the membership POST
        # (defaults: HTTP /healthz GET and /v1/membership POST)
        self._peer_alive_fn = mtopo.get("peer_alive")
        self._deliver_fn = mtopo.get("deliver")
        self._membership_probe_timeout = max(
            0.1, float(membership_probe_timeout))
        self._membership_auto_apply = bool(membership_auto_apply)
        self._standby_peers = list(membership_standby_peers or [])
        # "degraded, awaiting membership": a survivor that is NOT the
        # succession issuer (or has succession disabled) holds position
        # until the issuer's membership broadcast arrives — surfaced by
        # the fleet-window probe and the awaiting gauge
        self._awaiting_membership = False  # keplint: guarded-by=_lock
        # armed fabric incarnation for the next mesh-path membership (a
        # rejoin's fresh HostLocalFabric; production analog: restart the
        # jax.distributed job before re-applying the full set)
        self._mesh_arm: Any = None
        self._membership_rejected: dict[str, int] = {}  # keplint: guarded-by=_lock
        self._membership_applied: dict[str, int] = {}  # keplint: guarded-by=_lock
        self._autoscale: AutoscalePolicy | None = None
        if membership_autoscale:
            self._autoscale = AutoscalePolicy(
                scale_up_load=membership_scale_up_load,
                scale_down_load=membership_scale_down_load,
                up_windows=membership_up_windows,
                down_windows=membership_down_windows,
                min_replicas=membership_min_replicas,
                max_replicas=membership_max_replicas)
        self._autoscale_last: AutoscaleDecision | None = None  # keplint: guarded-by=_lock
        self._autoscale_decisions: dict[str, int] = {}  # keplint: guarded-by=_lock
        self._autoscale_shed_seen = 0
        # overload control (ISSUE 12): an AdmissionController in front of
        # the ingest path sheds with 429 + Retry-After BEFORE decode work
        # when the inflight or latency budget is blown — priority-aware,
        # so replay backlogs wait first and live RAPL ground truth sheds
        # last. Disabled (None) keeps the pre-admission ingest path
        # byte-for-byte: shedding off ≡ old behavior.
        self._admission: AdmissionController | None = None
        if admission_enabled:
            self._admission = AdmissionController(
                max_inflight=admission_max_inflight,
                latency_budget=admission_latency_budget,
                retry_after=admission_retry_after,
                retry_after_max=admission_retry_after_max,
                degraded_ttl=degraded_ttl,
                jitter_seed=admission_jitter_seed)
        # the ingest half of the stats (the window's are the scheduler's
        # own, under its lock; the surfaces that print them join the two)
        self._stats = {"reports_total": 0, "rejected_total": 0,
                       "quarantined_total": 0, "malformed_total": 0,
                       "clock_skew_total": 0,
                       "reports_redirected_total": 0,
                       # wire v2: deltas answered 409 needs-keyframe
                       # (missing/mismatched base — agent resends full)
                       "keyframe_requests_total": 0,
                       "duplicates_total": 0, "windows_lost_total": 0}
        # ingest payload bytes by wire version (the v1↔v2 byte-savings
        # evidence: kepler_fleet_ingest_bytes_total{version})
        self._ingest_bytes: dict[int, int] = {1: 0, 2: 0}  # keplint: guarded-by=_lock
        # ingest seconds since start, one sample per report that reached
        # the store: decode, waiting for the store lock, the merge under
        # it, and the history push inside the merge (/debug/window).
        # Company of the aggregator.decode / .merge spans: like them, not
        # taken with telemetry.enabled false
        self._ingest_legs = {"reports": 0, "decode_s": 0.0,  # keplint: guarded-by=_lock
                             "lock_wait_s": 0.0, "merge_s": 0.0,
                             "history_push_s": 0.0}
        # the window path, called down into and never up from: it reaches
        # this class through the two callables alone (history is store;
        # what a lost multi-host mesh means for the ring is membership's)
        self.windows = WindowScheduler(
            history_windows=self._history_windows,
            on_mesh_lost=self._on_mesh_lost,
            clock=self._clock, journal=self._journal,
            model_mode=model_mode, model_params=model_params,
            node_bucket=node_bucket, workload_bucket=workload_bucket,
            backend=backend, accuracy_mode=accuracy_mode,
            history_window=history_window,
            training_dump_dir=training_dump_dir,
            training_dump_max_files=training_dump_max_files,
            cum_retention=max(stale_after * 20.0, 600.0),
            pipeline_depth=pipeline_depth, fused_window_k=fused_window_k,
            bucket_shrink_after=bucket_shrink_after,
            fallback_enabled=fallback_enabled,
            repromote_after=repromote_after,
            dispatch_timeout=dispatch_timeout,
            mesh=mesh, mesh_shape=mesh_shape, mesh_axes=mesh_axes,
            multihost_enabled=multihost_enabled,
            multihost_topology=multihost_topology)

    def name(self) -> str:
        return "fleet-aggregator"

    # -- lifecycle ---------------------------------------------------------

    def init(self) -> None:
        windows = self.windows
        windows.init()  # the mesh, the bucket rounding, the params check
        if self._ring is not None and windows.multihost_active():
            # co-locate ingest with compute (ISSUE 15): ownership derives
            # from the mesh shard map — each replica ingests exactly the
            # agents whose packed rows live on its local devices.
            # aggregator.peers is ordered by jax process index here.
            proc = windows.device_process_fn()
            shard_procs = [proc(d) for d in windows.mesh.devices.flat]
            n_hosts = len(set(shard_procs))
            if len(self._config_peers) != n_hosts:
                raise ValueError(
                    f"aggregator.peers has {len(self._config_peers)} "
                    f"entries but the multi-host mesh spans {n_hosts} "
                    "processes — one peer endpoint per process, in "
                    "process-index order")
            me = windows.self_process()
            if (0 <= me < len(self._config_peers)
                    and self._config_peers[me] != self._self_peer):
                # a misordered list would silently INVERT ownership:
                # every replica ingesting exactly the OTHER host's
                # agents — fail loudly instead
                raise ValueError(
                    f"aggregator.peers[{me}] is "
                    f"{self._config_peers[me]!r} but this replica "
                    f"(process {me}) is aggregator.selfPeer "
                    f"{self._self_peer!r} — the list must be ordered "
                    "by jax process index")
            self._ring = ring_from_mesh(self._config_peers, shard_procs,
                                        epoch=self._ring_epoch_cfg)
            log.info("ingest ring derived from the mesh shard map: "
                     "%d shards over %d hosts, epoch %d, self owns "
                     "%.3f of the shard space", self._ring.n_shards,
                     n_hosts, self._ring.epoch,
                     self._ring.ownership_ratio(self._self_peer))
        self._server.register("/v1/report", "Fleet ingest",
                              "POST node window reports", self._handle_report,
                              max_body=MAX_REPORT_BYTES)
        self._server.register("/v1/reports", "Fleet batch ingest",
                              "POST a batch of node window reports "
                              "(length-prefixed envelope; per-record "
                              "status in the JSON response) — the "
                              "spool-drain replay path",
                              self._handle_report_batch,
                              max_body=MAX_REPORT_BYTES)
        self._server.register("/v1/results", "Fleet results",
                              "attributed watts per node", self._handle_results)
        self._server.register("/debug/window", "Window introspection",
                              "device-plane engine state: rung + "
                              "timeline, shards, bucket ladders, "
                              "compile-cache cost stats",
                              self._handle_window_debug)
        self._server.register("/debug/fleet", "Fleet scoreboard",
                              "per-node health state table",
                              self._handle_fleet_debug)
        self._server.register("/debug/ring", "Ingest ring",
                              "consistent-hash ingest ring: membership "
                              "epoch, peers, ownership share, redirect "
                              "counters", self._handle_ring_debug)
        self._server.register("/debug/journal", "Fleet black box",
                              "HLC-stamped causal event journal "
                              "(?since=<hlc cursor>&limit=N paginates)",
                              make_journal_handler(self._journal))
        self._server.register("/debug/bundle", "Incident bundle",
                              "one-shot incident snapshot: journal + "
                              "rung timeline + scoreboard + ring + "
                              "config fingerprint (canonical JSON — "
                              "feed to python -m kepler_tpu.blackbox)",
                              self._handle_bundle_debug)
        if self._ring is not None:
            self._server.register("/v1/membership", "Elastic membership",
                                  "POST apply/join/leave membership "
                                  "operations (coordinator-lease gated)",
                                  self._handle_membership)
        health = getattr(self._server, "health", None)
        if health is not None:
            health.register_probe("fleet-aggregator", self.health)
            health.register_probe("fleet-window", self.window_health)
            if self._ring is not None:
                health.register_probe("fleet-ring", self.ring_health)
            if self._admission is not None:
                # degraded while shedding — the "ingest tier is actively
                # re-pacing its agents" signal; recovers on its own
                health.register_probe("fleet-ingest",
                                      self._admission.health)
            # ready once init completed: endpoints registered, mesh built,
            # params validated — an empty fleet is still a ready aggregator
            health.register_readiness("fleet-aggregator",
                                      lambda: {"ok": True})
        device = windows.device_fields()
        log.info("aggregator: platform=%s device_kind=%s devices=%d mesh=%s "
                 "model=%s interval=%.1fs", device["platform"],
                 device["device_kind"], device["devices"],
                 dict(windows.mesh.shape), self._model_mode, self._interval)

    def run(self, ctx: CancelContext) -> None:
        self.windows.start()
        while not ctx.cancelled():
            # the wait is the first leg of the next window's record: its
            # span is laid on the record's marks in aggregate_once
            with TraceAnnotation("window.tick_wait",
                                 window=self.windows.tick_began()):
                cancelled = ctx.wait(self._interval)
            if cancelled:
                break
            try:
                self.aggregate_once()
            except Exception:
                log.exception("fleet aggregation failed")
        # deterministic drain: every dispatched window is published before
        # the loop exits — no result is abandoned in flight on shutdown.
        # The publisher finishes the window it is on and stops first, so
        # what is left is published here, in order
        self.windows.stop()
        try:
            self.windows.drain()
        except Exception:
            log.exception("fleet pipeline drain failed")

    # keplint: thread-role=shutdown
    def shutdown(self) -> None:
        self.windows.shutdown()
        self._journal.close()

    # -- ingest ------------------------------------------------------------

    def _handle_report(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        # one telemetry cycle per ingest POST, with the decode and merge
        # legs as stages — the receive half of the delivery trace the
        # agent opened at window emit
        with telemetry.span("aggregator.ingest"):
            ctrl = self._admission
            if request.command != "POST":
                return self._ingest_report(request)
            if not self._observe_request_hlc(request):
                return self._bad_hlc_response()
            # ONE header parse per record, carried from the admission
            # peek through _ingest_payload (v1 used to re-parse the
            # same JSON up to four times; v2 makes this a struct read)
            parsed = try_parse_header(request.body)
            if ctrl is None:
                return self._ingest_report(request, parsed)
            # admission runs BEFORE any decode work: over budget the
            # request is turned away at header-peek cost, and the spool
            # on the agent side makes that loss-free — the record stays
            # durable and replays after the Retry-After hint
            retry = ctrl.admit(self._priority_of(request.body, parsed))
            if retry is not None:
                return self._throttle_response(retry)
            self._note_admitted()
            t0 = _time.perf_counter()
            try:
                return self._ingest_report(request, parsed)
            finally:
                ctrl.done(_time.perf_counter() - t0)

    def _handle_report_batch(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        """``POST /v1/reports``: the batched spool-drain path. Each
        record runs through the SAME single-report ingest internals
        (per-record admission, dedup, quarantine, redirect), and the
        response carries a per-record status list — so one request
        replays K spooled records while every delivery/loss/dedup
        invariant stays per-record. Once admission sheds mid-batch, the
        remaining records are answered 429 without being looked at (the
        whole point is to stop paying decode cost)."""
        with telemetry.span("aggregator.ingest"):
            if request.command != "POST":
                return 405, {"Content-Type": "text/plain"}, b"POST only\n"
            if not self._observe_request_hlc(request):
                return self._bad_hlc_response()
            if fault.fire("replica.down") is not None:
                return (503, {"Content-Type": "text/plain"},
                        b"replica down (fault injection)\n")
            try:
                payloads = decode_report_batch(request.body)
            except WireError as err:
                with self._lock:
                    self._stats["rejected_total"] += 1
                    self._stats["malformed_total"] += 1
                return (400, {"Content-Type": "text/plain"},
                        f"{err}\n".encode())
            ctrl = self._admission
            results: list[dict[str, Any]] = []
            shed_retry: float | None = None
            for body in payloads:
                if shed_retry is not None:
                    # stop paying even peek cost once shedding started
                    results.append({"status": 429,
                                    "retry_after": shed_retry})
                    continue
                parsed = try_parse_header(body)
                if ctrl is not None:
                    retry = ctrl.admit(self._priority_of(body, parsed))
                    if retry is not None:
                        shed_retry = retry
                        self._note_shed_onset(retry)
                        results.append({"status": 429,
                                        "retry_after": retry})
                        continue
                    self._note_admitted()
                t0 = _time.perf_counter()
                try:
                    status, resp_headers, resp_body = \
                        self._ingest_payload(body, parsed)
                finally:
                    if ctrl is not None:
                        ctrl.done(_time.perf_counter() - t0)
                row: dict[str, Any] = {"status": status}
                if status == 421 or (
                        status == 409
                        and resp_headers.get(
                            "X-Kepler-Needs-Keyframe")):
                    # structured responses (owner redirect, needs-
                    # keyframe) keep their JSON shape per record, so
                    # the agent's guards see the same fields as on the
                    # single-record path
                    try:
                        row.update(json.loads(resp_body))
                    except ValueError:
                        pass
                elif status >= 400:
                    row["error"] = resp_body.decode(
                        errors="replace").strip()[:200]
                results.append(row)
            headers = {"Content-Type": "application/json",
                       **self._epoch_headers()}
            if shed_retry is not None:
                headers["Retry-After"] = f"{shed_retry:g}"
            return (200, headers,
                    json.dumps({"results": results}).encode())

    def _throttle_response(
            self, retry: float) -> tuple[int, dict[str, str], bytes]:
        self._note_shed_onset(retry)
        body = json.dumps({"retry_after": retry}).encode()
        return (429, {"Content-Type": "application/json",
                      "Retry-After": f"{retry:g}",
                      **self._epoch_headers()}, body)

    def _note_shed_onset(self, retry: float) -> None:
        """Journal the admission-shed ONSET (False→True edge only —
        steady-state shedding is a rate, not an event)."""
        with self._lock:
            onset = not self._shedding
            self._shedding = True
        if onset:
            self._journal.emit("admission.shed",
                               retry_after=round(float(retry), 3))

    def _note_admitted(self) -> None:
        """An admitted request closes the shed episode: the NEXT shed
        is a fresh onset."""
        if self._shedding:
            with self._lock:
                self._shedding = False

    def _priority_of(self, body: bytes,
                     parsed: "ParsedHeader | None" = None) -> int:
        """Admission priority from a CHEAP header peek (no array decode):
        replay backlogs behind fresh windows, model-estimated nodes
        behind RAPL ground truth, scoreboard-flagged reporters behind
        healthy ones — live attribution accuracy degrades last. With a
        ``parsed`` memo the peek is a dict read, not a re-parse."""
        if parsed is not None:
            name, path, mode = parsed.routing()
        else:
            name, path, mode = peek_routing(body)
        if path == "replay":
            p = PRIORITY_REPLAY_GROUND
        else:
            p = PRIORITY_FRESH_GROUND
        if mode == MODE_MODEL:
            p += 1
        if p == PRIORITY_FRESH_GROUND and name:
            with self._lock:
                flagged = self._scoreboard.flagged(name, self._clock())
            if flagged:
                p = PRIORITY_FRESH_MODEL
        return p

    def _ingest_report(
            self, request: Any,
            parsed: "ParsedHeader | None" = None
            ) -> tuple[int, dict[str, str], bytes]:
        if request.command != "POST":
            return 405, {"Content-Type": "text/plain"}, b"POST only\n"
        if fault.fire("replica.down") is not None:
            # chaos stand-in for a dying/overloaded replica: a 5xx the
            # agent counts as a send failure (failover + spool), never
            # as a permanent rejection
            return (503, {"Content-Type": "text/plain"},
                    b"replica down (fault injection)\n")
        return self._ingest_payload(request.body, parsed)

    # keplint: protocol-transition — base-row LRU touch
    def _delta_base_for(self, parsed: "ParsedHeader"
                        ) -> "_BaseRow | None":
        """Resolve a v2 delta frame's base keyframe. None = answer a
        structured 409 needs-keyframe (missing base after hand-off or
        eviction, run change, base-seq mismatch) — the agent resends
        full, nothing is charged or stored. A hostile node name raises
        into the ordinary quarantine path instead."""
        raw = parsed.header.get("node_name")
        name = sanitize_node_name(raw) if isinstance(raw, str) else ""
        if not name or name != raw:
            raise WireError("node_name must be 1-128 printable ASCII "
                            "chars")
        run = parsed.header.get("run")
        with self._lock:
            base = self._base_rows.get(name)
            if (base is None or not isinstance(run, str)
                    or not delta_base_matches(base.run, base.seq,
                                              run, parsed.base_seq)):
                self._stats["keyframe_requests_total"] += 1
                return None
            self._base_rows[name] = self._base_rows.pop(name)  # LRU touch
        return base

    def _needs_keyframe_response(
            self, parsed: "ParsedHeader"
            ) -> tuple[int, dict[str, str], bytes]:
        body = json.dumps({"needs_keyframe": True,
                           "base_seq": parsed.base_seq}).encode()
        return (409, {"Content-Type": "application/json",
                      "X-Kepler-Needs-Keyframe": "1",
                      **self._epoch_headers()}, body)

    # keplint: requires-lock=_lock
    # keplint: protocol-transition — keyframe plants the delta base
    def _store_base_locked(self, name: str, run: str, seq: int,
                           report: NodeReport,
                           zones: tuple[str, ...]) -> None:
        """Adopt a decoded v2 keyframe as the node's delta base (LRU:
        dict order = recency, oldest evicted at the cap). Runs for
        DUPLICATE keyframes too: a hand-off replay judged dup by the
        seeded tracker must still plant the base, or the agent's next
        delta would 409 forever."""
        self._base_rows.pop(name, None)
        while len(self._base_rows) >= self._base_row_cache:
            self._base_rows.pop(next(iter(self._base_rows)))
        self._base_rows[name] = _BaseRow(run=run, seq=seq,
                                         report=report,
                                         zone_names=zones)

    def _ingest_payload(
            self, body: bytes,
            parsed: "ParsedHeader | None" = None
            ) -> tuple[int, dict[str, str], bytes]:
        spec = fault.fire("aggregator.ingest_slow")
        if spec is not None:
            # chaos stand-in for a sinking ingest path (GC stall, slow
            # disk, CPU-starved replica): inflates the admission
            # controller's latency EWMA the honest way — by being slow
            _time.sleep(float(spec.arg or 0.05))
        if parsed is None:
            parsed = try_parse_header(body)
        if parsed is not None:
            # clamp to the two known versions: the counter keys a metric
            # label and must never grow with hostile frame contents
            version = 2 if parsed.version == 2 else 1
            with self._lock:
                self._ingest_bytes[version] = \
                    self._ingest_bytes.get(version, 0) + len(body)
        content_changed = True
        clock = (_time.monotonic if telemetry.recorder().enabled
                 else _no_clock)
        t_decode = clock()
        try:
            with telemetry.span("aggregator.decode"):
                if (parsed is not None and parsed.version == 2
                        and parsed.is_delta):
                    base = self._delta_base_for(parsed)
                    if base is None:
                        return self._needs_keyframe_response(parsed)
                    report, header, content_changed = decode_delta(
                        body, parsed, base.report, base.zone_names)
                else:
                    # v1 (the pinned JSON path — decoded off the ONE
                    # parse_header memo) or a v2 keyframe (zero-copy
                    # frombuffer views over the request body)
                    report, header = decode_report(body, parsed)
        except (WireError, ValueError) as err:
            # quarantine, charged to the sender when the header survives.
            # The header work runs OFF the store lock — a burst of
            # large malformed bodies must not stall ingest/aggregation.
            # The peeked name is UNVALIDATED wire input (the body already
            # failed decoding): sanitize before it becomes a degradation
            # key, scoreboard row, metric label, or log field (KTL112)
            if parsed is not None:
                raw = parsed.header.get("node_name")
                node = (sanitize_node_name(raw)
                        if isinstance(raw, str) else "")
            else:
                node = sanitize_node_name(peek_node_name(body) or "")
            with self._lock:
                self._stats["rejected_total"] += 1
                self._stats["quarantined_total"] += 1
                self._stats["malformed_total"] += 1
                if node:
                    self._record_degraded_locked(node, "malformed", str(err))
            return 400, {"Content-Type": "text/plain"}, f"{err}\n".encode()
        decode_s = clock() - t_decode
        received = self._clock()
        sent_at = header.get("sent_at")
        if (self._skew_tolerance > 0
                and isinstance(sent_at, (int, float))
                and not isinstance(sent_at, bool)
                and abs(received - float(sent_at)) > self._skew_tolerance):
            # a skewed sender's reports would corrupt staleness aging and
            # cumulative-energy timestamps — quarantine instead of ingest
            skew = float(sent_at) - received
            with self._lock:
                self._stats["rejected_total"] += 1
                self._stats["quarantined_total"] += 1
                self._stats["clock_skew_total"] += 1
                self._record_degraded_locked(
                    report.node_name, "clock_skew",
                    f"sender clock skewed {skew:+.1f}s")
            return (422, {"Content-Type": "text/plain"},
                    f"report clock skewed {skew:+.1f}s beyond tolerance "
                    f"{self._skew_tolerance:g}s\n".encode())
        # header identity coercion is VALIDATING, not converting: a report
        # whose seq/run carry the wrong JSON type (a string seq, a list
        # run) is malformed input from an untrusted network — quarantine
        # and charge the sender, never raise into a 500
        seq_raw = header.get("seq", 0)
        run_raw = header.get("run", "")
        if (isinstance(seq_raw, bool) or not isinstance(seq_raw, int)
                or seq_raw < 0 or not isinstance(run_raw, str)):
            with self._lock:
                self._stats["rejected_total"] += 1
                self._stats["quarantined_total"] += 1
                self._stats["malformed_total"] += 1
                self._record_degraded_locked(
                    report.node_name, "malformed",
                    f"bad header identity: seq={seq_raw!r} run={run_raw!r}")
            return (400, {"Content-Type": "text/plain"},
                    b"seq must be a non-negative integer and run a string\n")
        # ring-header coercion, hardened exactly like run/seq: the
        # owner/epoch/acked_through fields steer redirect handling and
        # loss accounting, so hostile values (non-int, negative, bool,
        # overlong/non-printable owner) are a 400 quarantine charged to
        # the node — never a 500, never silently honored
        owner_raw = header.get("owner", "")
        epoch_val = coerce_epoch(header.get("epoch", 0))
        acked_through = coerce_epoch(header.get("acked_through", 0))
        owner_ok = owner_raw == "" or sanitize_peer(owner_raw) == owner_raw
        if epoch_val is None or acked_through is None or not owner_ok:
            with self._lock:
                self._stats["rejected_total"] += 1
                self._stats["quarantined_total"] += 1
                self._stats["malformed_total"] += 1
                self._record_degraded_locked(
                    report.node_name, "malformed",
                    f"bad ring header: owner={owner_raw!r} "
                    f"epoch={header.get('epoch')!r} "
                    f"acked_through={header.get('acked_through')!r}")
            return (400, {"Content-Type": "text/plain"},
                    b"owner must be a printable string, epoch and "
                    b"acked_through non-negative integers\n")
        # ownership: a report for a node the ring assigns elsewhere is
        # answered with a structured redirect (the agent follows it and
        # re-delivers there) — not stored, not charged, not tracked
        ring = self._ring
        if ring is not None:
            owner = ring.owner(report.node_name)
            if owner != self._self_peer:
                with self._lock:
                    self._stats["reports_redirected_total"] += 1
                    self._last_redirect_at = received
                body = json.dumps({"owner": owner,
                                   "epoch": ring.epoch}).encode()
                return (421, {"Content-Type": "application/json",
                              "X-Kepler-Owner": owner,
                              "X-Kepler-Epoch": str(ring.epoch)}, body)
        stored = _Stored(report=report,
                         zone_names=tuple(header["zone_names"]),
                         received=received,
                         seq=seq_raw,
                         run=run_raw,
                         content_seq=seq_raw,
                         wire_version=(2 if parsed is not None
                                       and parsed.version == 2 else 1))
        # scoreboard input, computed OFF the store lock: the node's
        # self-reported power this window (valid zone energy over dt)
        report_power_w = _report_power_w(report)
        t_wait = clock()
        with telemetry.span("aggregator.merge"), self._lock:
            t_held = clock()
            push_s = 0.0
            prev = self._reports.get(report.node_name)
            # When BOTH sides carry a run nonce the cases are unambiguous:
            # different nonce = fresh agent process (restart), same nonce +
            # seq regression = network reorder or spool redelivery (the
            # dedup window sorts those out). A nonce that matches any run
            # a previous restart superseded is a delayed straggler from a
            # dead run — reject it outright rather than honoring it as
            # another restart (which would also wrongly mark the live run
            # as superseded).
            superseded = self._superseded_runs.get(report.node_name, [])
            if stored.run and stored.run in superseded:
                self._stats["rejected_total"] += 1
                return (409, {"Content-Type": "text/plain"},
                        b"stale run nonce (superseded by a newer agent run)\n")
            has_nonces = (prev is not None and bool(stored.run)
                          and bool(prev.run))
            restarted = has_nonces and stored.run != prev.run
            # wire v2: adopt an accepted keyframe as the node's delta
            # base BEFORE dedup (a duplicate keyframe is still a valid
            # base — see _store_base_locked) but AFTER the superseded-
            # run check, so a dead run can never plant base state
            if (parsed is not None and parsed.version == 2
                    and not parsed.is_delta and stored.run
                    and stored.seq > 0):
                self._store_base_locked(
                    report.node_name, stored.run, stored.seq, report,
                    stored.zone_names)
            # content identity: a FLAG_SAME delta asserts (and decode
            # verified) that this window's content EQUALS the base
            # keyframe's — so the content seq is the BASE's seq, not
            # this window's. Steady state pins every unchanged window
            # to the keyframe identity (zero staged rows); a node that
            # changed and then reverted gets the keyframe identity
            # back, which correctly restages it over the changed row.
            if (not content_changed and parsed is not None
                    and parsed.is_delta and parsed.base_seq > 0):
                stored.content_seq = parsed.base_seq
            if restarted:
                runs = self._superseded_runs.setdefault(
                    report.node_name, [])
                runs.append(prev.run)
                del runs[:-self._superseded_cap]
            # idempotent ingest + loss accounting (nonce-carrying agents
            # only — a pre-nonce agent's seq space restarts unannounced,
            # so gap math on it would fabricate loss). seq 0 means "no
            # sequencing" (encode_report's default): real agents number
            # from 1, and deduping a stream of constant zeros would
            # freeze the node's data on its first window forever.
            lost_windows = 0
            if stored.run and stored.seq > 0:
                tracker = self._seq_trackers.get(report.node_name)
                if tracker is None or tracker.run != stored.run:
                    # the cap tracks the LIVE fleet (2× headroom, floor
                    # for small fleets): a fixed cap below the fleet size
                    # would thrash — every round-robin arrival evicting a
                    # peer's tracker, disabling dedup and fabricating
                    # lost-window counts on every report. Memory is
                    # operator-bounded via aggregator.dedupWindow.
                    cap = max(self._tracker_cap, 2 * len(self._reports))
                    if (report.node_name not in self._seq_trackers
                            and len(self._seq_trackers) >= cap):
                        self._seq_trackers.pop(min(
                            self._seq_trackers,
                            key=lambda n: self._seq_trackers[n].touched))
                    tracker = _SeqTracker(stored.run, self._dedup_window)
                    # hand-off / restart seeding from the agent's
                    # delivered watermark (pure rule: fleet/delivery.py)
                    seed_fresh_tracker(tracker, acked_through,
                                       stored.seq)
                    self._seq_trackers[report.node_name] = tracker
                tracker.touched = received
                # ownership RETURN (elastic membership): the PR 16
                # re-seed rule — the away period's windows were 2xx'd
                # by the interim owner, not lost (pure rule:
                # fleet/delivery.py, model-checked by kepmc)
                ring_epoch = (self._ring.epoch
                              if self._ring is not None else 0)
                reseed_on_ownership_return(tracker, ring_epoch,
                                           acked_through, stored.seq)
                dup, lost = tracker.observe(stored.seq)
                if dup:
                    # at-least-once redelivery (spool replay, LB retry):
                    # acknowledge so the sender's cursor advances, ingest
                    # nothing — the earlier copy already counted. The
                    # duplicate still PROVES the sender is alive: refresh
                    # liveness, or a replay longer than stale_after would
                    # prune this tracker mid-stream and the rest of the
                    # backlog would re-ingest as fresh windows
                    if prev is not None and prev.run == stored.run:
                        prev.received = received
                    self._stats["duplicates_total"] += 1
                    self._stats["reports_total"] += 1
                    self._scoreboard.observe_duplicate(report.node_name,
                                                       received)
                    self._count_ingest_locked(clock, decode_s, t_wait,
                                              t_held, push_s)
                    return 204, self._epoch_headers(), b""
                if lost:
                    lost_windows = lost
                    self._stats["windows_lost_total"] += lost
                    # pop-and-reinsert keeps dict order = recency of last
                    # loss, so cap eviction drops the node that stopped
                    # losing longest ago — never an actively-firing
                    # series (a mid-series counter reset breaks rate()
                    # alerting on exactly this signal)
                    total = self._lost_by_node.pop(report.node_name,
                                                   0) + lost
                    if len(self._lost_by_node) >= self._lost_node_cap:
                        self._lost_by_node.pop(
                            next(iter(self._lost_by_node)))
                    self._lost_by_node[report.node_name] = total
                    log.warning("node %s: %d window(s) lost before seq %d "
                                "(never delivered)", report.node_name,
                                lost, stored.seq)
            # NOTE: the legacy `seq == 1` restart heuristic is gone — a
            # spool replay legitimately starts at seq 1 of an OLD run and
            # must not double-ingest as a "restart"; nonce-carrying agents
            # signal restarts explicitly, and pre-nonce agents simply age
            # out via stale_after before their fresh reports land again.
            if prev is None or restarted or stored.seq >= prev.seq:
                self._reports[report.node_name] = stored
                # history push is NOT idempotent (a dup would shift the
                # window) → require a seq change OR a run change (an agent
                # restart that happens to re-send the previous run's seq
                # value is still a new window). Ratio nodes' estimator
                # output is discarded, so their windows matter only as
                # TRAINING data — accrete them when a dump dir is set.
                # The push happens HERE, under the store lock: acceptance
                # order must equal buffer order (a deferred push could let
                # a concurrent seq=N+1 land before seq=N, derailing the
                # window's time axis) — the append itself is one tiny row
                # per workload; the expensive [N, W, T, F] ASSEMBLY is
                # what runs off this lock (_history_windows).
                if (self._model_mode == "temporal"
                        and (report.mode == MODE_MODEL or self._dump_dir)
                        and (prev is None or restarted
                             or stored.seq != prev.seq)):
                    t_push = clock()
                    self._push_history(report)
                    push_s = clock() - t_push
            self._scoreboard.observe_report(report.node_name, received,
                                            report_power_w,
                                            lost=lost_windows)
            self._observe_delivery_locked(report.node_name, header,
                                          received)
            self._stats["reports_total"] += 1
            self._count_ingest_locked(clock, decode_s, t_wait, t_held,
                                      push_s)
        return 204, self._epoch_headers(), b""

    def _count_ingest_locked(self, clock: Callable[[], float],
                             decode_s: float, t_wait: float,
                             t_held: float, push_s: float) -> None:
        """One report's ingest legs into the sums ``/debug/window``
        serves. Caller holds the store lock, at the end of the merge."""
        if clock is _no_clock:
            return
        legs = self._ingest_legs
        legs["reports"] += 1
        legs["decode_s"] += decode_s
        legs["lock_wait_s"] += t_held - t_wait
        legs["history_push_s"] += push_s
        legs["merge_s"] += clock() - t_held - push_s

    def _epoch_headers(self) -> dict[str, str]:
        """Accepts advertise the ring epoch so settled agents notice a
        membership bump lazily (no extra round-trips); with the journal
        enabled they ALSO carry this replica's HLC stamp, so agents'
        clocks chain causally to the aggregator's (piggyback — never an
        extra round-trip, absent entirely when the journal is off)."""
        headers: dict[str, str] = {}
        hlc_text = self._journal.header()
        if hlc_text is not None:
            headers["X-Kepler-HLC"] = hlc_text
        ring = self._ring
        if ring is not None:
            headers["X-Kepler-Epoch"] = str(ring.epoch)
        return headers

    def _observe_request_hlc(self, request: Any) -> bool:
        """Merge an inbound ``X-Kepler-HLC`` stamp into this replica's
        clock. Returns False ONLY for a present-but-hostile stamp (the
        caller answers 400) — absent headers and chaos/test stand-in
        requests without a ``headers`` attribute are fine. The clamp
        in :meth:`HlcClock.observe` bounds how far a valid-but-vaulted
        stamp can advance us (KTL112: laundered, never trusted)."""
        headers = getattr(request, "headers", None)
        if headers is None:
            return True
        raw = headers.get("X-Kepler-HLC")
        if raw is None:
            return True
        return self._journal.observe_text(raw)

    def _bad_hlc_response(self) -> tuple[int, dict[str, str], bytes]:
        with self._lock:
            self._stats["rejected_total"] += 1
            self._stats["malformed_total"] += 1
        return (400, {"Content-Type": "text/plain"},
                b"malformed X-Kepler-HLC header\n")

    # -- ingest ring (HA ingest tier) --------------------------------------

    def apply_membership(self, peers: Sequence[str], epoch: int, *,
                         source: str = "operator", issuer: str = "",
                         mesh: bool = False) -> int:
        """Adopt a new replica membership — the operator action it has
        always been (config rollout, chaos rebalance), and now ALSO
        the elastic plane's one write path: succession after a host
        death, join/leave fan-out from the lease holder, autoscale
        enactment. Swaps in a NEW ring at a HIGHER epoch and drops
        stored reports for nodes this replica no longer owns — their
        agents get redirected on their next send and replay their
        spool tails to the new owner. Seq trackers are KEPT (bounded
        by their cap): if ownership bounces back, dedup continuity
        absorbs the re-delivered overlap.

        Epoch semantics (ISSUE 16): re-applying the SAME peer set at
        the CURRENT epoch is an idempotent replay (returns 0 — a
        re-delivered broadcast, indistinguishable from a no-op); the
        same epoch with a DIFFERENT set is the split-brain detector
        firing — rejected loudly as ``equal_epoch_conflict`` and
        counted in ``kepler_fleet_membership_rejected_total``. A
        lower epoch is ``stale_epoch``. ``source`` labels the
        applied/rejected counters; ``issuer`` (default: succession
        over the new set) advances the coordinator lease in lock-step
        with the ring.

        A non-operator membership that EXCLUDES this replica retires
        it: the new ring is adopted anyway, every stored node is
        dropped, and all future ingest answers 421 toward the real
        owners — the scale-down path. The operator path keeps the
        strict self-in-set check (excluding yourself by hand is
        almost certainly a typo). ``mesh=True`` asks for the
        mesh-derived ring (and multi-host engine) to be restored over
        the new set — the rejoin path; it needs the peers to be a
        process-ordered subset of the configured list (and, after a
        fabric loss, a fresh incarnation via :meth:`arm_mesh`), and
        falls back to the plain hash ring otherwise.

        Returns the number of nodes handed off. Raises
        :class:`MembershipError` (a ``ValueError``) on rejection."""
        try:
            return self._apply_membership_checked(
                peers, epoch, source=source, issuer=issuer, mesh=mesh)
        except MembershipError as err:
            with self._lock:
                self._membership_rejected[err.reason] = \
                    self._membership_rejected.get(err.reason, 0) + 1
            log.error("membership rejected (%s, source=%s): %s",
                      err.reason, source, err)
            raise

    def _apply_membership_checked(self, peers: Sequence[str],
                                  epoch: int, *, source: str,
                                  issuer: str, mesh: bool) -> int:
        if self._ring is None:
            raise MembershipError(
                "ring_disabled",
                "ingest ring is not enabled (aggregator.peers is empty)")
        current = self._ring
        # the whole epoch/peer-set state machine is the PURE decision
        # (fleet/membership.py, model-checked by kepmc); this method
        # only wires its verdict to the ring/lease/stores
        decision = plan_membership_apply(
            current.epoch, current.peers, current.membership_digest,
            epoch, peers, self._self_peer, source)
        ep = decision.epoch
        if decision.action == "replay":
            log.info("membership replay at epoch %d ignored (same "
                     "peer set, digest %s)", ep,
                     current.membership_digest)
            return 0
        retired = decision.retired
        new, restored = self._build_ring(list(decision.peers), ep, mesh=mesh)
        who = issuer or plan_succession(
            self._lease.holder if self._lease is not None else "",
            new.peers)
        with self._lock:
            self._ring = new
            # the lease advances in lock-step with the ring epoch —
            # adopt cannot conflict here (ep > current epoch by the
            # checks above), so succession state never splits from
            # membership state
            if self._lease is not None:
                self._lease.adopt(who, ep)
            else:
                self._lease = CoordinatorLease(who, ep)
            dropped = [n for n in self._reports
                       if retired or new.owner(n) != self._self_peer]
            for name in dropped:
                del self._reports[name]
                self._history.pop(name, None)
                self._superseded_runs.pop(name, None)
                # the new owner holds no base for it either — dropping
                # ours keeps "409 → keyframe" the one hand-off story
                self._base_rows.pop(name, None)
                # the node reports to its NEW owner now — a row left
                # here would age into a permanent false 'stale' signal
                self._scoreboard.drop(name)
            self._last_membership_at = self._clock()
            self._membership_applied[source] = \
                self._membership_applied.get(source, 0) + 1
        # black box: the apply and the lock-step lease adopt are TWO
        # events — timeline readers correlate successions across
        # replicas by the adopt, membership churn by the apply
        self._journal.emit("membership.apply", epoch=ep,
                           peers=sorted(new.peers), source=source,
                           dropped=len(dropped), retired=retired)
        self._journal.emit("lease.adopt", holder=who, epoch=ep,
                           source=source)
        if self._multihost_enabled:
            # elastic rebuild: the next window does a full re-pack over
            # the new member set — on the restored submesh, or (a
            # non-mesh membership while the multi-host tier runs: the
            # mesh no longer describes ownership) on this replica's own
            # single-host engine until a mesh-path membership restores it
            self.windows.rebuild_engines(**restored)
        with self._lock:
            self._awaiting_membership = False
        log.warning("ingest ring membership changed: epoch %d, %d "
                    "peer(s) (digest %s, issuer %s, source %s), %d "
                    "node(s) handed off%s", new.epoch, len(new),
                    new.membership_digest, who, source, len(dropped),
                    (" — this replica RETIRED (owns nothing, redirects "
                     "everything)" if retired else ""))
        return len(dropped)

    def _build_ring(self, peers: list[str], epoch: int, mesh: bool
                    ) -> tuple[HashRing, dict[str, Any]]:
        """The new ring for a membership change: the mesh-derived ring
        when a mesh restore was requested AND the topology can honor
        it — the peers must be a >=2-process subset of the configured
        process-ordered list (ownership co-location is only true for
        processes the device mesh actually contains); otherwise the
        plain consistent-hash ring. → (the ring, what the scheduler's
        ``rebuild_engines`` is told: the restored submesh, or nothing)."""
        full = self.windows.mesh
        if mesh and self._multihost_enabled and full is not None:
            want = set(peers)
            procs = [i for i, p in enumerate(self._config_peers)
                     if p in want]
            if len(procs) == len(want) and len(procs) >= 2:
                # armed: a rejoin's fresh fabric incarnation (the old
                # one's barriers died with the departed peer)
                armed, self._mesh_arm = self._mesh_arm, None
                proc = self.windows.device_process_fn()
                sub = submesh_for_processes(full, procs, proc)
                order = {p: k for k, p in enumerate(procs)}
                shard_procs = [order[int(proc(d))]
                               for d in sub.devices.flat]
                peers_by_proc = [self._config_peers[p] for p in procs]
                log.info("mesh-derived ring restored over %d process(es) "
                         "(%d shards) at epoch %d", len(procs),
                         len(shard_procs), epoch)
                return (ring_from_mesh(peers_by_proc, shard_procs,
                                       epoch=epoch),
                        {"mesh": sub, "fabric": armed})
            log.warning("mesh-path membership cannot be honored (peers "
                        "%r are not a >=2-process subset of the "
                        "configured process-ordered list); falling back "
                        "to the plain hash ring", sorted(want))
        try:
            return self._ring.with_members(peers, epoch), {}
        except RingError as err:
            raise MembershipError("bad_peer", str(err))

    # -- elastic membership plane (ISSUE 16) -------------------------------

    def arm_mesh(self, fabric: Any) -> None:
        """Arm a fresh fabric incarnation for the NEXT mesh-path
        membership (the rejoin/restore handshake): the virtual
        topology passes its new :class:`HostLocalFabric`; production's
        analog is restarting the ``jax.distributed`` job before
        re-applying the full membership (a dead peer cannot rejoin a
        RUNNING job — see docs/developer/resilience.md). One-shot:
        consumed by the next ``apply_membership(..., mesh=True)``."""
        self._mesh_arm = fabric

    def _on_mesh_lost(self, reason: str) -> None:
        """The scheduler lost its multi-host mesh to a cross-host window
        failure (dead peer, broken collective, fabric loss) and dropped
        to its own single-host engine — sticky within this fabric
        incarnation; a rejoin (``/v1/membership`` join +
        :meth:`arm_mesh`) restores the tier under a NEW one. This heals
        the ring, by DETERMINISTIC SUCCESSION at any mesh size: every
        survivor probes the peer set and computes the same entitled
        issuer — the incumbent lease holder while it survives, else the
        lowest surviving peer. Exactly ONE survivor therefore bumps the
        epoch and broadcasts the survivor membership; the rest hold
        position "degraded, awaiting membership" until it lands. The
        equal-epoch conflict check at apply stays as the backstop a
        partitioned prober could still trip. Displaced agents follow
        421s to the new owners and replay their spool tails: zero
        windows lost."""
        if self._ring is None:
            return
        if not self._multihost_takeover:
            # succession disabled: the operator owns the rebalance —
            # flag the wait so the probe says WHY ingest is degraded
            with self._lock:
                self._awaiting_membership = True
            return
        survivors = self._probe_survivors()
        if set(survivors) == set(self._ring.peers):
            # the issuer's broadcast landed BEFORE this process noticed
            # the death: membership already reflects the survivor set,
            # so there is neither a bump to issue nor one to await
            return
        holder = self._lease.holder if self._lease is not None else ""
        issuer = plan_succession(holder, survivors)
        if issuer != self._self_peer:
            with self._lock:
                self._awaiting_membership = True
            log.warning(
                "mesh demotion: membership succession belongs to "
                "surviving peer %s (lease %s) — holding position, "
                "awaiting its membership broadcast", issuer,
                self._lease.lease_id if self._lease is not None
                else "?")
            return
        epoch = self._ring.epoch + 1
        try:
            self.apply_membership(survivors, epoch,
                                  source="succession",
                                  issuer=self._self_peer)
        except ValueError as err:
            log.error("mesh-demotion succession failed: %s", err)
            with self._lock:
                self._awaiting_membership = True
            return
        self._broadcast_membership(survivors, epoch)

    def _peer_alive(self, peer: str) -> bool:
        """Liveness probe for one peer: the injected seam, or an HTTP
        GET of its ``/healthz`` — ANY HTTP answer (even 503) proves a
        listener; only transport failures read as death."""
        probe = self._peer_alive_fn
        if probe is not None:
            try:
                return bool(probe(peer))
            except Exception:
                return False
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(
                    f"http://{peer}/healthz",
                    timeout=self._membership_probe_timeout):
                return True
        except urllib.error.HTTPError:
            return True
        except Exception:
            return False

    def _probe_survivors(self) -> list[str]:
        """The current peer set filtered by liveness (self is alive by
        definition). Every survivor runs the same probe over the same
        set, so — probe flakes aside, which the equal-epoch conflict
        check backstops — they compute the same survivor list and
        therefore the same succession issuer."""
        ring = self._ring
        if ring is None:
            return [self._self_peer]
        return [peer for peer in ring.peers
                if peer == self._self_peer or self._peer_alive(peer)]

    def _deliver_membership(self, peer: str,
                            payload: Mapping[str, Any]) -> dict:
        """POST one membership payload to ``peer`` (the injected seam,
        or HTTP ``/v1/membership``) and return its JSON reply.
        Transport failures return a structured not-ok reply instead of
        raising — broadcast is best-effort; a replica a broadcast
        misses converges via the epoch headers and the equal-epoch
        replay guard."""
        deliver = self._deliver_fn
        if deliver is not None:
            try:
                reply = deliver(peer, dict(payload))
            except Exception as err:
                return {"ok": False, "reason": "unreachable",
                        "detail": str(err)[:240]}
            if isinstance(reply, Mapping):
                return dict(reply)
            return {"ok": False, "reason": "bad_reply"}
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            f"http://{peer}/v1/membership",
            data=json.dumps(dict(payload)).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        try:
            with urllib.request.urlopen(
                    req, timeout=self._membership_probe_timeout) as resp:
                return json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as err:
            try:
                return json.loads(err.read() or b"{}")
            except Exception:
                return {"ok": False, "reason": "unreachable",
                        "detail": f"http {err.code}"}
        except Exception as err:
            return {"ok": False, "reason": "unreachable",
                    "detail": str(err)[:240]}

    def _broadcast_membership(self, peers: Sequence[str], epoch: int,
                              extra: Sequence[str] = (),
                              mesh: bool = False) -> None:
        """Fan the just-applied membership out to every OTHER member
        (plus ``extra`` — e.g. a peer the membership just removed, so
        it retires instead of serving a stale ring)."""
        # the issuer is the CURRENT lease holder, not necessarily this
        # replica: a holder retiring itself (leave) hands the lease to
        # its successor in the local apply, and the fan-out must carry
        # that successor or receivers would adopt the departed holder
        issuer = self._self_peer
        if not _BUG_BROADCAST_SELF_ISSUER \
                and self._lease is not None and self._lease.holder:
            issuer = self._lease.holder
        payload: dict[str, Any] = {
            "op": "apply", "peers": list(peers), "epoch": int(epoch),
            "issuer": issuer, "mesh": bool(mesh)}
        if self._lease is not None:
            payload["lease"] = self._lease.lease_id
        hlc_text = self._journal.header()
        if hlc_text is not None:
            # the HLC piggyback: receivers' journals order their apply
            # AFTER the issuer's (causal chain through the broadcast)
            payload["hlc"] = hlc_text
        for peer in sorted(set(peers) | set(extra)):
            if peer == self._self_peer:
                continue
            reply = self._deliver_membership(peer, payload)
            if not reply.get("ok", False):
                log.warning("membership broadcast to %s not applied: %s",
                            peer, reply.get("reason", "unknown"))

    def request_join(self, *, mesh: bool = False, via: str = "") -> dict:
        """Rejoin/new-host registration, run on the JOINING replica:
        register with the lease holder (``via`` overrides the first
        peer to ask), follow ``not_leader`` redirects, then adopt the
        returned membership — ring at the granted epoch, INCUMBENT
        holder from the reply (a rejoining peer therefore never
        self-elects over a live lease, even when it sorts lowest), and
        with ``mesh=True`` the mesh-derived ring + multi-host engine
        over the restored set. Returns the holder's reply."""
        if self._ring is None:
            raise MembershipError(
                "ring_disabled",
                "ingest ring is not enabled (aggregator.peers is empty)")
        payload = {"op": "join", "peer": self._self_peer,
                   "mesh": bool(mesh)}
        candidates: list[str] = []
        if via and via != self._self_peer:
            candidates.append(via)
        holder = self._lease.holder if self._lease is not None else ""
        if holder and holder != self._self_peer \
                and holder not in candidates:
            candidates.append(holder)
        for p in self._ring.peers:
            if p != self._self_peer and p not in candidates:
                candidates.append(p)
        reply: dict = {"ok": False, "reason": "unreachable",
                       "detail": "no peer to register with"}
        hops = 0
        max_hops = len(self._ring.peers) + 2
        while candidates and hops < max_hops:
            target = candidates.pop(0)
            hops += 1
            reply = self._deliver_membership(target, payload)
            if reply.get("reason") == "not_leader":
                nxt = sanitize_peer(reply.get("holder"))
                if nxt and nxt != self._self_peer \
                        and nxt != target:
                    candidates.insert(0, nxt)
                continue
            if reply.get("ok"):
                break
        if not reply.get("ok"):
            with self._lock:
                self._membership_rejected["join_failed"] = \
                    self._membership_rejected.get("join_failed", 0) + 1
            raise MembershipError(
                "join_failed",
                f"no lease holder accepted the join: "
                f"{reply.get('reason', 'unreachable')}")
        peers = [sanitize_peer(p) for p in reply.get("peers", [])]
        epoch = coerce_epoch(reply.get("epoch"))
        granted_holder = sanitize_peer(reply.get("holder")) or ""
        if epoch is None or not peers or any(p is None for p in peers):
            raise MembershipError(
                "bad_payload",
                "join reply did not carry a valid membership")
        try:
            self.apply_membership(peers, epoch, source="join",
                                  issuer=granted_holder, mesh=mesh)
        except MembershipError as err:
            # the holder's broadcast may have raced ahead of the reply
            # (our epoch already advanced) — that is convergence, not
            # failure; anything else propagates
            if err.reason != "stale_epoch":
                raise
        if granted_holder and self._lease is not None and epoch is not None:
            try:
                # an equal-epoch replay above skips the lease adopt —
                # take the incumbent from the reply explicitly
                before = (self._lease.holder, self._lease.epoch)
                self._lease.adopt(granted_holder, epoch)
                if (self._lease.holder, self._lease.epoch) != before:
                    self._journal.emit("lease.adopt",
                                       holder=granted_holder,
                                       epoch=epoch, source="join_reply")
            except MembershipError:
                pass  # a fresher lease was already adopted locally
        return reply

    def _membership_join(self, peer: str, mesh: bool
                         ) -> tuple[int, dict[str, str], bytes]:
        """Lease-holder handling of a join registration: fold the peer
        into the membership at epoch+1, fan out, and answer the joiner
        with the full adopted state (peers, epoch, holder) — the
        joiner ADOPTS the incumbent lease from this reply."""
        ring, lease = self._ring, self._lease
        if peer in ring.peers:
            # idempotent re-registration: answer the current state
            body = {"ok": True, "epoch": ring.epoch,
                    "peers": list(ring.peers),
                    "holder": lease.holder if lease else "",
                    "lease": lease.lease_id if lease else "",
                    "already_member": True}
            return (200, {"Content-Type": "application/json"},
                    json.dumps(body).encode())
        peers = sorted(set(ring.peers) | {peer})
        epoch = ring.epoch + 1
        try:
            self.apply_membership(peers, epoch, source="join",
                                  issuer=self._self_peer, mesh=mesh)
        except MembershipError as err:
            body = {"ok": False, "reason": err.reason,
                    "error": str(err)}
            return (409, {"Content-Type": "application/json"},
                    json.dumps(body).encode())
        self._broadcast_membership(peers, epoch, mesh=mesh)
        ring, lease = self._ring, self._lease
        body = {"ok": True, "epoch": ring.epoch,
                "peers": list(ring.peers),
                "holder": lease.holder if lease else "",
                "lease": lease.lease_id if lease else ""}
        return (200, {"Content-Type": "application/json"},
                json.dumps(body).encode())

    def _membership_leave(self, peer: str
                          ) -> tuple[int, dict[str, str], bytes]:
        """Lease-holder handling of a graceful leave: drop the peer at
        epoch+1 and fan out — INCLUDING to the leaver, whose wire
        apply retires it (it keeps the new ring it is not in and
        redirects everything)."""
        ring = self._ring
        if peer not in ring.peers:
            body = {"ok": True, "epoch": ring.epoch,
                    "peers": list(ring.peers), "already_left": True}
            return (200, {"Content-Type": "application/json"},
                    json.dumps(body).encode())
        remaining = sorted(set(ring.peers) - {peer})
        epoch = ring.epoch + 1
        try:
            # issuer defaults to succession over the remaining set, so
            # the holder leaving ITSELF hands the lease to the lowest
            # survivor in the same apply
            self.apply_membership(remaining, epoch, source="leave")
        except MembershipError as err:
            body = {"ok": False, "reason": err.reason,
                    "error": str(err)}
            return (409, {"Content-Type": "application/json"},
                    json.dumps(body).encode())
        self._broadcast_membership(remaining, epoch, extra=[peer])
        ring, lease = self._ring, self._lease
        body = {"ok": True, "epoch": ring.epoch,
                "peers": list(ring.peers),
                "holder": lease.holder if lease else "",
                "lease": lease.lease_id if lease else ""}
        return (200, {"Content-Type": "application/json"},
                json.dumps(body).encode())

    def _membership_reject(self, status: int, reason: str, detail: str
                           ) -> tuple[int, dict[str, str], bytes]:
        with self._lock:
            self._membership_rejected[reason] = \
                self._membership_rejected.get(reason, 0) + 1
        body = {"ok": False, "reason": reason, "error": detail}
        return (status, {"Content-Type": "application/json"},
                json.dumps(body).encode())

    def _handle_membership(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        """``POST /v1/membership``: the elastic-membership wire plane.
        Ops: ``apply`` (adopt an issuer's membership — the broadcast
        receiver), ``join`` (a rejoining/new replica registers with
        the lease holder), ``leave`` (graceful scale-down). Every
        field is laundered by ``validate_membership_payload`` before
        it can steer the ring, reach a log line, or key a metric; a
        non-holder answers join/leave with a structured ``not_leader``
        redirect naming the holder (the membership plane's 421)."""
        if request.command != "POST":
            return (405, {"Content-Type": "text/plain"},
                    b"POST membership operations\n")
        try:
            raw = json.loads(request.body or b"{}")
        except ValueError:
            return self._membership_reject(
                400, "bad_payload", "membership body must be JSON")
        try:
            cleaned = validate_membership_payload(raw)
        except MembershipError as err:
            return self._membership_reject(400, err.reason, str(err))
        if "hlc" in cleaned:
            # already laundered to an HLC by the validator; the observe
            # clamps a vaulted physical clock (KTL112)
            self._journal.observe(cleaned["hlc"])
        op = cleaned.get("op")
        if op == "apply":
            if "peers" not in cleaned or "epoch" not in cleaned:
                return self._membership_reject(
                    400, "bad_payload",
                    "membership apply needs peers and epoch")
            try:
                dropped = self.apply_membership(
                    cleaned["peers"], cleaned["epoch"], source="wire",
                    issuer=cleaned.get("issuer", ""),
                    mesh=cleaned["mesh"])
            except MembershipError as err:
                # already counted by apply_membership's wrapper
                body = {"ok": False, "reason": err.reason,
                        "error": str(err),
                        "epoch": (self._ring.epoch
                                  if self._ring is not None else 0)}
                return (409, {"Content-Type": "application/json"},
                        json.dumps(body).encode())
            ring, lease = self._ring, self._lease
            body = {"ok": True, "dropped": dropped,
                    "epoch": ring.epoch if ring is not None else 0,
                    "holder": lease.holder if lease else ""}
            return (200, {"Content-Type": "application/json"},
                    json.dumps(body).encode())
        if op in ("join", "leave"):
            if self._ring is None:
                return self._membership_reject(
                    409, "ring_disabled",
                    "ingest ring is not enabled on this replica")
            peer = cleaned.get("peer")
            if not peer:
                return self._membership_reject(
                    400, "bad_payload", f"membership {op} needs peer")
            lease = self._lease
            if lease is None or lease.holder != self._self_peer:
                body = {"ok": False, "reason": "not_leader",
                        "holder": lease.holder if lease else "",
                        "epoch": self._ring.epoch}
                return (421, {"Content-Type": "application/json"},
                        json.dumps(body).encode())
            if op == "join":
                return self._membership_join(peer, cleaned["mesh"])
            return self._membership_leave(peer)
        return self._membership_reject(
            400, "bad_op", "membership payload needs an op "
            "(apply | join | leave)")

    # -- autoscale (ISSUE 16) ----------------------------------------------

    def _autoscale_tick(self) -> None:
        """One autoscale observation per aggregation interval: fold
        the fleet's already-recorded signals (admission load, shed
        deltas, ingest-latency EWMA, scoreboard states) into the
        hysteresis policy. Recommendations are always surfaced (gauge
        + log); they are ENACTED — through the same apply_membership
        plane as every other change — only when
        ``aggregator.membership.autoApply`` is on AND this replica
        holds the lease, so ``autoApply=false`` keeps operator-driven
        behavior byte-for-byte."""
        policy = self._autoscale
        if policy is None or self._ring is None:
            return
        ctrl = self._admission
        shed_total = (sum(ctrl.shed_by_reason().values())
                      if ctrl is not None else 0)
        now = self._clock()
        with self._lock:
            live_nodes = len(self._reports)
            states = self._scoreboard.states(now, self._stale_after)
        flagged = sum(1 for code in states.values() if code != 0)
        sig = AutoscaleSignals(
            load=ctrl.load() if ctrl is not None else 0.0,
            shed_delta=max(0, shed_total - self._autoscale_shed_seen),
            ingest_latency_s=(ctrl.latency_ewma()
                              if ctrl is not None else 0.0),
            live_nodes=live_nodes, flagged_nodes=flagged,
            replicas=len(self._ring))
        self._autoscale_shed_seen = shed_total
        decision = policy.observe(sig)
        with self._lock:
            self._autoscale_last = decision
            self._autoscale_decisions[decision.direction] = \
                self._autoscale_decisions.get(decision.direction, 0) + 1
        if decision.direction == "hold":
            return
        log.warning("autoscale recommendation: scale %s to %d "
                    "replica(s) — %s", decision.direction,
                    decision.replicas, decision.reason)
        if not self._membership_auto_apply:
            return
        lease = self._lease
        if lease is None or lease.holder != self._self_peer:
            return  # only the lease holder enacts membership
        try:
            self._enact_scale(decision)
        except ValueError as err:
            log.error("autoscale enactment failed: %s", err)

    def _enact_scale(self, decision: AutoscaleDecision) -> None:
        """Turn one non-hold autoscale decision into a membership:
        scale-up promotes the first unused
        ``aggregator.membership.standbyPeers`` entry; scale-down
        retires the highest-sorting non-holder peer (deterministic,
        and never the lease holder — that would orphan the lease
        mid-change)."""
        ring = self._ring
        current = set(ring.peers)
        extra: list[str] = []
        if decision.direction == "up":
            pool = [p for p in self._standby_peers if p not in current]
            if not pool:
                log.warning(
                    "autoscale wants %d replicas but "
                    "aggregator.membership.standbyPeers has no unused "
                    "entry — recommendation stands, nothing enacted",
                    decision.replicas)
                return
            peers = sorted(current | {pool[0]})
        else:
            victims = [p for p in sorted(current, reverse=True)
                       if p != self._self_peer]
            if not victims:
                return
            peers = sorted(current - {victims[0]})
            extra = [victims[0]]
        epoch = ring.epoch + 1
        self.apply_membership(peers, epoch, source="autoscale",
                              issuer=self._self_peer)
        changed = sorted(set(peers) ^ current)
        self._journal.emit("autoscale.enact",
                           direction=decision.direction, epoch=epoch,
                           peer=changed[0] if changed else "",
                           replicas=len(peers), reason=decision.reason)
        self._broadcast_membership(peers, epoch, extra=extra)

    def ring_health(self) -> dict:
        """``fleet-ring`` probe for /healthz: degraded while a hand-off
        is actively settling — a redirect answered or a membership
        change applied within ``degradedTtl``. That is the operator's
        "rebalance in progress" signal; it recovers on its own once
        displaced agents stop arriving here."""
        ring = self._ring
        now = self._clock()
        with self._lock:
            last_redirect = self._last_redirect_at
            last_membership = self._last_membership_at
            redirected = self._stats["reports_redirected_total"]
            awaiting = self._awaiting_membership
        settling = any(
            t is not None and now - t <= self._degraded_ttl
            for t in (last_redirect, last_membership))
        lease = self._lease
        out = {
            "ok": not settling and not awaiting,
            "epoch": ring.epoch if ring is not None else 0,
            "peers": len(ring) if ring is not None else 0,
            "self": self._self_peer,
            "redirected_total": redirected,
            "lease_holder": lease.holder if lease is not None else "",
            "lease_epoch": lease.epoch if lease is not None else 0,
        }
        if awaiting:
            out["awaiting_membership"] = True
            out["detail"] = ("degraded, awaiting membership: a peer "
                             "died and this replica is not the "
                             "succession issuer (or takeover is off) — "
                             "recovers on the issuer's broadcast or an "
                             "operator apply_membership")
        if last_redirect is not None:
            out["last_redirect_age_s"] = round(now - last_redirect, 3)
        if last_membership is not None:
            out["last_membership_age_s"] = round(now - last_membership, 3)
        return out

    # keplint: requires-lock=_lock
    def _observe_delivery_locked(self, node: str, header: Mapping,
                                 received: float) -> None:
        """Close the window's delivery trace: observe emit→ingest latency
        into ``kepler_fleet_delivery_latency_seconds``.

        Runs only for ACCEPTED reports (duplicates were already measured
        when their first copy arrived; quarantined reports never merged).
        Fresh sends measure from the agent's ``emitted_at``; spool
        replays from the ORIGINAL ``appended_at``, under ``path=replay``.
        All header fields are untrusted: non-numeric stamps mean no
        observation, and the path label is clamped to the two known
        values so hostile input can't mint series."""
        def _num(v: object) -> float | None:
            return (float(v) if isinstance(v, (int, float))
                    and not isinstance(v, bool) else None)

        emitted = _num(header.get("emitted_at"))
        if emitted is None:
            return  # pre-telemetry agent: no trace to close
        path = ("replay" if header.get("delivery_path") == "replay"
                else "fresh")
        basis = emitted
        if path == "replay":
            appended = _num(header.get("appended_at"))
            if appended is not None:
                basis = appended
        latency = max(0.0, received - basis)
        self._delivery_hist[path].observe(latency)
        if path == "fresh":
            # the scoreboard's per-node EWMA tracks network health, so
            # replay latency (outage age, not delivery speed) stays out
            self._scoreboard.observe_delivery(node, latency)
        trace = header.get("trace")
        if trace:
            log.debug("delivery trace %s closed: node=%s path=%s "
                      "latency=%.3fs", trace, node, path, latency)

    def _push_history(self, report: NodeReport) -> None:
        """Advance the node's feature-history window (temporal mode).
        Caller holds the store lock; the buffer's own lock (ordered
        store→buffer, matching _history_windows' buffer-only usage) still
        guards against a concurrent window assembly reading the node."""
        from kepler_tpu.resource.informer import FeatureBatch

        entry = self._history.get(report.node_name)
        if entry is None:
            entry = (threading.Lock(),
                     HistoryBuffer(window=self._history_window))
            self._history[report.node_name] = entry
        lock, buf = entry
        kinds = (report.workload_kinds if report.workload_kinds is not None
                 else np.zeros(len(report.workload_ids), np.int8))
        batch = FeatureBatch(
            kinds=kinds,
            ids=list(report.workload_ids),
            cpu_deltas=np.asarray(report.cpu_deltas, np.float32),
            node_cpu_delta=float(report.node_cpu_delta),
            usage_ratio=float(report.usage_ratio),
        )
        with lock:
            buf.push(batch, dt_s=float(report.dt_s))

    # -- degradation accounting --------------------------------------------

    def _record_degraded_locked(self, node: str, reason: str,
                                detail: str) -> None:
        """Charge one quarantined report to ``node``. Caller holds _lock."""
        node = node[:self._degraded_name_cap]
        entry = self._degraded.get(node)
        if entry is None:
            # black box: ONSET only — the node ENTERING the degraded
            # set is the event; per-report charges are counters
            self._journal.emit("quarantine.onset", node=node,
                               reason=reason)
            if len(self._degraded) >= self._degraded_cap:
                oldest = min(self._degraded,
                             key=lambda n: self._degraded[n]["last_at"])
                del self._degraded[oldest]
            entry = {"malformed": 0, "clock_skew": 0,
                     "last_error": "", "last_at": 0.0}
            self._degraded[node] = entry
        entry[reason] += 1
        entry["last_error"] = detail
        entry["last_at"] = self._clock()
        self._scoreboard.observe_quarantine(node, entry["last_at"], reason)
        log.warning("quarantined %s report from node %s: %s",
                    reason, node, detail)

    def degraded_nodes(self) -> dict[str, dict]:
        """Nodes with quarantined reports inside the decay window."""
        now = self._clock()
        with self._lock:
            return {n: dict(e) for n, e in self._degraded.items()
                    if now - e["last_at"] <= self._degraded_ttl}

    def health(self) -> dict:
        """Probe for /healthz: degraded while any node's reports are being
        quarantined (decays after degraded_ttl of clean ingest)."""
        degraded = self.degraded_nodes()
        last = self.windows.last_window_at()
        out = {
            "ok": not degraded,
            "degraded_nodes": sorted(degraded),
            "quarantined_total": self._stats["quarantined_total"],
            "windows_lost_total": self._stats["windows_lost_total"],
            "duplicates_total": self._stats["duplicates_total"],
        }
        if last is not None:
            out["last_window_age_s"] = round(self._clock() - last, 3)
        return out

    def window_health(self) -> dict:
        """``fleet-window`` probe for /healthz: the scheduler's ladder
        (degraded while the device window leg runs below the full
        packed-pipelined rung), joined on a multi-host tier with what
        membership knows: the lease, and whether this replica is
        waiting for a membership."""
        if not self._multihost_enabled:
            return self.windows.health()
        lease = self._lease
        with self._lock:
            awaiting = self._awaiting_membership
        out = self.windows.health({
            "awaiting_membership": awaiting,
            "lease_holder": lease.holder if lease is not None else "",
            "lease_epoch": lease.epoch if lease is not None else 0,
        })
        if awaiting:
            # a peer died and this replica is NOT the succession issuer
            # (or takeover is disabled): engines rebuilt over a stale
            # ring would misattribute, so the probe flags it until the
            # issuer's broadcast (or an operator apply_membership) lands
            out["ok"] = False
            out["multihost"]["detail"] = "degraded, awaiting membership"
        return out

    # -- aggregation -------------------------------------------------------

    def aggregate_once(self) -> "FleetResults | None":
        """One step of the loop: snapshot the live reports (pruning the
        store of what went stale) and hand them to the scheduler, which
        dispatches this interval's window and publishes the oldest in
        flight if it is still there (``WindowScheduler.step``: with
        ``pipeline_depth`` 1 every call publishes the window it
        assembled; deeper, or under ``run``'s publisher thread, what it
        returns is whatever THIS call published, maybe nothing).

        An empty fleet drains the pipeline instead of dispatching, so
        results never rot in flight when reports stop.
        """
        begin = _time.monotonic()
        now = self._clock()
        # the window's record: the last_*_ms gauges, the legs on
        # /debug/window and the legs' spans are all differences of its
        # marks. A tick that finds the fleet empty takes no sequence
        # number and leaves no record.
        rec = self.windows.new_record(now, begin)
        if rec.tick is not None:  # a cycle of its own, as the wait was
            telemetry.mark_span("window.tick_wait", rec.tick, begin,
                                window=rec.seq)
        # one telemetry cycle per non-empty fleet window, opened on the
        # record's first mark so that it covers the snapshot: a cycle is
        # what last_attribution_ms counts, with the record's legs as
        # stages (the histograms add distribution). An empty fleet's
        # cycle is discarded.
        cycle = telemetry.span("aggregator.window", window=rec.seq)
        cycle.open_at(begin)
        try:
            with rec.leg("window.snapshot"), self._lock:
                live = {name: s for name, s in self._reports.items()
                        if now - s.received <= self._stale_after}
                self._reports = dict(live)
                for name in [n for n in self._history if n not in live]:
                    del self._history[name]
                for name in [n for n in self._superseded_runs
                             if n not in live]:
                    del self._superseded_runs[name]
                # _seq_trackers are NOT pruned here: they must survive
                # partitions longer than stale_after (see __init__
                # comment)
                for name in [n for n, e in self._degraded.items()
                             if now - e["last_at"] > self._degraded_ttl]:
                    del self._degraded[name]
            # one autoscale observation per aggregation interval — BEFORE
            # the empty-fleet early return, so an idle fleet still feeds
            # the scale-down streak
            self._autoscale_tick()
            if live:
                stored_sorted = sorted(live.values(),
                                       key=lambda s: s.report.node_name)
                zone_names = sorted(
                    {z for s in stored_sorted for z in s.zone_names})
                return self.windows.step(stored_sorted, zone_names, now,
                                         rec)
            cycle.discard()
        finally:
            cycle.close_at(_time.monotonic())
        return self.windows.drain()

    def _history_windows(self, batch: Any) -> tuple[np.ndarray,
                                                    np.ndarray]:
        """→ (feat_hist [N, W, T, F], t_valid [N, W, T]) aligned with the
        padded fleet batch's (node, workload) layout.

        Holds only ONE node's buffer lock at a time (never the report-
        store lock), so ingest POSTs stall at most for one node's
        ``window_arrays`` — not the whole [N, W, T, F] assembly. Each
        node's windows are unrolled straight into its rows of the result."""
        from kepler_tpu.models.features import NUM_FEATURES

        n, w = batch.cpu_deltas.shape
        t = self._history_window
        hist = np.zeros((n, w, t, NUM_FEATURES), np.float32)
        tv = np.zeros((n, w, t), bool)
        with self._lock:
            entries = [self._history.get(batch.node_names[i])
                       for i in range(batch.n_nodes)]
        for i, entry in enumerate(entries):
            ids = batch.workload_ids[i]
            if entry is None or not ids:
                continue
            lock, buf = entry
            k = len(ids)
            with lock:
                buf.window_arrays(ids, out=(hist[i, :k], tv[i, :k]))
        return hist, tv

    # -- read endpoints ----------------------------------------------------

    def _handle_results(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        from urllib.parse import unquote_plus

        query = ""
        if "?" in request.path:
            query = request.path.split("?", 1)[1]
        node = None
        for part in query.split("&"):
            if part.startswith("node="):
                node = unquote_plus(part[len("node="):])
        results = self.windows.results()
        if node is not None:
            if results is None or node not in results:
                return (404, {"Content-Type": "text/plain"},
                        f"no results for node {node!r}\n".encode())
            payload = results.render_node(node)
        else:
            nodes = ({} if results is None
                     else {name: results.render_node(name)
                           for name in results.names})
            payload = {"nodes": nodes, "stats": self._joined_stats()}
        return (200, {"Content-Type": "application/json"},
                json.dumps(payload).encode())

    def _joined_stats(self) -> dict[str, Any]:
        """The ingest stats and the window's, as the one dict they were:
        ingest keys first."""
        with self._lock:
            stats: dict[str, Any] = dict(self._stats)
        stats.update(self.windows.stats())
        return stats

    def _handle_window_debug(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        """``GET /debug/window``: the scheduler's flight-recorder dump
        (``WindowScheduler.debug``: rung, timeline, engines, ``stats``,
        the windows' ``counts``) with the ingest seconds summed since
        start (``ingest``) and the last complete window ``records``
        (``fleet/window_record.py``)."""
        payload, records = self.windows.debug()
        with self._lock:
            payload["ingest"] = dict(self._ingest_legs)
        # copied under the locks, rendered outside them. The records are
        # JSON text (a row is rendered once): they are spliced in as the
        # body's last key, not parsed and dumped again
        body = (f'{json.dumps(payload)[:-1]}, '
                f'"records": {records_json(records)}}}')
        return 200, {"Content-Type": "application/json"}, body.encode()

    def _handle_ring_debug(
            self, request: Any) -> tuple[int, dict[str, str], bytes]:
        """``GET /debug/ring``: the ingest ring's membership +
        ownership view from THIS replica — epoch, peers, hash-space
        share, owned node count, redirect accounting. ``enabled: false``
        (epoch 0) when the tier runs single-replica."""
        ring = self._ring
        now = self._clock()
        with self._lock:
            redirected = self._stats["reports_redirected_total"]
            last_redirect = self._last_redirect_at
            owned = len(self._reports)
        payload: dict[str, Any] = {
            "enabled": ring is not None,
            "epoch": ring.epoch if ring is not None else 0,
            "self": self._self_peer,
            "peers": list(ring.peers) if ring is not None else [],
            "vnodes": ring.vnodes if ring is not None else 0,
            "ownership_ratio": (
                round(ring.ownership_ratio(self._self_peer), 6)
                if ring is not None else 1.0),
            "owned_nodes": owned,
            "redirected_total": redirected,
            "last_redirect_age_s": (
                round(now - last_redirect, 3)
                if last_redirect is not None else None),
        }
        if ring is not None:
            payload["digest"] = ring.membership_digest
            lease = self._lease
            with self._lock:
                awaiting = self._awaiting_membership
                decision = self._autoscale_last
                rejected = dict(self._membership_rejected)
                applied = dict(self._membership_applied)
            payload["membership"] = {
                "lease": lease.describe() if lease is not None else None,
                "awaiting_membership": awaiting,
                "auto_apply": self._membership_auto_apply,
                "rejected_total": rejected,
                "applied_total": applied,
                "standby_peers": list(self._standby_peers),
            }
            if decision is not None:
                payload["membership"]["autoscale"] = {
                    "direction": decision.direction,
                    "replicas": decision.replicas,
                    "reason": decision.reason,
                }
        return (200, {"Content-Type": "application/json"},
                json.dumps(payload).encode())

    def _handle_fleet_debug(self, request: Any) -> tuple[int,
                                                         dict[str, str],
                                                    bytes]:
        """``GET /debug/fleet``: the per-node scoreboard table."""
        now = self._clock()
        with self._lock:
            snap = self._scoreboard.snapshot(now, self._stale_after)
        return (200, {"Content-Type": "application/json"},
                json.dumps(snap).encode())

    def _handle_bundle_debug(self, request: Any) -> tuple[int,
                                                          dict[str, str],
                                                          bytes]:
        """``GET /debug/bundle``: the one-shot incident snapshot —
        journal + rung timeline + scoreboard + ring view + config
        fingerprint, as CANONICAL JSON (sorted keys, no whitespace) so
        two captures of the same state are byte-identical. Feed the
        file straight to ``python -m kepler_tpu.blackbox``."""
        return (200, {"Content-Type": "application/json"},
                canonical_json(self.bundle()) + b"\n")

    def bundle(self) -> dict[str, Any]:
        """The incident-bundle document (kepler-bundle/v1). Pure state
        capture — safe to call from tests and the chaos conductor."""
        now = self._clock()
        ring = self._ring
        lease = self._lease
        with self._lock:
            scoreboard = self._scoreboard.snapshot(now, self._stale_after)
        stats = self._joined_stats()
        rung, timeline = self.windows.rung_timeline()
        ring_view: dict[str, Any] = {
            "enabled": ring is not None,
            "epoch": ring.epoch if ring is not None else 0,
            "peers": list(ring.peers) if ring is not None else [],
            "holder": lease.holder if lease is not None else "",
        }
        if ring is not None:
            ring_view["digest"] = ring.membership_digest
        return {
            "schema": "kepler-bundle/v1",
            "node": self._journal.node or self._self_peer,
            "captured_hlc": (self._journal.hlc.now().to_dict()
                             if self._journal.enabled else None),
            "journal": self._journal.snapshot(),
            "journal_stats": self._journal.stats(),
            "rung": rung,
            "rung_timeline": timeline,
            "scoreboard": scoreboard,
            "ring": ring_view,
            "stats": {k: stats[k] for k in sorted(stats)
                      if isinstance(stats[k], (int, float, str))},
            "config_fingerprint": self._config_fingerprint,
        }

    # -- prometheus (cluster-level families) -------------------------------

    def collect(self) -> "Iterator[Any]":
        """prometheus_client custom-collector hook (kepler_fleet_*)."""
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )
        # black-box families ride the aggregator's registration (the
        # binary registers ONE collector; the journal's events/HLC
        # families must not need a second)
        yield from self._journal.collect()
        yield from self.windows.collect()
        with self._lock:
            stats = dict(self._stats)
        reports = CounterMetricFamily(
            "kepler_fleet_reports_total", "Node reports received")
        reports.add_metric([], stats["reports_total"])
        yield reports
        rejected = CounterMetricFamily(
            "kepler_fleet_reports_rejected_total", "Malformed reports rejected")
        rejected.add_metric([], stats["rejected_total"])
        yield rejected
        quarantined = CounterMetricFamily(
            "kepler_fleet_reports_quarantined_total",
            "Reports quarantined before ingest, by reason",
            labels=["reason"])
        quarantined.add_metric(["malformed"], stats["malformed_total"])
        quarantined.add_metric(["clock_skew"], stats["clock_skew_total"])
        yield quarantined
        duplicates = CounterMetricFamily(
            "kepler_fleet_reports_duplicate_total",
            "Redelivered (run, seq) reports absorbed by the dedup window")
        duplicates.add_metric([], stats["duplicates_total"])
        yield duplicates
        redirected = CounterMetricFamily(
            "kepler_fleet_reports_redirected_total",
            "Reports answered with a 421 owner redirect (node owned by "
            "another ring replica; the agent follows to the owner)")
        redirected.add_metric([], stats["reports_redirected_total"])
        yield redirected
        keyframes = CounterMetricFamily(
            "kepler_fleet_reports_keyframe_requests_total",
            "Wire-v2 delta frames answered with a structured 409 "
            "needs-keyframe (base missing after hand-off/eviction or "
            "run/seq mismatch) — the agent resends full, never a loss")
        keyframes.add_metric([], stats["keyframe_requests_total"])
        yield keyframes
        with self._lock:
            ingest_bytes_snap = sorted(self._ingest_bytes.items())
            version_rollup: dict[int, int] = {1: 0, 2: 0}
            for s in self._reports.values():
                version_rollup[s.wire_version] = \
                    version_rollup.get(s.wire_version, 0) + 1
        ingest_bytes = CounterMetricFamily(
            "kepler_fleet_ingest_bytes_total",
            "Report payload bytes ingested, by wire version (v2 delta "
            "steady state runs far below v1's JSON-framed bytes)",
            labels=["version"])
        for version, count in ingest_bytes_snap:
            ingest_bytes.add_metric([str(version)], count)
        yield ingest_bytes
        wire_version = GaugeMetricFamily(
            "kepler_fleet_wire_version",
            "Live nodes by the wire version of their last stored "
            "report (the v1→v2 fleet-rollout progress rollup)",
            labels=["version"])
        for version, count in sorted(version_rollup.items()):
            wire_version.add_metric([str(version)], count)
        yield wire_version
        ctrl = self._admission
        shed = CounterMetricFamily(
            "kepler_fleet_reports_shed_total",
            "Reports shed by ingest admission control (429 + "
            "Retry-After before decode), by budget signal — loss-free: "
            "shed records stay spooled on the agent and replay later",
            labels=["reason"])
        for reason, count in sorted((ctrl.shed_by_reason() if ctrl
                                     else {}).items()):
            shed.add_metric([reason], count)
        yield shed
        inflight = GaugeMetricFamily(
            "kepler_fleet_ingest_inflight",
            "Admitted ingest requests currently being decoded/merged "
            "(admission sheds at a load-derived multiple of "
            "aggregator.admissionMaxInflight; 0 with admission off)")
        inflight.add_metric([], ctrl.inflight() if ctrl else 0)
        yield inflight
        ingest_lat = GaugeMetricFamily(
            "kepler_fleet_ingest_latency_seconds",
            "EWMA of per-record ingest service time — the admission "
            "controller's latency-budget signal (decays while shedding "
            "so recovery probes always resume; 0 with admission off)")
        ingest_lat.add_metric([], ctrl.latency_ewma() if ctrl else 0.0)
        yield ingest_lat
        ring = self._ring
        ring_epoch = GaugeMetricFamily(
            "kepler_fleet_ring_epoch",
            "Ingest ring membership epoch (monotonic, bumped per "
            "membership change; 0 = ring disabled / single-replica)")
        ring_epoch.add_metric([], ring.epoch if ring is not None else 0)
        yield ring_epoch
        ownership = GaugeMetricFamily(
            "kepler_fleet_ring_ownership_ratio",
            "Share of the consistent-hash space this replica owns "
            "(1.0 = single replica or ring disabled)")
        ownership.add_metric(
            [], ring.ownership_ratio(self._self_peer)
            if ring is not None else 1.0)
        yield ownership
        ring_peers = GaugeMetricFamily(
            "kepler_fleet_ring_peers",
            "Replicas in the current ingest-ring membership (0 = ring "
            "disabled) — the elastic fleet's replica count")
        ring_peers.add_metric([], len(ring) if ring is not None else 0)
        yield ring_peers
        with self._lock:
            rejected_snap = sorted(self._membership_rejected.items())
            applied_snap = sorted(self._membership_applied.items())
        mem_rejected = CounterMetricFamily(
            "kepler_fleet_membership_rejected_total",
            "Membership operations rejected, by structured reason "
            "(equal_epoch_conflict is the split-brain detector firing)",
            labels=["reason"])
        for reason, count in rejected_snap:
            mem_rejected.add_metric([reason], count)
        yield mem_rejected
        mem_applied = CounterMetricFamily(
            "kepler_fleet_membership_applied_total",
            "Membership changes applied, by source (operator | "
            "succession | wire | join | leave | autoscale)",
            labels=["source"])
        for source, count in applied_snap:
            mem_applied.add_metric([source], count)
        yield mem_applied
        with self._lock:
            awaiting_now = self._awaiting_membership
            decision_now = self._autoscale_last
            scale_snap = sorted(self._autoscale_decisions.items())
        mem_awaiting = GaugeMetricFamily(
            "kepler_fleet_membership_awaiting_state",
            "1 while this replica is degraded awaiting a membership "
            "(a peer died and it is not the succession issuer, or "
            "takeover is disabled)")
        mem_awaiting.add_metric([], 1 if awaiting_now else 0)
        yield mem_awaiting
        if self._autoscale is not None:
            rec = GaugeMetricFamily(
                "kepler_fleet_autoscale_recommended_replicas",
                "The autoscale policy's current replica recommendation "
                "(enacted only with aggregator.membership.autoApply)")
            rec.add_metric([], decision_now.replicas
                           if decision_now is not None
                           else (len(ring) if ring is not None else 0))
            yield rec
            scale_dec = CounterMetricFamily(
                "kepler_fleet_autoscale_decisions_total",
                "Autoscale observations by decision direction",
                labels=["direction"])
            for direction, count in scale_snap:
                scale_dec.add_metric([direction], count)
            yield scale_dec
        now = self._clock()
        with self._lock:
            lost_by_node = dict(self._lost_by_node)
            delivery_snap = [
                (path, h.cumulative(), h.sum)
                for path, h in sorted(self._delivery_hist.items())]
            node_states = self._scoreboard.states(now, self._stale_after)
        from prometheus_client.core import HistogramMetricFamily
        delivery = HistogramMetricFamily(
            "kepler_fleet_delivery_latency_seconds",
            "End-to-end window delivery latency, agent emit → aggregator "
            "merge (fresh sends from emitted_at; spool replays from the "
            "original appended_at)",
            labels=["path"])
        for path, buckets, total_sum in delivery_snap:
            delivery.add_metric([path], buckets=buckets,
                                sum_value=total_sum)
        yield delivery
        lost = CounterMetricFamily(
            "kepler_fleet_windows_lost_total",
            "Windows that never arrived (seq gaps), by reporting node",
            labels=["node_name"])
        for node, count in lost_by_node.items():
            lost.add_metric([node], count)
        yield lost
        degraded = GaugeMetricFamily(
            "kepler_fleet_degraded_nodes",
            "Nodes whose reports were quarantined within the decay window")
        degraded.add_metric([], len(self.degraded_nodes()))
        yield degraded
        node_state = GaugeMetricFamily(
            "kepler_fleet_node_state",
            "Scoreboard state per node (0 healthy, 1 stale, 2 lossy, "
            "3 anomalous, 4 quarantined); cardinality bounded by the "
            "scoreboard LRU cap",
            labels=["node_name"])
        state_rollup = {name: 0 for name in STATE_NAMES}
        for node, code in node_states.items():
            node_state.add_metric([node], code)
            state_rollup[STATE_NAMES[code]] += 1
        yield node_state
        scoreboard_nodes = GaugeMetricFamily(
            "kepler_fleet_scoreboard_nodes",
            "Scoreboard rollup: nodes currently in each health state",
            labels=["state"])
        for name in STATE_NAMES:
            scoreboard_nodes.add_metric([name], state_rollup[name])
        yield scoreboard_nodes
        yield from self.windows.collect_nodes()
