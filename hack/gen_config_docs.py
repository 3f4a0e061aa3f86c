#!/usr/bin/env python3
"""Generate ``docs/user/configuration.md`` from the live config schema.

The reference ships a hand-written option catalog
(``docs/user/configuration.md`` upstream); here the catalog is GENERATED
the same way ``hack/gen_metric_docs.py`` generates the metrics doc: walk
the ``Config`` dataclass tree for every key, default, and type; pull the
flag spellings out of the real argparse registration; and render the
user-facing reference. Teeth:

  * every config leaf MUST have a description below — adding a field
    without documenting it fails the generator (and the freshness test);
  * every registered CLI flag must be mentioned — a flag the doc doesn't
    know about fails the generator.

Usage:  python hack/gen_config_docs.py [--check]
  --check   exit 1 if docs/user/configuration.md is stale (CI mode).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kepler_tpu.config.config import (  # noqa: E402
    _CANONICAL_YAML_KEYS,
    default_config,
    register_flags,
)
from kepler_tpu.config.level import Level  # noqa: E402

OUT_PATH = os.path.join(REPO, "docs", "user", "configuration.md")

# one description per leaf (dotted snake_case path). The generator fails
# on any undocumented field, so this dict can never silently lag the
# schema.
DESCRIPTIONS = {
    "log.level": "Log verbosity: `debug`, `info`, `warn`, `error`.",
    "log.format": "Log output format: `text` or `json`.",
    "host.sysfs": "Sysfs mount point (RAPL zones are discovered under "
                  "`<sysfs>/class/powercap`).",
    "host.procfs": "Procfs mount point (process scan, `/proc/stat` usage "
                   "ratio, cpuinfo).",
    "monitor.interval": "Refresh interval for the attribution loop "
                        "(Go-style duration; reference default 5s).",
    "monitor.staleness": "Snapshot freshness window: a scrape older than "
                         "this triggers a refresh; two scrapes inside it "
                         "see identical data (HA Prometheus pairs).",
    "monitor.max_terminated": "Terminated workloads kept for export, "
                              "top-N by primary-zone energy; 0 disables "
                              "tracking, negative is unbounded.",
    "monitor.min_terminated_energy_threshold":
        "Joules a terminated workload must have consumed to be tracked.",
    "monitor.stall_after": "Watchdog threshold: a refresh loop silent "
                           "longer than this is flagged stalled and the "
                           "snapshot marked stale on `/healthz` "
                           "(`0` = auto, 3 × `monitor.interval`).",
    "monitor.state_path": "Counter-state file (atomic-rename JSON): the "
                          "last raw RAPL/TPU readings survive a restart "
                          "so the first window attributes the energy "
                          "consumed across it instead of reseeding "
                          "(empty disables).",
    "monitor.state_max_age": "Freshness bound on the restored counter "
                             "state: an older state file is ignored with "
                             "a warning (a stale baseline would "
                             "misattribute long-dead energy; `0` = no "
                             "bound).",
    "rapl.zones": "Zone-name filter (e.g. `[package, dram]`); empty "
                  "means every discovered zone.",
    "msr.enabled": "Opt-in MSR fallback: read RAPL counters from "
                   "`/dev/cpu/*/msr` when powercap is unavailable. "
                   "SECURITY: MSR reads enable PLATYPUS-class side "
                   "channels (CVE-2020-8694/95) — deliberately YAML-only, "
                   "no CLI flag.",
    "msr.force": "Use the MSR meter even when powercap works (testing "
                 "only).",
    "msr.device_path": "MSR device tree (mounted as `host/dev/cpu` in "
                       "containers).",
    "exporter.stdout.enabled": "Periodic node-power table on stdout "
                               "(logs move to stderr).",
    "exporter.prometheus.enabled": "Serve `/metrics` on the API server.",
    "exporter.prometheus.debug_collectors":
        "Extra runtime collectors (`go` = python runtime analog of the "
        "reference's Go collector set).",
    "exporter.prometheus.metrics_level":
        "Bitmask of exported families: any of `node`, `process`, "
        "`container`, `vm`, `pod` (cumulative `--metrics` flag).",
    "web.config_file": "exporter-toolkit-style web config (TLS, basic "
                       "auth) applied to every listener.",
    "web.listen_addresses": "API server listen addresses (repeatable "
                            "`--web.listen-address`).",
    "web.max_connections": "Concurrent-connection cap per listener: an "
                           "accept over the cap is answered `503 + "
                           "Connection: close` immediately, with NO "
                           "handler thread spawned — a connection "
                           "storm can't grow threads without bound "
                           "(`0` = unbounded).",
    "debug.pprof.enabled": "Mount the pprof-style debug service "
                           "(`/debug/pprof/`: stacks, profile, JAX "
                           "trace).",
    "kube.enabled": "Enable the pod informer (node-filtered LIST+WATCH) "
                    "so containers resolve to pods.",
    "kube.config": "Kubeconfig path; empty uses in-cluster service "
                   "account.",
    "kube.node_name": "This node's name (the informer watch filters "
                      "`spec.nodeName`; also the `node_name` metric "
                      "label).",
    "tpu.platform": "JAX platform, pinned before the backend starts: "
                    "`tpu` refuses to start without a TPU, `cpu` pins "
                    "the CPU, `auto` is JAX's choice in the aggregator "
                    "(logged with `device_kind`) and `cpu` in the node "
                    "agent — one process per chip, and the agent does "
                    "not own it.",
    "tpu.workload_bucket": "Workload-axis padding bucket — ragged "
                           "workload counts round up to a multiple so "
                           "the jit cache sees O(buckets) shapes.",
    "tpu.node_bucket": "Node-axis padding bucket for the fleet batch "
                       "(rounded up to the mesh size).",
    "tpu.mesh_shape": "Device mesh shape for the aggregator program "
                      "(empty = all visible devices, 1-D).",
    "tpu.mesh_axes": "Mesh axis names (the node axis shards the fleet).",
    "tpu.fleet_backend": "Attribution contraction backend: `einsum` "
                         "(XLA-fused) or `pallas` (hand-written Mosaic "
                         "kernel).",
    "tpu.compilation_cache_dir": "Persistent XLA compilation cache "
                                 "directory (empty = "
                                 "`<checkout>/.jax_cache`; the "
                                 "`JAX_COMPILATION_CACHE_DIR` "
                                 "environment variable wins over "
                                 "both): bucket-crossing and restart "
                                 "compiles become disk hits.",
    "aggregator.enabled": "Run the cluster-aggregator role (ingest node "
                          "reports, batched fleet attribution).",
    "aggregator.listen_address": "Aggregator API listen address.",
    "aggregator.endpoint": "Agent role: aggregator base URL to POST "
                           "window reports to (empty disables the "
                           "agent).",
    "aggregator.tls_skip_verify": "Agent: skip TLS certificate "
                                  "verification toward the aggregator.",
    "aggregator.interval": "Fleet attribution cadence (duration).",
    "aggregator.stale_after": "A node whose newest report is older than "
                              "this falls out of the batch (duration).",
    "aggregator.model": "Estimator family serving non-RAPL nodes: "
                        "`linear`, `mlp`, `moe`, `deep`, `temporal` "
                        "(empty = ratio-only).",
    "aggregator.params_path": "Trained estimator params (`.npz` from "
                              "`kepler-tpu-train`); empty serves "
                              "untrained initialization with a warning.",
    "aggregator.accuracy_mode": "Serve estimators at f32/highest matmul "
                                "precision (the configuration validated "
                                "to ≤0.5% error) instead of bf16 "
                                "throughput mode.",
    "aggregator.history_window": "Temporal model: feature-history ticks "
                                 "kept per workload.",
    "aggregator.training_dump_dir": "Capture RAPL nodes' windows + ratio "
                                    "watts as training files for "
                                    "`kepler-tpu-train` (empty "
                                    "disables).",
    "aggregator.training_dump_max_files": "Training-dump retention: "
                                          "oldest files beyond this are "
                                          "pruned.",
    "aggregator.node_mode": "Agent: report as a `ratio` (RAPL ground "
                            "truth) or `model` (estimator-served) node.",
    "aggregator.backoff_initial": "Agent: initial send-retry backoff "
                                  "(exponential, jittered).",
    "aggregator.backoff_max": "Agent: send-retry backoff ceiling.",
    "aggregator.breaker_threshold": "Agent: consecutive send failures "
                                    "that open the circuit breaker "
                                    "(sends are shed while open).",
    "aggregator.breaker_cooldown": "Agent: breaker cooldown before a "
                                   "half-open probe (doubles per failed "
                                   "probe, capped).",
    "aggregator.flush_timeout": "Agent: bound on the best-effort flush "
                                "of queued reports during shutdown "
                                "(a clean drain delivers its final "
                                "window).",
    "aggregator.skew_tolerance": "Aggregator: quarantine reports whose "
                                 "sender clock is skewed beyond this "
                                 "(`0` disables the check).",
    "aggregator.degraded_ttl": "Aggregator: how long a node stays marked "
                               "degraded on `/healthz` after its last "
                               "quarantined report.",
    "aggregator.dedup_window": "Aggregator: per-node `(run, seq)` dedup "
                               "window — redelivered reports (spool "
                               "replay, retries) are absorbed "
                               "idempotently; seq jumps beyond it count "
                               "as `kepler_fleet_windows_lost_total`.",
    "aggregator.pipeline_depth": "Aggregator: the bound on fleet windows "
                                 "in flight (dispatched, not yet "
                                 "published). `1` = serial "
                                 "assemble→dispatch→fetch; `2` (default) "
                                 "overlaps window N's program, fetch and "
                                 "scatter with window N+1's "
                                 "assembly+dispatch. A window is "
                                 "published as soon as its program is "
                                 "done, whatever the depth: a result is "
                                 "as old as its own assembly and "
                                 "program, not an interval; the loop "
                                 "waits only where it would hold more "
                                 "than this many. Shutdown drains "
                                 "in-flight windows deterministically.",
    "aggregator.fused_window_k": "Aggregator: intervals batched into "
                                 "one fused device scan at rung 0's top "
                                 "tier. `1` (default) = unfused "
                                 "per-window dispatch; `K>1` stages "
                                 "delta rows host-side and pays the "
                                 "host↔device sync once per K windows "
                                 "(one `lax.scan` dispatch + one "
                                 "batched fetch) — results are at most "
                                 "`fusedWindowK−1` intervals stale. See "
                                 "observability.md \"Fused window "
                                 "loop\".",
    "aggregator.bucket_shrink_after": "Aggregator: consecutive windows "
                                      "at under half bucket occupancy "
                                      "before a padded batch bucket "
                                      "shrinks one geometric step "
                                      "(growth is immediate; hysteresis "
                                      "prevents recompile thrash at a "
                                      "bucket edge).",
    "aggregator.fallback_enabled": "Aggregator: demote the window's "
                                   "device leg down the degradation "
                                   "ladder (packed pipelined → packed "
                                   "serial → einsum-f32 serial → "
                                   "pure-NumPy host) on any device "
                                   "failure instead of crashing the "
                                   "aggregation loop.",
    "aggregator.repromote_after": "Aggregator: consecutive clean windows "
                                  "at a demoted ladder rung before the "
                                  "rung above is retried (hysteresis, "
                                  "like the breaker's half-open probe).",
    "aggregator.dispatch_timeout": "Aggregator: stall watchdog on the "
                                   "window fetch — a dispatch that "
                                   "hasn't produced output within this "
                                   "bound demotes the ladder instead of "
                                   "wedging the loop (`0` disables).",
    "aggregator.mesh_shape": "Device mesh shape for the fleet window "
                             "path (`[]` = every device on a 1-D node "
                             "axis). With > 1 device on a 1-D node "
                             "mesh the packed window runs SHARDED: "
                             "per-shard resident rings, per-shard "
                             "delta H2D, sticky node→shard assignment.",
    "aggregator.mesh_axes": "Mesh axis names for the fleet window path; "
                            "must lead with `node` (the axis the fleet "
                            "batch shards over).",
    "aggregator.scoreboard_cap": "Fleet scoreboard LRU cap: per-node "
                                 "health rows kept (bounds memory AND "
                                 "`kepler_fleet_node_state` "
                                 "cardinality; least-recently-updated "
                                 "node evicted beyond it).",
    "aggregator.anomaly_z": "Rolling z-score threshold flagging a "
                            "node's self-reported power as anomalous "
                            "on the scoreboard (`0` disables the "
                            "flag).",
    "aggregator.peers": "HA ingest ring: every replica's dialable "
                        "endpoint (the SAME list on every replica and "
                        "agent). Each replica accepts only the nodes "
                        "the consistent-hash ring assigns it and "
                        "answers the rest with a `421 + owner + epoch` "
                        "redirect agents follow. Empty = "
                        "single-replica ingest.",
    "aggregator.self_peer": "Which `aggregator.peers` entry THIS "
                            "replica is (replica role only; agents "
                            "leave it empty).",
    "aggregator.ring_epoch": "Ingest-ring membership epoch — bump it "
                             "when rolling out a changed peers list so "
                             "agents re-resolve ownership (monotonic, "
                             ">= 1).",
    "aggregator.ring_vnodes": "Virtual nodes per ring peer: ownership "
                              "granularity (higher = smoother "
                              "distribution, slower ring build).",
    "aggregator.admission_enabled": "Ingest admission control: shed "
                                    "with `429 + Retry-After` BEFORE "
                                    "decode work when the inflight or "
                                    "latency budget is blown — "
                                    "priority-aware (replay backlogs "
                                    "first, live RAPL ground truth "
                                    "last). Loss-free: shed records "
                                    "stay spooled on the agent and "
                                    "replay later.",
    "aggregator.admission_max_inflight": "Inflight-ingest budget: "
                                         "admitted requests being "
                                         "decoded/merged concurrently "
                                         "before the shed ladder "
                                         "engages.",
    "aggregator.admission_latency_budget": "Per-record ingest service-"
                                           "time budget (EWMA) the "
                                           "shed ladder is scaled "
                                           "against (`0` disables the "
                                           "latency signal).",
    "aggregator.admission_retry_after": "Base `Retry-After` answered "
                                        "on a shed; multiplied by the "
                                        "measured load and jittered "
                                        "±50% so a throttled herd "
                                        "doesn't re-arrive in phase.",
    "aggregator.admission_retry_after_max": "Clamp on the shed "
                                            "`Retry-After` — the "
                                            "longest an agent is ever "
                                            "asked to stay away.",
    "aggregator.multihost.enabled":
        "Multi-host SPMD fleet window: join a `jax.distributed` cluster "
        "and run rung 0 over every host's devices — host-local donated "
        "rings and delta H2D, ONE SPMD dispatch, owned-rows publish "
        "fetch, and (with `aggregator.peers` set) ingest ownership "
        "derived from the mesh shard map so each replica ingests "
        "exactly the agents whose rows live on its local devices.",
    "aggregator.multihost.coordinator":
        "`jax.distributed` coordinator address (empty = "
        "`JAX_COORDINATOR_ADDRESS`, the TPU pod runtime convention).",
    "aggregator.multihost.num_processes":
        "Process count of the multi-host job (`-1` = "
        "`JAX_NUM_PROCESSES`). With `aggregator.peers` set, the peer "
        "list must carry one endpoint per process in process-index "
        "order.",
    "aggregator.multihost.process_id":
        "This process's id in the multi-host job (`-1` = "
        "`JAX_PROCESS_ID`).",
    "aggregator.multihost.init_timeout":
        "Bound on the coordinator join (duration; `0` = jax's default "
        "deadline). An unreachable coordinator surfaces as the distinct "
        "`coordinator_unreachable` failure reason in the log and the "
        "`fleet-window` health probe — never a generic decline.",
    "aggregator.multihost.takeover":
        "On a mesh demotion (\"mesh minus one host\"), heal the ring "
        "by DETERMINISTIC SUCCESSION at any mesh size: every survivor "
        "probes the peer set and computes the same entitled issuer "
        "(the incumbent lease holder while it survives, else the "
        "lowest surviving peer), so exactly ONE survivor bumps the "
        "epoch and broadcasts the survivor membership — displaced "
        "agents follow 421s and replay their spool tails. Disabled, "
        "survivors hold position \"degraded, awaiting membership\" "
        "until an operator `apply_membership`.",
    "aggregator.membership.auto_apply":
        "Let the lease holder ENACT membership changes the autoscale "
        "policy recommends (promote a standby, retire the "
        "highest-sorting peer). Off (the default), recommendations "
        "are surfaced only — logs, `/debug/ring`, and "
        "`kepler_fleet_autoscale_recommended_replicas` — and "
        "operator behavior is byte-for-byte unchanged.",
    "aggregator.membership.autoscale_enabled":
        "Feed each aggregation window's recorded signals (admission "
        "load, shed deltas, ingest-latency EWMA, scoreboard states) "
        "into the hysteresis autoscale policy. Pure function of the "
        "signal trace: replaying the same metrics reproduces the "
        "same decisions.",
    "aggregator.membership.scale_up_load":
        "Admission-load threshold at or above which a window counts "
        "toward the scale-up streak (any shed traffic in the window "
        "also counts).",
    "aggregator.membership.scale_down_load":
        "Admission-load threshold at or below which a window counts "
        "toward the scale-down streak (only with zero shed and zero "
        "flagged nodes). Must sit below `scaleUpLoad`; the gap is the "
        "hysteresis dead band, where both streaks are preserved.",
    "aggregator.membership.up_windows":
        "Consecutive overloaded windows required before a scale-up "
        "fires (the streak resets after firing).",
    "aggregator.membership.down_windows":
        "Consecutive idle windows required before a scale-down fires "
        "— deliberately slower than scale-up so diurnal troughs "
        "don't flap the fleet.",
    "aggregator.membership.min_replicas":
        "Floor the autoscale policy never recommends below.",
    "aggregator.membership.max_replicas":
        "Ceiling the autoscale policy never recommends above (`0` = "
        "one step above the current replica count).",
    "aggregator.membership.standby_peers":
        "Warm standby replica endpoints (repeatable) the lease holder "
        "may promote into the ring on an enacted scale-up; must not "
        "overlap `aggregator.peers`.",
    "aggregator.membership.probe_timeout":
        "Per-peer bound on the liveness probe (`GET /healthz`) behind "
        "succession and the autoscale live-node count (duration). ANY "
        "HTTP answer proves a listener; only transport failures read "
        "as death.",
    "aggregator.base_row_cache": "Wire-v2 delta-base LRU size: per-"
                                 "node last-keyframe state the delta "
                                 "frames merge against. Eviction "
                                 "costs the node one structured 409 "
                                 "needs-keyframe round-trip (it "
                                 "resends full), never data. Set it to "
                                 "at least the fleet's size: under more "
                                 "round-robin senders than bases every "
                                 "base is evicted before its node "
                                 "reports again, so every delta costs "
                                 "a 409 and a keyframe.",
    "agent.spool.dir": "Crash-safe report spool directory: windows are "
                       "appended (CRC-framed) before any send and only "
                       "acked on 2xx, so crashes/outages replay instead "
                       "of losing data (empty = in-memory ring only).",
    "agent.spool.max_bytes": "Spool byte cap; the oldest segment is "
                             "evicted beyond it and every unacked record "
                             "lost is counted "
                             "(`kepler_fleet_spool_evicted_total`).",
    "agent.spool.max_records": "Spool record cap (same eviction and "
                               "accounting as the byte cap).",
    "agent.spool.segment_bytes": "Spool segment rotation size — the "
                                 "granularity of cap eviction and of "
                                 "acked-data reclamation.",
    "agent.spool.fsync": "Spool durability policy: `batch` (default — "
                         "at most one fsync per `fsyncInterval`, none "
                         "on the per-send path), `always`, or `none`.",
    "agent.spool.fsync_interval": "Minimum spacing between batched spool "
                                  "fsyncs.",
    "agent.drain.batch_max": "Spooled records shipped per `/v1/reports` "
                             "request during recovery replay (`1` = "
                             "the single-record drain; per-record "
                             "status in the response keeps every "
                             "dedup/loss invariant record-grained).",
    "agent.drain.replay_rps": "Token-bucket cap on spool-replay "
                              "records/second, so a rejoining agent "
                              "slews its backlog in instead of dumping "
                              "it on a recovering replica (`0` = "
                              "unpaced).",
    "agent.drain.retry_after_max": "Clamp on any server-sent "
                                   "`Retry-After` the agent honors — "
                                   "an adversarial owner must not be "
                                   "able to park an agent forever.",
    "agent.wire.version": "Report wire format: `2` (default) = binary "
                          "delta-encoded v2 frames (struct-packed "
                          "header, changed workload rows only in "
                          "steady state); `1` pins the legacy "
                          "JSON-headered frames (rollout escape "
                          "hatch).",
    "agent.wire.keyframe_every": "Send a full keyframe every N windows "
                                 "even when a delta would do — bounds "
                                 "the state a fresh owner must request "
                                 "(409 needs-keyframe) after a "
                                 "hand-off.",
    "agent.wire.degraded_ttl": "How long a replica that answered "
                               "415/400 to v2 bytes is remembered as "
                               "v1-only before the agent re-probes v2 "
                               "(the wire-version analog of the batch "
                               "404/405 downgrade).",
    "service.restart_max": "Supervised restarts per crashing service "
                           "before the group fails (`0` = reference "
                           "semantics: first crash ends the group).",
    "service.restart_backoff_initial": "Initial supervised-restart "
                                       "backoff (exponential, jittered).",
    "service.restart_backoff_max": "Supervised-restart backoff ceiling.",
    "fault.enabled": "Arm the fault-injection plan at startup (YAML-only "
                     "chaos harness; see docs/developer/resilience.md).",
    "fault.seed": "Fault-plan RNG seed: the same seed replays the same "
                  "fault sequence.",
    "fault.specs": "Fault specs: mappings with a `site` "
                   "(e.g. `net.refuse`, `device.read_error`) plus "
                   "optional probability/count/skip/start/duration/arg.",
    "telemetry.enabled": "Self-telemetry plane: span tracing of the "
                         "monitor/exporter/fleet hot paths, "
                         "`kepler_self_*` metrics, and `/debug/traces`. "
                         "Disabled spans cost one global read per call "
                         "(see docs/developer/observability.md).",
    "telemetry.ring_size": "Complete cycle traces kept for "
                           "`/debug/traces`, per cycle name (newest "
                           "wins; per-name rings keep high-rate cycles "
                           "from evicting rare ones).",
    "telemetry.stage_buckets": "`kepler_self_stage_duration_seconds` "
                               "histogram bucket bounds in seconds "
                               "(empty = built-in defaults, 0.5ms–10s).",
    "telemetry.delivery_buckets": "`kepler_fleet_delivery_latency_"
                                  "seconds` histogram bucket bounds in "
                                  "seconds (empty = built-in defaults, "
                                  "10ms–6h — the tail reaches hours "
                                  "because spool replays carry outage "
                                  "durations).",
    "telemetry.journal.enabled": "Fleet black box: the HLC-stamped "
                                 "causal event journal behind "
                                 "`/debug/journal` and `/debug/bundle`, "
                                 "plus the `X-Kepler-HLC` clock "
                                 "piggyback on fleet wire exchanges. "
                                 "Disabled emission costs one global "
                                 "read per call (see "
                                 "docs/developer/observability.md).",
    "telemetry.journal.ring_size": "Journal events kept in memory "
                                   "(newest win) — the `/debug/journal` "
                                   "page and the bundle's journal "
                                   "section.",
    "telemetry.journal.dir": "Durable journal spool directory (empty = "
                             "ring only). CRC-framed `.kepj` files, one "
                             "per node, readable by "
                             "`python -m kepler_tpu.blackbox` after a "
                             "crash.",
    "telemetry.journal.max_bytes": "Durable spool cap per file; at the "
                                   "cap the file rotates once to "
                                   "`.kepj.1` (bounded disk, newest "
                                   "events always on disk).",
    "aggregator.hlc_max_drift": "HLC clamp: an inbound clock stamp may "
                                "advance this replica's clock at most "
                                "this far past local wall time. Clamped "
                                "stamps count in "
                                "`kepler_fleet_hlc_clamped_total`.",
    "dev.fake_cpu_meter.enabled": "Dev-only synthetic meter (YAML-only, "
                                  "never a flag — reference "
                                  "config.go:104,189).",
    "dev.fake_cpu_meter.zones": "Zone names the fake meter exposes "
                                "(empty = package/core/dram/uncore).",
}

# dotted path → CLI flag (only paths that HAVE flags; YAML-only settings
# simply aren't listed). Checked against the real parser below.
FLAG_OF = {
    "log.level": "--log.level",
    "log.format": "--log.format",
    "host.sysfs": "--host.sysfs",
    "host.procfs": "--host.procfs",
    "monitor.interval": "--monitor.interval",
    "monitor.max_terminated": "--monitor.max-terminated",
    "monitor.state_path": "--monitor.state-path",
    "debug.pprof.enabled": "--debug.pprof / --no-debug.pprof",
    "web.config_file": "--web.config-file",
    "web.listen_addresses": "--web.listen-address (repeatable)",
    "web.max_connections": "--web.max-connections",
    "exporter.stdout.enabled": "--exporter.stdout / --no-exporter.stdout",
    "exporter.prometheus.enabled":
        "--exporter.prometheus / --no-exporter.prometheus",
    "exporter.prometheus.metrics_level": "--metrics (cumulative)",
    "kube.enabled": "--kube.enable / --no-kube.enable",
    "kube.config": "--kube.config",
    "kube.node_name": "--kube.node-name",
    "aggregator.enabled": "--aggregator.enable / --no-aggregator.enable",
    "aggregator.listen_address": "--aggregator.listen-address",
    "aggregator.endpoint": "--aggregator.endpoint",
    "aggregator.tls_skip_verify": "--aggregator.tls-skip-verify",
    "aggregator.model": "--aggregator.model",
    "aggregator.params_path": "--aggregator.params-path",
    "aggregator.node_mode": "--aggregator.node-mode",
    "aggregator.accuracy_mode": "--aggregator.accuracy-mode",
    "aggregator.history_window": "--aggregator.history-window",
    "aggregator.training_dump_dir": "--aggregator.training-dump-dir",
    "aggregator.training_dump_max_files":
        "--aggregator.training-dump-max-files",
    "aggregator.dedup_window": "--aggregator.dedup-window",
    "aggregator.pipeline_depth": "--aggregator.pipeline-depth",
    "aggregator.fused_window_k": "--aggregator.fused-window-k",
    "aggregator.bucket_shrink_after": "--aggregator.bucket-shrink-after",
    "aggregator.fallback_enabled":
        "--aggregator.fallback-enabled / --no-aggregator.fallback-enabled",
    "aggregator.repromote_after": "--aggregator.repromote-after",
    "aggregator.dispatch_timeout": "--aggregator.dispatch-timeout",
    "aggregator.scoreboard_cap": "--aggregator.scoreboard-cap",
    "aggregator.anomaly_z": "--aggregator.anomaly-z",
    "aggregator.peers": "--aggregator.peers (repeatable)",
    "aggregator.self_peer": "--aggregator.self-peer",
    "aggregator.ring_epoch": "--aggregator.ring-epoch",
    "aggregator.ring_vnodes": "--aggregator.ring-vnodes",
    "aggregator.admission_enabled":
        "--aggregator.admission-enabled / "
        "--no-aggregator.admission-enabled",
    "agent.spool.dir": "--agent.spool-dir",
    "agent.wire.version": "--agent.wire-version",
    "aggregator.base_row_cache": "--aggregator.base-row-cache",
    "aggregator.multihost.enabled":
        "--aggregator.multihost.enabled / "
        "--no-aggregator.multihost.enabled",
    "aggregator.multihost.coordinator": "--aggregator.multihost.coordinator",
    "aggregator.multihost.num_processes":
        "--aggregator.multihost.num-processes",
    "aggregator.multihost.process_id": "--aggregator.multihost.process-id",
    "aggregator.multihost.init_timeout":
        "--aggregator.multihost.init-timeout",
    "aggregator.multihost.takeover":
        "--aggregator.multihost.takeover / "
        "--no-aggregator.multihost.takeover",
    "aggregator.membership.auto_apply":
        "--aggregator.membership.auto-apply / "
        "--no-aggregator.membership.auto-apply",
    "aggregator.membership.autoscale_enabled":
        "--aggregator.membership.autoscale-enabled / "
        "--no-aggregator.membership.autoscale-enabled",
    "aggregator.membership.scale_up_load":
        "--aggregator.membership.scale-up-load",
    "aggregator.membership.scale_down_load":
        "--aggregator.membership.scale-down-load",
    "aggregator.membership.up_windows":
        "--aggregator.membership.up-windows",
    "aggregator.membership.down_windows":
        "--aggregator.membership.down-windows",
    "aggregator.membership.min_replicas":
        "--aggregator.membership.min-replicas",
    "aggregator.membership.max_replicas":
        "--aggregator.membership.max-replicas",
    "aggregator.membership.standby_peers":
        "--aggregator.membership.standby-peers (repeatable)",
    "aggregator.membership.probe_timeout":
        "--aggregator.membership.probe-timeout",
    "tpu.platform": "--tpu.platform",
    "tpu.fleet_backend": "--tpu.fleet-backend",
    "telemetry.enabled": "--telemetry.enable / --no-telemetry.enable",
    "telemetry.journal.enabled":
        "--telemetry.journal.enable / --no-telemetry.journal.enable",
}

_SNAKE_TO_CAMEL = {v: k for k, v in _CANONICAL_YAML_KEYS.items()}

_DURATION_PATHS = {"monitor.interval", "monitor.staleness",
                   "monitor.stall_after", "monitor.state_max_age",
                   "agent.spool.fsync_interval",
                   "aggregator.interval", "aggregator.stale_after",
                   "aggregator.backoff_initial", "aggregator.backoff_max",
                   "aggregator.breaker_cooldown", "aggregator.flush_timeout",
                   "aggregator.skew_tolerance", "aggregator.degraded_ttl",
                   "aggregator.dispatch_timeout",
                   "aggregator.admission_latency_budget",
                   "aggregator.admission_retry_after",
                   "aggregator.admission_retry_after_max",
                   "agent.drain.retry_after_max",
                   "agent.wire.degraded_ttl",
                   "aggregator.membership.probe_timeout",
                   "aggregator.hlc_max_drift",
                   "service.restart_backoff_initial",
                   "service.restart_backoff_max"}


def yaml_path(path: str) -> str:
    parts = [_SNAKE_TO_CAMEL.get(p, p) for p in path.split(".")]
    return ".".join(parts)


def fmt_default(path: str, value) -> str:
    if path in _DURATION_PATHS:
        secs = float(value)
        return f"`{secs:g}s`"
    if isinstance(value, Level):
        return "`[node, process, container, vm, pod]`"
    if isinstance(value, bool):
        return f"`{str(value).lower()}`"
    if isinstance(value, str):
        return f"`{value!r}`" if value == "" else f"`{value}`"
    return f"`{value}`"


def leaves(obj, prefix=""):
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if dataclasses.is_dataclass(v):
            yield from leaves(v, f"{prefix}{f.name}.")
        else:
            yield f"{prefix}{f.name}", v


def registered_flags() -> set[str]:
    parser = argparse.ArgumentParser(add_help=False)
    register_flags(parser)
    out = set()
    for action in parser._actions:
        for opt in action.option_strings:
            out.add(opt)
    return out


def render() -> str:
    cfg = default_config()
    rows = list(leaves(cfg))
    missing = [p for p, _ in rows if p not in DESCRIPTIONS]
    if missing:
        raise SystemExit(
            f"gen_config_docs: undocumented config fields {missing} — add "
            "DESCRIPTIONS entries")
    stale = [p for p in DESCRIPTIONS if p not in {p for p, _ in rows}]
    if stale:
        raise SystemExit(
            f"gen_config_docs: DESCRIPTIONS has stale paths {stale}")
    doc_flags = " ".join(FLAG_OF.values())
    unmentioned = [
        f for f in registered_flags()
        if f not in doc_flags and not f.startswith("--no-")
        and f not in ("--config.file",)
    ]
    if unmentioned:
        raise SystemExit(
            f"gen_config_docs: flags missing from FLAG_OF: {unmentioned}")

    lines = [
        "# Configuration",
        "",
        "Every option, generated from the live `Config` schema by",
        "`hack/gen_config_docs.py` — do not edit by hand. Regenerate with",
        "`python hack/gen_config_docs.py` (CI checks freshness with",
        "`--check`).",
        "",
        "Precedence (reference `config.go:285-395`): built-in defaults <",
        "YAML file (`--config.file`) < explicitly-passed CLI flags. YAML",
        "keys accept camelCase (`maxTerminated`) and kebab-case",
        "(`max-terminated`) spellings interchangeably. Durations accept",
        "Go syntax (`5s`, `500ms`, `1m30s`).",
        "",
        "Settings without a flag are YAML-only — either dev-only",
        "(`dev.*`) or security-sensitive (`msr.*`), per the reference's",
        "stance of not exposing those on the command line.",
        "",
        "| Key (YAML path) | Default | Flag | Description |",
        "|---|---|---|---|",
    ]
    for path, value in rows:
        flag = FLAG_OF.get(path, "—")
        if flag != "—":
            flag = f"`{flag}`"
        desc = DESCRIPTIONS[path].replace("\n", " ")
        lines.append(
            f"| `{yaml_path(path)}` | {fmt_default(path, value)} | "
            f"{flag} | {desc} |")
    lines += [
        "",
        "## Example",
        "",
        "```yaml",
        "log: {level: info}",
        "monitor: {interval: 5s, staleness: 500ms}",
        "exporter:",
        "  stdout: {enabled: false}",
        "  prometheus:",
        "    enabled: true",
        "    metricsLevel: [node, process, container, vm, pod]",
        "web: {listenAddresses: [':28282']}",
        "kube: {enabled: true, node-name: worker-1}",
        "# agent half of the fleet plane:",
        "aggregator: {endpoint: 'https://aggregator:28283'}",
        "```",
        "",
        "See `docs/user/installation.md` for deployment-specific",
        "configuration (DaemonSet mounts, Helm values, compose).",
    ]
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    text = render()
    if "--check" in sys.argv:
        try:
            with open(OUT_PATH, encoding="utf-8") as f:
                current = f.read()
        except OSError:
            current = ""
        if current != text:
            print(f"{OUT_PATH} is stale; run python hack/gen_config_docs.py",
                  file=sys.stderr)
            return 1
        print(f"{OUT_PATH} is up to date")
        return 0
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"wrote {OUT_PATH} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
