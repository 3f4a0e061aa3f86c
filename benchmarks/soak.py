"""Aggregator ingest soak: ≥1000 simulated agents against a LIVE service.

VERDICT r3 item 4: the aggregator *service* was never measured at the
north-star fleet shape — only the device program. This drives the real
stack end to end: N agent threads POST wire-encoded reports to a real
``APIServer`` socket on the agent cadence while the aggregation loop
runs concurrently, for ``--seconds`` of wall clock. Measured:

  * report POST round-trip p50/p99/max (the ingest SLO — a slow window
    assembly or a lock hold shows up here immediately),
  * zero dropped fresh reports (every in-order POST must 204),
  * attribution windows completed + their host/device leg latencies,
  * RSS growth over the run (bounded-memory check).

Run directly: ``python -m benchmarks.soak --agents 1000 --seconds 60``
→ one JSON line. bench.py merges the fields into BENCH_r{N}.json.

The default gate: ingest p99 < 250 ms (these are 64 KiB POSTs against a
Python ThreadingHTTPServer sharing one host with 1000 sender threads —
the budget is an SLO for the SERVICE, not a micro-benchmark), no
rejected fresh reports, steady-state RSS growth < soak_rss_growth_budget_mib.

RSS accounting (round 6): the baseline is taken AFTER the ramp — all
agent threads started, connections established, the first attribution
window completed. Thread stacks, per-connection handler threads, arena
warm-up, and the first window's jit compile are one-time costs (the
~212 MiB "leak" round 5 measured was almost entirely this plateau,
reported separately as ``soak_rss_ramp_mib``); the GATED number is
growth during steady state, where the bounded-memory claim actually
lives. The aggregator side was audited: the history rings, delivery
histograms, seq trackers, degraded/superseded tables are all capped,
and the packed-resident window path reuses its staging buffers instead
of allocating per window.
"""

from __future__ import annotations

# keplint: monotonic-only — soak durations/ramp deadlines are elapsed
# time; an NTP step mid-soak must not corrupt the gated numbers

import argparse
import contextlib
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable from any cwd


def rss_mib() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def percentile(sorted_vals: list[float], q: float) -> float:
    import math

    if not sorted_vals:
        return float("nan")
    return sorted_vals[min(len(sorted_vals) - 1,
                           math.ceil(q * len(sorted_vals)) - 1)]


def run_soak(n_agents: int = 1000, seconds: float = 60.0,
             interval: float = 5.0, workloads: int = 100,
             model_mode: str | None = "mlp", replicas: int = 1,
             kill_at: float = 0.0, shed: bool = False,
             rebalance_after: float = 0.0, diurnal: bool = False,
             seed: int = 0) -> dict:
    from kepler_tpu.fleet.aggregator import Aggregator
    from kepler_tpu.fleet.journal import EventJournal
    from kepler_tpu.fleet.wire import (encode_delta_v2, encode_report,
                                       encode_report_batch,
                                       encode_report_v2, restamp_transmit)
    from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
    from kepler_tpu.parallel.mesh import make_mesh
    from kepler_tpu.server.http import APIServer
    from kepler_tpu.service.lifecycle import CancelContext

    # multi-replica topology (ISSUE 11): N aggregator replicas sharing
    # the consistent-hash ingest ring; agents follow 421 owner
    # redirects and fail over between replicas. --kill-at shuts one
    # replica down mid-soak and rebalances the survivors (epoch 2) —
    # the gate then requires ZERO windows lost across the hand-off.
    #
    # --shed (ISSUE 12 herd mode): the replicas run ADMISSION CONTROL
    # (429 + Retry-After under load) and the agents keep a local
    # backlog they drain BATCHED through /v1/reports — the soak then
    # measures the overload plane itself: sheds fired, drain requests
    # vs records (batching factor), and the survivors' post-kill
    # ingest p99.
    #
    # --diurnal (ISSUE 16 elastic membership): a 1 → peak → 2 replica
    # schedule UNDER LIVE LOAD driven through the real membership
    # plane — standbys register with the lease holder over
    # ``/v1/membership`` (join) at seconds/3, the holder retires them
    # again (leave) at 2·seconds/3, and displaced agents follow 421s
    # and replay to the new owners. The gate requires ZERO windows
    # lost across every scale event.
    replicas = max(1, int(replicas))
    # every stochastic stream derives from --seed (default 0 keeps the
    # historical runs bit-identical); printed up front so any soak line
    # in a log is replayable
    mode = ("diurnal" if diurnal else "shed" if shed
            else "kill" if kill_at else "steady")
    print(f"# soak seed={seed} mode={mode} agents={n_agents} "
          f"replicas={replicas} interval={interval}", file=sys.stderr)
    admission_kw = dict(
        admission_enabled=True, admission_max_inflight=64,
        admission_latency_budget=0.25, admission_retry_after=0.5,
        admission_retry_after_max=5.0, admission_jitter_seed=seed,
    ) if shed else {}
    servers: list[APIServer] = []
    for _ in range(replicas):
        s = APIServer(listen_addresses=["127.0.0.1:0"])
        s.init()
        servers.append(s)
    peers = [f"{h}:{p}" for (h, p) in (s.addresses[0] for s in servers)]
    aggs: list[Aggregator] = []
    ctxs: list[CancelContext] = []
    replica_threads: list[list[threading.Thread]] = []
    for i, server in enumerate(servers):
        if diurnal:
            # replica 0 starts as a ring of ONE (the lease holder);
            # standbys carry just [holder, self] so request_join has a
            # ring and a first peer to register with
            peer_kw = dict(
                peers=[peers[0]] if i == 0 else [peers[0], peers[i]],
                self_peer=peers[i])
        else:
            peer_kw = dict(peers=peers if replicas > 1 else None,
                           self_peer=peers[i] if replicas > 1 else "")
        agg = Aggregator(server, interval=interval,
                         stale_after=interval * 3,
                         model_mode=model_mode, node_bucket=64,
                         workload_bucket=128, pipeline_depth=2,
                         # the diurnal leg soaks the fused window loop
                         # (ISSUE 20) under live scale events: K=4
                         # amortizes the host sync and the zero-windows-
                         # lost gate below must still hold across every
                         # join/leave (pending-snapshot replay included)
                         fused_window_k=4 if diurnal else 1,
                         # the diurnal gate reconstructs the scale story
                         # from the merged black-box journals; the pure
                         # latency soaks keep the journal at its
                         # disabled-default cost
                         journal=(EventJournal(enabled=True,
                                               node=peers[i])
                                  if diurnal else None),
                         **peer_kw, **admission_kw)
        agg.windows.mesh = make_mesh()
        agg.init()
        ctx = CancelContext()
        replica_threads.append([
            threading.Thread(target=server.run, args=(ctx,), daemon=True),
            threading.Thread(target=agg.run, args=(ctx,), daemon=True)])
        aggs.append(agg)
        ctxs.append(ctx)
    live = {0} if diurnal else set(range(replicas))
    for i in sorted(live):
        for t in replica_threads[i]:
            t.start()
    time.sleep(0.2)
    victim = replicas - 1 if replicas > 1 and kill_at > 0 else -1

    rng = np.random.default_rng(seed)
    zones = ["package", "core", "dram", "uncore"]
    # pre-encode each agent's report ONCE per seq (the arrays change per
    # window in production but the encode cost is the agent's, not the
    # service's — the soak measures the SERVICE)
    latencies: list[list[tuple[float, float]]] = [
        [] for _ in range(n_agents)]
    rejects = np.zeros(n_agents, np.int64)
    errors = np.zeros(n_agents, np.int64)
    redirects = np.zeros(n_agents, np.int64)
    replays = np.zeros(n_agents, np.int64)
    kf_409s = np.zeros(n_agents, np.int64)  # structured needs-keyframe
    throttled = np.zeros(n_agents, np.int64)
    drain_requests = np.zeros(n_agents, np.int64)
    drain_records = np.zeros(n_agents, np.int64)
    drain_batch_peak = np.zeros(n_agents, np.int64)
    kill_mono = [float("inf")]  # monotonic instant the victim died
    stop = threading.Event()

    def agent(idx: int) -> None:
        # per-thread generator: np.random.Generator is NOT thread-safe,
        # and all agents draw at thread start (seed=0 preserves the
        # historical per-agent streams exactly)
        rng_local = np.random.default_rng(seed * 1_000_003 + idx)
        cpu = rng_local.uniform(0.1, 5.0, workloads).astype(np.float32)
        rep = NodeReport(
            node_name=f"soak-{idx:04d}",
            zone_deltas_uj=rng_local.uniform(1e7, 5e8, 4).astype(
                np.float32),
            zone_valid=np.ones(4, bool),
            usage_ratio=0.6,
            cpu_deltas=cpu,
            workload_ids=[f"s{idx}-w{k}" for k in range(workloads)],
            node_cpu_delta=float(cpu.sum()),
            dt_s=interval,
            mode=MODE_MODEL if idx % 2 else MODE_RATIO,
            workload_kinds=np.ones(workloads, np.int8),
        )
        # diurnal starts single-replica: everyone aims at the holder
        t_idx = 0 if diurnal else idx % len(peers)

        def connect():
            h, _, p = peers[t_idx].rpartition(":")
            return http.client.HTTPConnection(h, int(p), timeout=30)

        conn = connect()
        seq = 0
        acked = 0
        epoch = 0
        # de-synchronized start so 1000 agents don't phase-lock
        time.sleep((idx / n_agents) * interval)
        lat = latencies[idx]
        kf_base: bytes | None = None  # last ACKED v2 keyframe bytes
        while not stop.is_set():
            seq += 1
            if diurnal:
                # the diurnal leg speaks wire v2 — deltas against the
                # last acked keyframe with the structured-409 recovery
                # loop — because scale events are exactly what displaces
                # shards onto owners with no base row; the gate bounds
                # the resulting needs-keyframe burst (keyframe cadence:
                # every 5th window ships full regardless)
                full = encode_report_v2(rep, zones, seq=seq,
                                        run=f"r{idx}")
                frame = (encode_delta_v2(full, kf_base)
                         if kf_base is not None and seq % 5 else None)
                is_kf = frame is None
                base = full if is_kf else frame
            else:
                full, is_kf = b"", False
                base = encode_report(rep, zones, seq=seq, run=f"r{idx}")
            first_target = t_idx
            # at-least-once: retry THIS seq until a replica concludes
            # it — a replica outage then shows up as duplicates and
            # redirects, never as a seq-gap loss, which is exactly what
            # the multi-replica gate asserts
            while not stop.is_set():
                # sent_at is semantically WALL time: the aggregator's
                # skew quarantine compares it against its own wall clock
                # keplint: disable=KTL101
                body = restamp_transmit(base, time.time(),
                                        owner=peers[t_idx], epoch=epoch,
                                        acked_through=acked)
                t0 = time.perf_counter()
                try:
                    conn.request("POST", "/v1/report", body=body)
                    resp = conn.getresponse()
                    data = resp.read()
                    status = resp.status
                except OSError:
                    errors[idx] += 1
                    conn.close()
                    t_idx = (t_idx + 1) % len(peers)  # failover
                    conn = connect()
                    stop.wait(min(0.25, interval))  # no reconnect spin
                    continue
                if status == 421:
                    redirects[idx] += 1
                    owner = ""
                    try:
                        payload = json.loads(data)
                        owner = payload.get("owner", "")
                        epoch = max(epoch, int(payload.get("epoch", 0)))
                    except (ValueError, TypeError):
                        pass
                    t_idx = (peers.index(owner) if owner in peers
                             else (t_idx + 1) % len(peers))
                    conn.close()
                    conn = connect()
                    continue
                if status >= 500:
                    errors[idx] += 1
                    conn.close()
                    t_idx = (t_idx + 1) % len(peers)
                    conn = connect()
                    stop.wait(min(0.25, interval))
                    continue
                lat.append((time.monotonic(),
                            (time.perf_counter() - t0) * 1e3))
                if status == 409 and diurnal and not is_kf:
                    # structured needs-keyframe: the owner has no base
                    # for this delta (hand-off/eviction) — resend THIS
                    # window full; anything else 409-shaped falls
                    # through to the reject accounting
                    try:
                        needs_kf = bool(json.loads(data)
                                        .get("needs_keyframe"))
                    except (ValueError, UnicodeDecodeError,
                            AttributeError):
                        needs_kf = False
                    if needs_kf:
                        kf_409s[idx] += 1
                        base, is_kf = full, True
                        continue
                if status == 204:
                    acked = seq
                    if diurnal and is_kf:
                        kf_base = full
                    if t_idx != first_target:
                        # the window concluded on a DIFFERENT replica
                        # than first tried — a membership change (or
                        # outage) moved the shard and the report was
                        # replayed to its new owner
                        replays[idx] += 1
                else:
                    rejects[idx] += 1
                break
            stop.wait(interval)
        conn.close()

    def shed_agent(idx: int) -> None:
        """Herd-mode sender (--shed): emits on cadence into a local
        backlog (the spool stand-in) and drains it BATCHED through
        /v1/reports — 429s honored (bounded), 421s followed, outages
        survived by the backlog rather than a blocking retry loop."""
        rng_local = np.random.default_rng(seed * 1_000_003 + idx)
        cpu = rng_local.uniform(0.1, 5.0, workloads).astype(np.float32)
        rep = NodeReport(
            node_name=f"soak-{idx:04d}",
            zone_deltas_uj=rng_local.uniform(1e7, 5e8, 4).astype(
                np.float32),
            zone_valid=np.ones(4, bool),
            usage_ratio=0.6,
            cpu_deltas=cpu,
            workload_ids=[f"s{idx}-w{k}" for k in range(workloads)],
            node_cpu_delta=float(cpu.sum()),
            dt_s=interval,
            mode=MODE_MODEL if idx % 2 else MODE_RATIO,
            workload_kinds=np.ones(workloads, np.int8),
        )
        t_idx = idx % len(peers)

        def connect():
            h, _, p = peers[t_idx].rpartition(":")
            return http.client.HTTPConnection(h, int(p), timeout=30)

        def failover():
            nonlocal t_idx, conn
            conn.close()
            t_idx = (t_idx + 1) % len(peers)
            conn = connect()

        def follow(owner, adv_epoch):
            nonlocal t_idx, conn, epoch
            try:
                epoch = max(epoch, int(adv_epoch or 0))
            except (TypeError, ValueError):
                pass
            conn.close()
            t_idx = (peers.index(owner) if owner in peers
                     else (t_idx + 1) % len(peers))
            conn = connect()

        conn = connect()
        seq = 0
        acked = 0
        epoch = 0
        backlog: list[tuple[int, bytes]] = []
        time.sleep((idx / n_agents) * interval)
        lat = latencies[idx]

        def drain() -> None:
            nonlocal acked
            attempts = 0
            while backlog and not stop.is_set() and attempts < 8:
                attempts += 1
                head_seq = backlog[0][0]
                bodies = []
                for k, (s_, base_) in enumerate(backlog[:32]):
                    # everything but the newest window is a replay —
                    # under admission pressure the backlog waits while
                    # fresh ground truth keeps flowing
                    path = "replay" if s_ < seq else "fresh"
                    # sent_at is semantically WALL time (skew check)
                    sent_at = time.time()  # keplint: disable=KTL101
                    bodies.append(restamp_transmit(
                        base_, sent_at, delivery_path=path,
                        owner=peers[t_idx], epoch=epoch,
                        acked_through=acked))
                t0 = time.perf_counter()
                try:
                    if len(bodies) == 1:
                        conn.request("POST", "/v1/report", body=bodies[0])
                    else:
                        conn.request("POST", "/v1/reports",
                                     body=encode_report_batch(bodies))
                    resp = conn.getresponse()
                    data = resp.read()
                    status = resp.status
                except OSError:
                    errors[idx] += 1
                    failover()
                    return
                lat.append((time.monotonic(),
                            (time.perf_counter() - t0) * 1e3))
                if len(bodies) > 1:
                    drain_requests[idx] += 1
                if status == 429:
                    throttled[idx] += 1
                    try:
                        retry = float(resp.headers.get("Retry-After", 1))
                    except (TypeError, ValueError):
                        retry = 1.0
                    stop.wait(min(max(retry, 0.05), interval))
                    return
                if status == 421:
                    redirects[idx] += 1
                    owner = ""
                    try:
                        payload = json.loads(data)
                        owner = payload.get("owner", "")
                        follow(owner, payload.get("epoch", 0))
                    except (ValueError, TypeError):
                        failover()
                    continue
                if status >= 500:
                    errors[idx] += 1
                    failover()
                    stop.wait(min(0.25, interval))
                    return
                if len(bodies) == 1:
                    if status == 204:
                        acked = max(acked, head_seq)
                    else:
                        rejects[idx] += 1
                    backlog.pop(0)
                    continue
                # batch response: conclude the per-record prefix
                try:
                    rows = json.loads(data).get("results", [])
                except (ValueError, AttributeError):
                    rows = []
                concluded = 0
                throttled_row = None
                redirect_row = None
                for row in rows[:len(bodies)]:
                    st = (row.get("status")
                          if isinstance(row, dict) else None)
                    if isinstance(st, bool) or not isinstance(st, int):
                        break
                    if 200 <= st < 300:
                        acked = max(acked, backlog[concluded][0])
                        concluded += 1
                    elif st == 429:
                        throttled_row = row
                        break
                    elif st == 421:
                        redirect_row = row
                        break
                    elif 400 <= st < 500:
                        rejects[idx] += 1
                        concluded += 1
                    else:
                        break
                del backlog[:concluded]
                drain_records[idx] += concluded
                drain_batch_peak[idx] = max(drain_batch_peak[idx],
                                            concluded)
                if throttled_row is not None:
                    throttled[idx] += 1
                    try:
                        retry = float(throttled_row.get("retry_after", 1))
                    except (TypeError, ValueError):
                        retry = 1.0
                    stop.wait(min(max(retry, 0.05), interval))
                    return
                if redirect_row is not None:
                    follow(redirect_row.get("owner", ""),
                           redirect_row.get("epoch", 0))
                    continue
                if concluded == 0:
                    errors[idx] += 1
                    failover()
                    return

        while not stop.is_set():
            seq += 1
            backlog.append((seq, encode_report(rep, zones, seq=seq,
                                               run=f"r{idx}")))
            drain()
            stop.wait(interval)
        conn.close()

    del rng  # each agent thread builds its own generator
    rss_boot = rss_mib()
    t_start = time.monotonic()
    sender = shed_agent if shed else agent
    agents = [threading.Thread(target=sender, args=(i,), daemon=True)
              for i in range(n_agents)]
    for t in agents:
        t.start()

    killer = None
    if victim >= 0:
        def rebalance() -> None:
            surviving = [p for i, p in enumerate(peers) if i != victim]
            for i in sorted(live):
                aggs[i].apply_membership(surviving, 2)

        def kill_and_rebalance() -> None:
            # the chaos leg: one replica goes dark mid-soak, survivors
            # adopt the shrunken membership at epoch 2 — displaced
            # agents fail over, follow redirects, and the gate proves
            # no window was lost across the hand-off.
            # --rebalance-after > 0 (herd mode) delays the membership
            # change past the kill: until then the ring still names the
            # dead replica as owner, so displaced agents accumulate a
            # real backlog — the thundering herd the batched drain and
            # admission control then have to absorb.
            kill_mono[0] = time.monotonic()
            ctxs[victim].cancel()
            servers[victim].shutdown()
            aggs[victim].shutdown()
            live.discard(victim)
            if rebalance_after > 0:
                t = threading.Timer(rebalance_after, rebalance)
                t.daemon = True
                t.start()
            else:
                rebalance()

        killer = threading.Timer(max(0.0, kill_at), kill_and_rebalance)
        killer.daemon = True
        killer.start()

    scale_events = [0]
    departed_kf = [0]  # keyframe 409s served by replicas that left
    departed_journals: list[list[dict]] = []  # leavers' rings, at exit
    if diurnal:
        def membership_post(holder: str, payload: dict) -> None:
            h, _, p = holder.rpartition(":")
            conn = http.client.HTTPConnection(h, int(p), timeout=10)
            try:
                conn.request("POST", "/v1/membership",
                             body=json.dumps(payload).encode())
                conn.getresponse().read()
            finally:
                conn.close()

        def diurnal_schedule() -> None:
            # 1 → peak at seconds/3: every standby replica registers
            # with the lease holder over the REAL /v1/membership wire
            # (the holder folds it in at epoch+1 and broadcasts)
            up_at = t_start + seconds / 3.0
            down_at = t_start + 2.0 * seconds / 3.0
            while time.monotonic() < up_at and not stop.is_set():
                time.sleep(0.1)
            for i in range(1, replicas):
                if stop.is_set():
                    return
                for t in replica_threads[i]:
                    t.start()
                time.sleep(0.2)
                try:
                    aggs[i].request_join()
                except ValueError as err:
                    print(f"diurnal join of replica {i} failed: {err}",
                          file=sys.stderr)
                    continue
                live.add(i)
                scale_events[0] += 1
            # peak → 2 at 2·seconds/3: graceful leave through the
            # holder; the leaver keeps answering 421s for a grace
            # period (redirect drain) before going dark
            while time.monotonic() < down_at and not stop.is_set():
                time.sleep(0.1)
            left = []
            for i in range(2, replicas):
                if stop.is_set() or i not in live:
                    continue
                try:
                    membership_post(peers[0],
                                    {"op": "leave", "peer": peers[i]})
                except OSError as err:
                    print(f"diurnal leave of replica {i} failed: {err}",
                          file=sys.stderr)
                    continue
                left.append(i)
                scale_events[0] += 1
            time.sleep(min(2.0, interval))
            for i in left:
                live.discard(i)
                departed_kf[0] += int(
                    aggs[i]._stats.get("keyframe_requests_total", 0))
                departed_journals.append(aggs[i]._journal.snapshot())
                ctxs[i].cancel()
                servers[i].shutdown()
                aggs[i].shutdown()

        scheduler = threading.Thread(target=diurnal_schedule,
                                     daemon=True)
        scheduler.start()
    # ramp: wait until every agent has had a chance to connect+report and
    # a couple of attribution windows completed (first-window jit compile
    # memory and GIL stalls are one-time), so the steady-state baselines
    # — RSS and ingest-latency alike — measure the SERVICE, not startup.
    # The plateau is still reported, as soak_rss_ramp_mib.
    ramp_deadline = time.monotonic() + min(4 * interval, seconds)
    while time.monotonic() < ramp_deadline:
        done = sum(aggs[i].windows._stats["attributions_total"]
                   for i in sorted(live))
        if done >= 2 * len(live) \
                and time.monotonic() - t_start >= interval:
            break
        time.sleep(0.25)
    time.sleep(1.0)  # let compile-peak allocations settle before baselining
    rss_start = rss_mib()
    steady_mono = time.monotonic()
    time.sleep(max(1.0, seconds - (steady_mono - t_start)))
    stop.set()
    for t in agents:
        t.join(timeout=10)
    duration = time.monotonic() - t_start
    if killer is not None:
        killer.cancel()  # no-op when it already fired
    # stop the loops and DRAIN before the stats snapshot: the fused
    # ring (diurnal, fusedWindowK=4) holds up to K-1 staged intervals
    # whose publish would otherwise be missing from the final figures —
    # last_batch_nodes would read a stale mid-scale window. The run()
    # threads drain on exit, so JOIN them before snapshotting (a cancel
    # alone races their exit-drain) — then shutdown() idempotently
    # covers a thread that never got to run
    for ctx in ctxs:
        ctx.cancel()
    for i in sorted(live):
        servers[i].shutdown()
    for i in sorted(live):
        for t in replica_threads[i]:
            t.join(timeout=30)
    for i in sorted(live):
        aggs[i].shutdown()
    # surviving-replica stats: counters sum, per-window last_* figures
    # take the max (summing latencies across replicas would be a lie)
    live_aggs = [aggs[i] for i in sorted(live)]
    stats = live_aggs[0]._joined_stats()
    for a in live_aggs[1:]:
        for k, v in a._joined_stats().items():
            cur = stats.get(k)
            if not isinstance(v, (int, float)) \
                    or not isinstance(cur, (int, float)):
                continue
            if k.startswith("last_") and k.endswith("_ms"):
                stats[k] = max(cur, v)
            else:
                stats[k] = cur + v
    rss_end = rss_mib()

    all_samples = [tv for lat in latencies for tv in lat]
    # SLO percentiles over STEADY-STATE samples only (post-ramp): the
    # ramp's jit compiles hold the GIL and stall in-flight POSTs — a
    # one-time cost, not the service's p99
    flat = sorted(v for t, v in all_samples if t >= steady_mono)
    if not flat:
        flat = sorted(v for _, v in all_samples)
    out = {
        "soak_seed": seed,
        "soak_agents": n_agents,
        "soak_seconds": round(duration, 1),
        "soak_reports_sent": len(all_samples),
        "soak_report_p50_ms": round(percentile(flat, 0.50), 2),
        "soak_report_p99_ms": round(percentile(flat, 0.99), 2),
        "soak_report_max_ms": round(percentile(flat, 1.0), 2),
        "soak_rejected": int(rejects.sum()),
        "soak_conn_errors": int(errors.sum()),
        "soak_windows": stats["attributions_total"],
        "soak_last_batch_nodes": stats["last_batch_nodes"],
        "soak_window_ms": round(stats["last_attribution_ms"], 2),
        "soak_assembly_ms": round(stats["last_assembly_ms"], 2),
        "soak_device_ms": round(stats["last_device_ms"], 2),
        "soak_scatter_ms": round(stats["last_scatter_ms"], 2),
        "soak_h2d_rows": int(stats["last_h2d_rows"]),
        "soak_compile_count": int(stats["window_compiles_total"]),
        "soak_rss_ramp_mib": round(rss_start - rss_boot, 1),
        "soak_rss_growth_mib": round(rss_end - rss_start, 1),
        "soak_replicas": replicas,
        "soak_replica_killed": victim >= 0,
        "soak_redirects": int(redirects.sum()),
        "soak_windows_lost": int(stats.get("windows_lost_total", 0)),
        "soak_duplicates": int(stats.get("duplicates_total", 0)),
    }
    if diurnal:
        out.update({
            "soak_diurnal": True,
            # amortized host↔device sync cost of the last fused flush
            # (batch device ms / K) — the figure the fused loop shrinks
            "soak_sync_per_window_ms": round(
                stats.get("last_sync_per_window_ms", 0.0), 2),
            # enacted membership transitions: (peak-1) joins on the way
            # up plus (peak-2) leaves on the way down
            "soak_scale_events": int(scale_events[0]),
            "soak_scale_events_expected": (replicas - 1) + (replicas - 2),
            # reports concluded on a different replica than first
            # tried — displaced shards replayed to their new owners
            "soak_rejoin_replays": int(replays.sum()),
            # wire-v2 hand-off recovery: structured 409s served fleet-
            # wide (survivors + departed leavers) vs observed by agents
            "soak_keyframe_requests": (
                int(stats.get("keyframe_requests_total", 0))
                + departed_kf[0]),
            "soak_keyframe_409s_seen": int(kf_409s.sum()),
            "soak_final_replicas": len(live),
            "soak_final_epoch": max(
                aggs[i]._ring.epoch for i in sorted(live)),
        })
        # the black-box cross-check: merge every replica's journal
        # (survivors + departed leavers) into one fleet timeline; each
        # enacted scale event bumped the ring epoch exactly once, so
        # the merged journal must hold a membership.apply at >= that
        # many distinct post-initial epochs
        from kepler_tpu.blackbox import merge_events
        merged = merge_events(
            [aggs[i]._journal.snapshot() for i in sorted(live)]
            + departed_journals)
        apply_epochs = {e["fields"]["epoch"] for e in merged
                        if e["kind"] == "membership.apply"}
        out.update({
            "soak_journal_events": len(merged),
            "soak_journal_scale_applies": len(apply_epochs),
        })
    if shed:
        shed_total = sum(
            sum(aggs[i]._admission.shed_by_reason().values())
            for i in sorted(live))
        survivor = sorted(v for t, v in all_samples
                          if t >= kill_mono[0])
        out.update({
            "soak_shed": True,
            "soak_shed_total": int(shed_total),
            "soak_throttled": int(throttled.sum()),
            "soak_drain_requests": int(drain_requests.sum()),
            "soak_drain_records": int(drain_records.sum()),
            "soak_drain_records_per_request": (
                round(drain_records.sum() / drain_requests.sum(), 2)
                if drain_requests.sum() else 0.0),
            # deepest single recovery-replay batch delivered — the
            # request-count cut vs the PR 11 one-record-per-request
            # baseline is this over 1
            "soak_drain_batch_peak": int(drain_batch_peak.max()),
            # the headline herd number: ingest p99 on the SURVIVORS
            # after the kill (equals the overall p99 with no kill)
            "soak_survivor_ingest_p99_ms": round(
                percentile(survivor, 0.99), 2) if survivor else
                round(percentile(flat, 0.99), 2),
        })
    return out


def gate(row: dict, p99_budget_ms: float = 250.0,
         rss_budget_mib: float = 96.0) -> list[str]:
    failures = []
    if row["soak_report_p99_ms"] > p99_budget_ms:
        failures.append(f"ingest p99 {row['soak_report_p99_ms']} ms > "
                        f"{p99_budget_ms} ms")
    if row["soak_rejected"]:
        failures.append(f"{row['soak_rejected']} fresh reports rejected")
    if row["soak_rss_growth_mib"] > rss_budget_mib:
        failures.append(
            f"steady-state RSS grew {row['soak_rss_growth_mib']} MiB > "
            f"{rss_budget_mib} MiB")
    if row["soak_windows"] < 2:
        failures.append(f"only {row['soak_windows']} windows completed")
    if row["soak_last_batch_nodes"] < row["soak_agents"] * 0.95:
        failures.append(
            f"last window saw {row['soak_last_batch_nodes']} of "
            f"{row['soak_agents']} agents (reports going stale?)")
    if row.get("soak_replicas", 1) > 1 and row.get("soak_windows_lost"):
        failures.append(
            f"{row['soak_windows_lost']} windows lost across the "
            "replicated ingest tier (hand-off must be replay, not loss)")
    if row.get("soak_diurnal"):
        # elastic membership: every scheduled transition must have been
        # ENACTED through the membership plane, shards must actually
        # have moved (and replayed), and — via the replicas>1 zero-loss
        # check above — no window may be lost across any scale event
        if row["soak_scale_events"] < row["soak_scale_events_expected"]:
            failures.append(
                f"only {row['soak_scale_events']} of "
                f"{row['soak_scale_events_expected']} scale events "
                "enacted (join/leave through the membership plane "
                "failed)")
        if not row["soak_rejoin_replays"]:
            failures.append(
                "no rejoin replays observed: membership changes moved "
                "no shards (ring ownership never changed hands?)")
        if row["soak_final_replicas"] != 2:
            failures.append(
                f"diurnal schedule ended at {row['soak_final_replicas']} "
                "replicas (expected 2)")
        # fleet black box (ISSUE 19): every ENACTED scale event must be
        # reconstructable from the merged journals — a join/leave that
        # moved the ring without a membership.apply event is a silent
        # transition the incident timeline would never show
        if row["soak_journal_scale_applies"] < row["soak_scale_events"]:
            failures.append(
                f"merged journal shows {row['soak_journal_scale_applies']} "
                f"membership applies for {row['soak_scale_events']} "
                "enacted scale events (black-box journal missed a "
                "transition)")
        # bounded keyframe burst: a displaced shard's first delta at
        # its new owner earns exactly ONE structured 409 before the
        # keyframe lands (kepmc KTL132 pins the convergence), so the
        # fleet-wide 409 count must stay within a small constant of
        # the displaced-shard replay count — a needs-keyframe loop or
        # a thrashing base-row cache blows straight past this
        kf_budget = 4 * max(1, row["soak_rejoin_replays"])
        if row["soak_keyframe_requests"] > kf_budget:
            failures.append(
                f"{row['soak_keyframe_requests']} keyframe requests "
                f"(409s) > {kf_budget} = 4 x "
                f"max(1, {row['soak_rejoin_replays']} displaced-shard "
                "replays): needs-keyframe recovery is not converging")
        if not row["soak_keyframe_requests"]:
            failures.append(
                "zero keyframe requests across the scale schedule: the "
                "wire-v2 delta leg never exercised hand-off recovery")
    if row.get("soak_shed"):
        # herd mode: batched drain must measurably cut request count —
        # the deep recovery replay ships ≥ 8 records in one request
        # (the PR 11 baseline was exactly 1 record per request)
        if row.get("soak_replica_killed") \
                and row["soak_drain_batch_peak"] < 8:
            failures.append(
                f"deepest recovery batch delivered "
                f"{row['soak_drain_batch_peak']} records (< 8): "
                "recovery replay is not batching")
        if row.get("soak_replica_killed") \
                and row["soak_survivor_ingest_p99_ms"] > p99_budget_ms:
            failures.append(
                f"survivor ingest p99 "
                f"{row['soak_survivor_ingest_p99_ms']} ms > "
                f"{p99_budget_ms} ms after the kill (admission control "
                "failed to hold the herd off)")
    return failures


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--agents", type=int, default=1000)
    p.add_argument("--seconds", type=float, default=60.0)
    p.add_argument("--interval", type=float, default=5.0)
    p.add_argument("--workloads", type=int, default=100)
    p.add_argument("--replicas", type=int, default=1,
                   help="aggregator replicas sharing the ingest ring")
    p.add_argument("--kill-at", type=float, default=0.0,
                   help="seconds into the soak to kill one replica and "
                        "rebalance (0 = no kill; needs --replicas >= 2)")
    p.add_argument("--shed", action="store_true",
                   help="herd mode (ISSUE 12): replicas run admission "
                        "control (429 + Retry-After) and agents drain "
                        "their backlog batched through /v1/reports; "
                        "emits soak_shed_total / soak_drain_requests / "
                        "soak_survivor_ingest_p99_ms and gates the "
                        "deepest recovery batch at >= 8 records")
    p.add_argument("--diurnal", action="store_true",
                   help="elastic-membership mode (ISSUE 16): a 1 -> "
                        "peak -> 2 replica schedule under live load "
                        "driven through /v1/membership join/leave; "
                        "agents speak wire v2 (deltas + 409 keyframe "
                        "recovery); the replicas run the fused window "
                        "loop (fusedWindowK=4, ISSUE 20) and emit "
                        "soak_sync_per_window_ms; emits "
                        "soak_scale_events / "
                        "soak_rejoin_replays / soak_keyframe_requests "
                        "and gates ZERO windows lost plus a BOUNDED "
                        "post-rebalance keyframe burst (<= 4x the "
                        "displaced-shard replay count; ISSUE 17)")
    p.add_argument("--seed", type=int, default=0,
                   help="base seed for every stochastic stream (agent "
                        "report contents, admission jitter); default 0 "
                        "reproduces the historical runs bit-for-bit and "
                        "the chosen value is echoed in the header and "
                        "the soak_seed output field")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="conductor-driven mode: arm the kepchaos "
                        "schedule generate(chaos_seed, chaos_schedule) "
                        "for the whole soak (fault events only — op "
                        "events need the in-process conductor, "
                        "python -m kepler_tpu.chaos); fires are "
                        "reported in soak_chaos_fires. Randomized "
                        "pressure usually wants --no-gate")
    p.add_argument("--chaos-schedule", type=int, default=0,
                   help="schedule index within --chaos-seed")
    p.add_argument("--rebalance-after", type=float, default=None,
                   help="seconds AFTER the kill before survivors adopt "
                        "the shrunken membership (ownership-convergence "
                        "lag; default 0, or 8 intervals in --shed herd "
                        "mode so displaced agents build a real backlog)")
    p.add_argument("--p99-budget-ms", type=float, default=250.0)
    p.add_argument("--rss-budget-mib", type=float, default=96.0,
                   help="steady-state (post-ramp) RSS growth gate")
    p.add_argument("--no-gate", action="store_true")
    args = p.parse_args()
    from kepler_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    if args.diurnal and (args.shed or args.kill_at):
        p.error("--diurnal runs its own scale schedule; it does not "
                "compose with --shed or --kill-at")
    if args.diurnal:
        args.replicas = max(args.replicas, 4)
    rebalance_after = args.rebalance_after
    if rebalance_after is None:
        rebalance_after = 8 * args.interval if args.shed else 0.0
    plan = None
    if args.chaos_seed is not None:
        # the conductor's schedule grammar, lowered onto the soak's wall
        # clock: the same (seed, index) key names the same fault events
        # here and under `python -m kepler_tpu.chaos`
        from kepler_tpu import fault as fault_mod
        from kepler_tpu.chaos.schedule import (compile_fault_specs,
                                               generate)

        sched = generate(args.chaos_seed, args.chaos_schedule,
                         horizon=max(1, int(args.seconds
                                            / args.interval)),
                         members=["soak"], standbys=[])
        specs = compile_fault_specs(sched.events, args.interval)
        plan = fault_mod.FaultPlan(
            specs,
            seed=args.chaos_seed * 1_000_003 + args.chaos_schedule)
        print(f"# soak chaos schedule armed: seed={args.chaos_seed} "
              f"index={args.chaos_schedule} "
              f"fault_events={len(specs)} "
              f"sites={','.join(sorted(plan.sites()))}",
              file=sys.stderr)
    ctx = (fault_mod.installed(plan) if plan is not None
           else contextlib.nullcontext())
    with ctx:
        row = run_soak(args.agents, args.seconds, args.interval,
                       args.workloads, replicas=args.replicas,
                       kill_at=args.kill_at, shed=args.shed,
                       rebalance_after=rebalance_after,
                       diurnal=args.diurnal, seed=args.seed)
    if plan is not None:
        row["soak_chaos_fires"] = dict(sorted(plan.fires.items()))
    row["soak_rss_growth_budget_mib"] = args.rss_budget_mib
    failures = ([] if args.no_gate
                else gate(row, args.p99_budget_ms, args.rss_budget_mib))
    row["soak_ok"] = not failures
    print(json.dumps(row))
    for f in failures:
        print(f"SOAK VIOLATION: {f}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
