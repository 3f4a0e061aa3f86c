"""The window path: one fleet window, from stored reports to published results.

``WindowScheduler`` owns everything between the aggregator's report store
and ``/v1/results``: the degradation ladder, the engines, the windows in
flight, their publication, and what publication leaves behind (the latest
results, cumulative node energy, the window records, the window's stats).
It knows nothing of ingest, the ring or membership. What it needs from
above is given at construction — plain values and two callables,
``history_windows`` and ``on_mesh_lost`` — and :meth:`rebuild_engines` is
the one way membership reaches down into engine state.

The default path is DEVICE-RESIDENT and PIPELINED (``fleet/window.py``):
the padded packed-f16 batch lives on device, each window scatter-updates
only the rows whose report changed (delta H2D through a donated in-place
program), and with ``pipeline_depth`` ≥ 2 the program, fetch and scatter
of window N overlap window N+1's host assembly and dispatch. With the
publisher thread (:meth:`start`) a window is published when its program is
done, while the loop sleeps out the interval or assembles the next one, so
``pipeline_depth`` is only the bound on windows in flight; without it,
:meth:`step` N+1 publishes window N. :meth:`drain` (shutdown, an emptied
fleet) publishes what is in flight, in order.

The serial einsum-f32 path — full assemble, one sharded dispatch, a
multi-array fetch — serves ``accuracy_mode`` (the configuration the 0.5%
budget is validated under), temporal mode (its feature-history tensor has
no packed layout) and training-dump capture (it needs the host batch).
"""

from __future__ import annotations

import collections
import logging
import math
import queue
import threading
import time as _time
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Mapping, Protocol, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from kepler_tpu import fault, telemetry
from kepler_tpu.fleet.journal import EventJournal
from kepler_tpu.fleet.window import (BucketLadder, DeviceWindowError,
                                     FusedFlush, FusedWindowEngine,
                                     MultiHostWindowEngine,
                                     PackedWindowEngine, RowInput,
                                     ShardedWindowEngine, WindowMeta,
                                     align_zone_matrices)
from kepler_tpu.fleet.window_record import WindowLedger, WindowRecord
from kepler_tpu.parallel.aggregator_core import (
    HISTORY_ROWS_BASE,
    compact_history,
    fleet_shardings,
    fused_trunk_chosen,
    make_fleet_program,
    make_temporal_fleet_program,
    put_fleet_batch,
)
from kepler_tpu.parallel.fleet import (MODE_MODEL, NodeReport,
                                       assemble_fleet_batch)
from kepler_tpu.parallel.mesh import (NODE_AXIS, make_mesh,
                                      submesh_for_processes)
from kepler_tpu.utils.rowstore import RowStore

log = logging.getLogger("kepler.fleet.scheduler")

# degradation-ladder rungs of the window's device leg
# (docs/developer/resilience.md "Device-plane faults"): every device
# failure demotes ONE rung; `repromote_after` consecutive clean windows at
# a lower rung retry the rung above. The bottom rung touches no jax API, so
# windows keep publishing with the device plane completely dead.
RUNG_PIPELINED = 0  # packed-f16 resident batch, pipelineDepth in flight
RUNG_PACKED_SERIAL = 1  # packed-f16 resident batch, depth 1
RUNG_EINSUM = 2  # serial einsum-f32 (full assemble + dense dispatch)
RUNG_NUMPY = 3  # pure-NumPy host fallback (no device, no jax)
RUNG_NAMES = ("packed-pipelined", "packed-serial", "einsum-serial",
              "numpy-host")
# only rung 0 has other forms. Sharded over a multi-device node mesh
# (ShardedWindowEngine; a shard's failure demotes to the single-device
# rungs above):
RUNG_NAME_SHARDED = "packed-sharded-pipelined"
# on a multi-host mesh (MultiHostWindowEngine): healthy, and after the
# "mesh minus one host" demotion to the surviving process's own sharded
# engine (sticky: a dead jax.distributed peer cannot rejoin a running job)
RUNG_NAME_MULTIHOST = "packed-multihost-pipelined"
RUNG_NAME_MESH_DEGRADED = "packed-sharded-mesh-minus-host"
# the fused window loop (FusedWindowEngine, fusedWindowK > 1): one
# lax.scan dispatch + one fetch per K windows. A failure there demotes
# WITHIN rung 0 to the packed-pipelined engine before the ladder applies
RUNG_NAME_FUSED = "packed-fused-scan"

# per-mode checkpoint layout: required keys, and which key's last axis is
# the zone count Z
_REQUIRED_PARAM_KEYS = {
    "mlp": ("w0", "b0", "w1", "b1", "w2", "b2", "w_skip"),
    "linear": ("weight", "bias"),
    "moe": ("gate_w", "w0", "b0", "w1", "b1", "w_skip"),
    "deep": ("in_proj", "in_bias", "blocks", "w_head", "b_head", "w_skip"),
    "temporal": ("in_proj", "pos_emb", "wq", "wk", "wv", "wo",
                 "w_mlp0", "w_mlp1", "w_head", "b_head", "w_skip"),
}
_OUTPUT_BIAS_KEY = {"mlp": "b2", "linear": "bias", "moe": "b1",
                    "deep": "b_head", "temporal": "b_head"}


# the window's unlabelled families: stats key → (kind, name, help)
_SCALAR_FAMILIES: dict[str, tuple[str, str, str]] = {
    "last_batch_nodes": ("gauge", "kepler_fleet_nodes",
        "Nodes in the last fleet batch"),
    "last_batch_workloads": ("gauge", "kepler_fleet_workloads",
        "Workloads in the last fleet batch"),
    "last_attribution_ms": ("gauge", "kepler_fleet_attribution_latency_ms",
        "Whole-window latency of the last fleet attribution (assembly + "
        "device + scatter)"),
    "last_h2d_rows": ("gauge", "kepler_fleet_window_h2d_rows",
        "Node rows re-uploaded (delta H2D) for the last fleet window — 0 "
        "when the resident device batch was already current"),
    "last_h2d_device_bytes": ("gauge", "kepler_fleet_window_h2d_device_bytes",
        "Bytes the last fleet window sent to the device that was sent most "
        "(serial einsum/temporal path: each device of the mesh is put its "
        "own nodes' rows, so this is the window's H2D bytes over the device "
        "count; 0 on the packed paths, whose delta H2D counts rows)"),
    "last_fetch_ms": ("gauge", "kepler_fleet_window_fetch_ms",
        "Publish-fetch leg of the last fleet window: per-shard addressable "
        "D2H materialization of the result plane (owned shards only on the "
        "multi-host engine, so the cost scales with owned rows, not fleet "
        "size)"),
    "last_sync_per_window_ms": (
        "gauge", "kepler_fleet_window_sync_per_window_ms",
        "Amortized host↔device sync cost per published window at the fused "
        "tier: the last fused flush's whole device leg (dispatch + scan + "
        "batched K-window fetch) divided by the windows it published; 0.0 "
        "until a fused flush has run (fusedWindowK=1 or unfused rungs never "
        "set it)"),
    "window_shards": ("gauge", "kepler_fleet_window_shards",
        "Device shards the last fleet window ran over (node-axis mesh size "
        "on the sharded packed path; 1 = unsharded engine or a demoted "
        "single-device ladder rung)"),
    "shard_skew": ("gauge", "kepler_fleet_window_shard_skew_ratio",
        "Sticky-map load skew: max/mean per-shard resident-row occupancy "
        "(1.0 = balanced; the sparse model bucket — and so the whole mesh's "
        "estimator FLOPs — is sized by the fullest shard)"),
    "window_compiles_total": ("counter", "kepler_fleet_window_compiles_total",
        "Fleet-window program-cache misses — attribution programs AND delta "
        "scatter-updates (bucket-ladder shape changes; growth is geometric, "
        "shrink is hysteretic)"),
    "window_rung": ("gauge", "kepler_fleet_window_degraded",
        "Degradation-ladder rung of the window's device leg (0 = packed-f16 "
        "pipelined [healthy], 1 = packed serial, 2 = einsum-f32 serial, 3 = "
        "pure-NumPy host fallback)"),
    "window_repromotions_total": (
        "counter", "kepler_fleet_window_repromotions_total",
        "Window ladder re-promotions (repromoteAfter consecutive clean "
        "windows at a demoted rung retried the rung above)"),
    "attributions_total": ("counter", "kepler_fleet_attributions_total",
        "Completed fleet attributions"),
    "published_early_total": (
        "counter", "kepler_fleet_windows_published_early_total",
        "Fleet windows whose publication began before the loop snapshotted a "
        "later window (under the served loop: as soon as their program was "
        "done)"),
}


def _primary_introspect(snap: Mapping[str, dict]) -> dict | None:
    """The engine snapshot the shard/staleness/skew metrics should read:
    the one holding resident rows. After a demotion every engine was
    reset and only the DEMOTED rung's re-packs: preferring rung 0's would
    blank the flight recorder exactly while the plane is degraded."""
    fused = snap.get("fused")
    pipelined = snap.get("pipelined")
    serial = snap.get("serial")
    if fused and fused["resident"]["rows"]:
        return fused
    if pipelined and pipelined["resident"]["rows"]:
        return pipelined
    if serial and serial["resident"]["rows"]:
        return serial
    return fused or pipelined or serial


@dataclass
class _Pending:
    """One dispatched, not-yet-published window. Everything here was
    SNAPSHOTTED at dispatch: publishing window N after window N+1 changed
    the fleet must never mix rows — the metadata (and, on the packed path,
    the resident batch version the program read) is this window's own."""

    kind: str  # "packed" | "legacy"
    out: object  # device handle(s): packed f16 array, or FleetResult
    meta: WindowMeta | None  # packed path row layout
    now: float  # publication timestamp (dispatch-time clock)
    # the window's own record: its marks are the path's one clock, the
    # last_*_ms gauges are differences of them (fleet/window_record.py)
    rec: WindowRecord
    h2d_rows: int
    # packed path: per-shard H2D breakdown + shard count ((), 1 when the
    # dispatching engine was unsharded; legacy/numpy paths leave 1)
    h2d_shards: tuple = ()
    shards: int = 1
    # the engine plan's publish fetch (per-shard addressable; owned shards
    # only on the multi-host engine). None = np.asarray of the output
    fetch: Callable | None = None
    # kind "fused": `out` is already a HOST slice of the batch fetch. The
    # batch's device cost is carried by its LAST window's record (the K−1
    # free rides are the amortization); sync_per_window_ms is the averaged
    # figure (−1 on non-fused windows)
    sync_per_window_ms: float = -1.0
    fused_fetch_ms: float = 0.0
    # legacy path extras (training dump + dense scatter)
    batch: object = None
    aligned: list | None = None
    zone_names: list | None = None
    feat_hist: object = None
    t_valid: object = None
    # the compact history this window put (compact_history's arrays):
    # the window's until its outputs are fetched, then a later window's
    history_rows: tuple | None = None
    # what the served loop's publisher thread caught while publishing
    # this window: it stays at the head of the deque and the loop's next
    # step (or a drain) raises it where a failed fetch was always raised
    failure: Exception | None = None


class _FetchWorker:
    """One persistent daemon thread running window fetches, so the
    dispatch-timeout watchdog bounds them without a thread per window. A
    fetch that exceeds its timeout abandons the WORKER — parked in native
    code on the hung handle, which the ladder's ring re-seed guarantees
    nothing else reads — and the next fetch lazily replaces it."""

    __slots__ = ("_requests", "_thread")

    def __init__(self) -> None:
        self._requests: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="kepler-window-fetch")
        self._thread.start()

    # keplint: thread-role=fetch-worker
    def _loop(self) -> None:
        while True:
            fn, out = self._requests.get()
            if fn is None:
                return
            try:
                out.put(("value", fn()))
            except BaseException as err:  # relayed to the caller thread
                out.put(("error", err))

    def alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> None:
        self._requests.put((None, None))

    def run(self, fn: "Callable[[], object]",
            timeout: float) -> "tuple[str, object] | None":
        """→ ("value", result) | ("error", exc) | None on timeout (the
        worker is then permanently occupied — abandon it)."""
        out: queue.Queue = queue.Queue(maxsize=1)
        self._requests.put((fn, out))
        try:
            return out.get(timeout=timeout)
        except queue.Empty:
            return None


class FleetResults:
    """One published fleet window, column-oriented: publication is a
    handful of array references, no Python per workload or node; JSON
    materializes per ``/v1/results`` request (:meth:`render_node`).

    Arrays are indexed by ROW via ``rows[name]`` — on the packed resident
    path nodes sit at stable row indices with holes, so ``names`` is the
    key list, never an implicit index order. There the per-workload
    matrices arrive as ONE f16 watts array; the µW/µJ f32 planes (two
    [N, W, Z] passes) are made on first access (``wl_power_uw`` /
    ``wl_energy_uj``), never in the window hot loop — renders slice per
    row straight from the f16 plane."""

    __slots__ = ("timestamp", "zones", "names", "rows", "mode",
                 "node_power_uw", "node_energy_uj", "node_joules_total",
                 "workload_ids", "workload_kinds", "counts", "dt",
                 "_wl_watts_f16", "_wl_power_uw", "_wl_energy_uj")

    def __init__(self, timestamp: float, zones: list[str],
                 names: list[str], rows: dict[str, int], mode: np.ndarray,
                 node_power_uw: np.ndarray, node_energy_uj: np.ndarray,
                 node_joules_total: np.ndarray, workload_ids: list,
                 workload_kinds: list, counts: list,
                 wl_power_uw: np.ndarray | None = None,
                 wl_energy_uj: np.ndarray | None = None,
                 wl_watts_f16: np.ndarray | None = None,
                 dt: np.ndarray | None = None) -> None:
        self.timestamp = timestamp
        self.zones = zones
        self.names = names
        self.rows = rows
        self.mode = mode
        self.node_power_uw = node_power_uw
        self.node_energy_uj = node_energy_uj
        self.node_joules_total = node_joules_total
        self.workload_ids = workload_ids
        self.workload_kinds = workload_kinds
        self.counts = counts
        self.dt = dt
        self._wl_watts_f16 = wl_watts_f16
        self._wl_power_uw = wl_power_uw
        self._wl_energy_uj = wl_energy_uj

    def __contains__(self, name: str) -> bool:
        return name in self.rows

    @property
    def wl_power_uw(self) -> np.ndarray:
        if self._wl_power_uw is None:
            self._wl_power_uw = np.multiply(
                self._wl_watts_f16, 1e6, dtype=np.float32)
        return self._wl_power_uw

    @property
    def wl_energy_uj(self) -> np.ndarray:
        if self._wl_energy_uj is None:
            self._wl_energy_uj = self.wl_power_uw * self.dt[:, None, None]
        return self._wl_energy_uj

    def _row_wl(self, i: int, w: int) -> tuple[np.ndarray, np.ndarray]:
        """(power_uw [w, Z], energy_uj [w, Z]) for one row — slices the
        f16 plane directly when the full f32 planes were never forced."""
        if self._wl_power_uw is not None:
            return self._wl_power_uw[i, :w], self.wl_energy_uj[i, :w]
        power = np.multiply(self._wl_watts_f16[i, :w], 1e6,
                            dtype=np.float32)
        return power, power * float(self.dt[i])

    def render_node(self, name: str) -> dict:
        """The node's JSON payload (wire schema unchanged from the
        per-window-dict era)."""
        i = self.rows[name]
        w = self.counts[i]
        kinds = self.workload_kinds[i]
        power, energy = self._row_wl(i, w)
        return {
            "timestamp": self.timestamp,
            "zones": list(self.zones),
            "mode": int(self.mode[i]),
            "node_power_uw": self.node_power_uw[i].tolist(),
            "node_energy_uj": self.node_energy_uj[i].tolist(),
            "node_joules_total": self.node_joules_total[i].tolist(),
            "workloads": [
                {
                    "id": wid,
                    "kind": int(kinds[k]) if kinds is not None else -1,
                    "power_uw": p,
                    "energy_uj": e,
                }
                for k, (wid, p, e) in enumerate(zip(
                    self.workload_ids[i],
                    power.tolist(),
                    energy.tolist()))
            ],
        }


class StoredReport(Protocol):
    """What crosses the seam from the report store, read structurally:
    the aggregator's stored entry, or anything shaped like it."""

    report: NodeReport
    zone_names: tuple[str, ...]
    seq: int
    run: str
    content_seq: int


Reports = Sequence[StoredReport]  # sorted by node name where it matters


def _no_history(batch: Any) -> tuple[np.ndarray, np.ndarray]:
    raise RuntimeError("temporal mode needs a history_windows callable")


class WindowScheduler:
    """The window path below the report store. The loop thread drives
    :meth:`step`/:meth:`drain`; the publisher thread, the probe and the
    scrape go through the locks named on each attribute."""

    def __init__(
        self,
        *,
        history_windows: Callable[[Any], tuple[np.ndarray, np.ndarray]]
        = _no_history,
        on_mesh_lost: Callable[[str], None] | None = None,
        clock: Callable[[], float] | None = None,
        journal: EventJournal | None = None,
        model_mode: str | None = "mlp",
        model_params: Mapping[str, np.ndarray] | None = None,
        node_bucket: int = 8,
        workload_bucket: int = 256,
        backend: str = "einsum",
        accuracy_mode: bool = False,
        history_window: int = 16,
        training_dump_dir: str = "",
        training_dump_max_files: int = 1000,
        cum_retention: float = 600.0,
        pipeline_depth: int = 1,
        fused_window_k: int = 1,
        bucket_shrink_after: int = 16,
        fallback_enabled: bool = True,
        repromote_after: int = 8,
        dispatch_timeout: float = 30.0,
        mesh: Any = None,
        mesh_shape: Sequence[int] | None = None,
        mesh_axes: Sequence[str] | None = None,
        multihost_enabled: bool = False,
        multihost_topology: Mapping[str, Any] | None = None,
    ) -> None:
        # from above: temporal mode's [N, W, T, F] history assembly (the
        # store's: ingest writes the buffers), and what membership does
        # about a lost multi-host mesh once this side dropped its engines
        self._history_windows = history_windows
        self._on_mesh_lost = on_mesh_lost or (lambda reason: None)
        self._clock = clock or _time.time
        self._journal = journal if journal is not None else EventJournal(
            enabled=False, clock=self._clock)
        self._model_mode = model_mode
        self._params = model_params
        self._node_bucket = node_bucket
        self._workload_bucket = workload_bucket
        self._backend = backend
        # f32/highest precision (what the 0.5% accuracy budget is
        # validated under); off = bf16 throughput mode
        self._accuracy_mode = accuracy_mode
        self._history_window = history_window
        # training-data capture: ratio nodes' windows + their watts become
        # (features, labels) files for cmd/train
        self._dump_dir = training_dump_dir
        self._dump_max_files = max(1, training_dump_max_files)
        self._dump_seq = 0
        self._dump_files: list[str] | None = None  # seeded on first dump
        # the device mesh (meshShape [] = all devices, 1-D node axis: the
        # sharded production shape); built in init()
        self.mesh = mesh
        self._mesh_shape = list(mesh_shape or [])
        self._mesh_axes = list(mesh_axes or [])
        # multi-host SPMD tier: on a mesh spanning > 1 process rung 0 runs
        # the MultiHostWindowEngine (host-local rings, one SPMD dispatch);
        # a cross-host failure demotes STICKY to "mesh minus one host"
        self._multihost_enabled = bool(multihost_enabled)
        topo = dict(multihost_topology or {})
        self._mh_process_index: int | None = topo.get("process_index")
        self._mh_device_process = topo.get("device_process")
        self._mh_fabric = topo.get("fabric")
        self._mesh_degraded = False  # keplint: guarded-by=_results_lock
        self._mesh_elastic: Any = None  # live submesh (rebuild_engines)
        self._engine_mesh: Any = None  # mesh the packed engines run on
        self._device_info: dict[str, Any] = {}  # see device_fields()

        self._results_lock = threading.Lock()
        self._results: FleetResults | None = None  # keplint: guarded-by=_results_lock
        self._last_window_at: float | None = None
        # the window's own stats, written under _results_lock
        self._stats: dict[str, Any] = {
            "attributions_total": 0,
            # of those, begun before a later window was snapshotted
            "published_early_total": 0,
            "last_batch_nodes": 0, "last_batch_workloads": 0,
            # whole-window cost = the sum of its legs below (pipelined,
            # wall time spans two calls: the sum is the honest figure)
            "last_attribution_ms": 0.0,
            "last_assembly_ms": 0.0, "last_device_ms": 0.0,
            "last_scatter_ms": 0.0,
            "last_dispatch_ms": 0.0, "last_wait_ms": 0.0,
            "last_fetch_ms": 0.0,  # publish-fetch alone, inside the wait
            # fused tier: device sync cost averaged over the windows of
            # the last flushed batch
            "last_sync_per_window_ms": 0.0,
            "last_h2d_rows": 0,
            # serial path: the most H2D bytes any one device was sent
            "last_h2d_device_bytes": 0,
            # device shards the last window ran over (1 = unsharded or
            # demoted) and the per-shard H2D breakdown
            "window_shards": 0, "last_h2d_shards": [],
            "shard_skew": 0.0,  # max/mean per-shard rows (1.0 = balanced)
            "window_compiles_total": 0,
            "window_rung": 0,  # 0 = healthy full path
            "window_demotions_total": 0, "window_repromotions_total": 0}
        # cumulative per-node energy for the _total counters: a dense
        # RowStore whose columns follow the canonical zone axis (remapped
        # BY NAME when it changes); a node's row survives _cum_retention
        # of silence
        self._cum = RowStore(0, initial_rows=0)
        self._cum_zones: list[str] = []
        self._cum_last_seen: dict[str, float] = {}
        self._cum_retention = cum_retention
        # serial-path jit (jax caches per shape); the temporal program in
        # its two forms, {compact: jit}
        self._program: Any = None
        self._legacy_compiles = 0  # its cold dispatches (loop thread)
        # one record per window (fleet/window_record.py): the id the next
        # snapshot takes, when the loop began the wait before it, and the
        # complete records
        self._window_seq = 0
        self._tick_began: float | None = None
        self._window_ledger = WindowLedger()  # keplint: guarded-by=_results_lock
        # untrained fallbacks per zone count — never clobber trained params
        self._fallback_params: dict[int, object] = {}
        # (params as _params_for_zones gave them, the same on the mesh)
        self._params_placed: tuple[object, object] | None = None
        # -- windows in flight: depth 1 = dispatch then fetch in one step;
        # depth D ≥ 2 leaves at most D−1 in flight when a step returns.
        # Whoever holds _pipeline_lock publishes the oldest: the publisher
        # thread as soon as it is dispatched (waiting for the outputs
        # under the lock); the loop's step, for what is still there at the
        # depth; a drain. Never held during dispatch.
        self._pipeline_depth = max(1, int(pipeline_depth))
        self._bucket_shrink_after = max(1, int(bucket_shrink_after))
        # the rows of history a shard of the serial temporal program is
        # sent: the fullest shard's valid rows, on a ladder of its own
        self._history_rows = BucketLadder(HISTORY_ROWS_BASE,
                                          self._bucket_shrink_after)
        # ... and the host arrays of published windows, for the next to
        # write into: a fresh 29 MB block is 7,000 page faults, 27 ms on
        # the chip's host, whenever the allocator has handed the freed one
        # back to the system, and whether it does differs run to run
        # (PERF.md section 6). Appended by whoever publishes, popped by
        # the loop: a deque's two ends are atomic
        self._history_spare: collections.deque[tuple] = collections.deque(
            maxlen=self._pipeline_depth + 1)
        self._pipeline_lock = threading.Lock()
        self._inflight: collections.deque[_Pending] = collections.deque()  # keplint: guarded-by=_pipeline_lock
        # the publisher sleeps on the condition until the loop appends a
        # window, and runs for as long as it is the thread named here
        self._pipeline_cond = threading.Condition(self._pipeline_lock)
        self._publisher: threading.Thread | None = None  # keplint: guarded-by=_pipeline_lock
        # windows the publisher published since the loop's last step: the
        # loop counts them on the ladder, which only it may move
        self._early_unacked = 0  # keplint: guarded-by=_pipeline_lock
        # rung 0: ShardedWindowEngine on a multi-device 1-D node mesh,
        # else PackedWindowEngine; _engine_serial is the single-device
        # engine of the packed-serial rung when rung 0 is sharded
        self._engine: PackedWindowEngine | None = None
        self._engine_serial: PackedWindowEngine | None = None
        self._shard_count = 1  # set in init() from the mesh shape
        # fused window loop (fusedWindowK > 1, single-host only): host-only
        # staging per interval, ONE lax.scan dispatch + one fetch per K
        self._fused_window_k = max(1, int(fused_window_k))
        self._engine_fused: FusedWindowEngine | None = None
        # a failure at the fused tier flips this (rung 0 stays, on the
        # packed-pipelined engine); repromote_after clean windows clear it
        self._fused_degraded = False  # keplint: guarded-by=_results_lock
        # (stored_sorted, zone_names, now, record) per un-flushed window,
        # oldest first, parallel to the fused engine's ring; popped as the
        # flush publishes. A failure's engine reset ORPHANS them and
        # _replay_fused_pending republishes them at the demoted tier (zero
        # gaps). Loop-thread-only.
        self._fused_pending: list[tuple] = []
        # -- degradation ladder: written only by the loop thread; the
        # probe/metrics threads read a snapshot under _results_lock
        self._fallback_enabled = bool(fallback_enabled)
        self._repromote_after = max(1, int(repromote_after))
        self._dispatch_timeout = max(0.0, float(dispatch_timeout))
        self._rung = RUNG_PIPELINED  # keplint: guarded-by=_results_lock
        self._clean_windows = 0  # consecutive clean at the current rung
        self._windows_since_failure = 0
        # bounded ring of ladder transitions (probe, /debug/window);
        # published windows tick _windows_at_rung
        self._rung_timeline: collections.deque[dict] = collections.deque(  # keplint: guarded-by=_results_lock
            maxlen=64)
        self._windows_at_rung = 0
        # per-window engine introspection snapshot (taken by the publish
        # path, read by /debug/window + collect off-thread)
        self._introspect_cache: dict = {}  # keplint: guarded-by=_results_lock
        # failed-probe backoff: a demotion before a just-promoted rung
        # proved itself doubles the clean-window threshold of the next
        # probe (capped) — probing a wedged device, each stall abandoning
        # a fetch worker, DECAYS. Reset on reaching full health.
        self._probe_penalty = 1
        self._probe_penalty_cap = 64
        self._just_promoted = False
        self._last_window_failure = ""
        self._demotions_by_reason: dict[str, int] = {}  # keplint: guarded-by=_results_lock
        # lazy, replaced after a stall abandons it; used only by the
        # publish path (serialized by _pipeline_lock)
        self._fetch_worker: _FetchWorker | None = None

    # -- lifecycle -----------------------------------------------------------

    def init(self) -> None:
        """Build the mesh, round the node bucket to it, check the params:
        everything that can fail at start-up instead of the first window."""
        if self.mesh is None:
            self.mesh = make_mesh(self._mesh_shape,
                                  self._mesh_axes or (NODE_AXIS,))
        n_dev = self.mesh.devices.size
        # the node axis shards over the mesh: round the bucket up so padded
        # batches always divide evenly across devices
        if self._node_bucket % n_dev:
            self._node_bucket = ((self._node_bucket // n_dev) + 1) * n_dev
        self._shard_count = self._mesh_shard_count()
        if self._model_mode:
            if self._model_mode != "temporal":
                from kepler_tpu.models.estimator import predictor

                # fail at startup on unservable mode; temporal serves via
                # its dedicated history program instead of the registry
                predictor(self._model_mode)
            self._check_params_shape()
            if self._params is None:
                log.warning("no trained %s params given; estimates will use "
                            "untrained initialization", self._model_mode)

    def start(self) -> None:
        """Start the served loop's early publisher."""
        thread = threading.Thread(target=self._publish_early, daemon=True,
                                  name="kepler-window-publish")
        with self._pipeline_lock:
            self._publisher = thread
        thread.start()

    def stop(self) -> None:
        """Stop the publisher (it finishes the window it is on first)."""
        with self._pipeline_lock:
            thread, self._publisher = self._publisher, None
            self._pipeline_cond.notify_all()
        if thread is not None:
            thread.join()

    # keplint: thread-role=shutdown
    def shutdown(self) -> None:
        # idempotent with the loop's exit drain (the deque is empty then);
        # covers direct step() users who never ran the loop
        self.drain()
        worker, self._fetch_worker = self._fetch_worker, None
        if worker is not None:
            worker.stop()

    def tick_began(self) -> int:
        """The loop is about to wait out its interval: the wait is the
        first leg of the next window's record. → that window's id."""
        self._tick_began = _time.monotonic()
        return self._window_seq

    def new_record(self, now: float, begin: float) -> WindowRecord:
        """The record of the window about to be snapshotted. It takes its
        sequence number only if :meth:`step` runs: a tick that finds the
        fleet empty leaves no record."""
        rec = WindowRecord(self._window_seq, now, begin, self._tick_began)
        self._tick_began = None
        return rec

    def rebuild_engines(self, mesh: Any = None, fabric: Any = None) -> None:
        """Membership changed the member set or the live mesh: sticky maps
        cleared, rings re-seeded — the next window does a full re-pack.
        ``mesh``: the elastic submesh a mesh-path membership restored the
        multi-host tier over (``fabric``: a rejoin's fresh incarnation).
        None: the mesh no longer describes ownership, and a configured
        multi-host tier serves from its own single-host engine until a
        mesh-path membership restores it."""
        self._mesh_elastic = mesh
        if fabric is not None:
            self._mh_fabric = fabric
        with self._results_lock:
            if mesh is not None:
                self._mesh_degraded = False
            elif self.multihost_active():
                self._mesh_degraded = True
        self._engine = None
        self._engine_serial = None

    # -- mesh ---------------------------------------------------------------

    def device_fields(self) -> dict[str, Any]:
        """The engine mesh's first device as jax reports it, and the
        mesh's device count — read once, then served by /debug/window and
        the start-up log, so an aggregator serving off the CPU can never
        pass for one on the chip. Empty until a mesh exists."""
        if not self._device_info and self.mesh is not None:
            first = self.mesh.devices.flat[0]
            self._device_info = {"platform": first.platform,
                                 "device_kind": first.device_kind,
                                 "devices": int(self.mesh.devices.size)}
        return self._device_info

    def _mesh_shard_count(self, mesh: Any = None) -> int:
        """Shards the packed window runs over: the node-axis size when
        the mesh is 1-D over ``node`` (every device an independent
        shard with its own resident ring). Single-device and 2-D
        (node × model) meshes run the unsharded engine — their batch
        still shards via NamedSharding, but H2D stays whole-batch."""
        mesh = mesh if mesh is not None else self.mesh
        if mesh is None:
            return 1
        n_dev = mesh.devices.size
        if n_dev > 1 and dict(mesh.shape).get(NODE_AXIS, 0) == n_dev:
            return n_dev
        return 1

    # -- multi-host topology -----------------------------------------------

    def device_process_fn(self) -> Callable[[Any], int]:
        if self._mh_device_process is not None:
            return self._mh_device_process
        return lambda d: int(getattr(d, "process_index", 0))

    def self_process(self) -> int:
        if self._mh_process_index is not None:
            return int(self._mh_process_index)
        import jax

        return int(jax.process_index())

    def multihost_active(self) -> bool:
        """True when rung 0 should run the multi-host engine: multihost
        enabled, a 1-D node mesh, and devices spanning > 1 process
        (real ``jax.distributed`` processes, or the injected virtual
        topology the tests/bench drive in one process)."""
        if not self._multihost_enabled or self.mesh is None:
            return False
        mesh = self._live_mesh()
        n_dev = mesh.devices.size
        if n_dev < 2 or dict(mesh.shape).get(NODE_AXIS, 0) != n_dev:
            return False
        proc = self.device_process_fn()
        return len({proc(d) for d in mesh.devices.flat}) > 1

    def _live_mesh(self) -> Any:
        """The mesh the multi-host tier currently runs on: the full
        configured mesh, or the elastic submesh the last mesh-path
        membership restored over a peer subset."""
        return (self._mesh_elastic if self._mesh_elastic is not None
                else self.mesh)

    def _local_mesh(self) -> Any:
        """The surviving single-host mesh after a mesh demotion: this
        process's own devices, 1-D over node."""
        return submesh_for_processes(self.mesh, [self.self_process()],
                                     self.device_process_fn())

    # keplint: thread-role=window-publisher
    def _publish_early(self) -> None:
        """The publisher thread: publish the oldest window in flight as
        soon as there is one, not at the loop's next step. The wait for
        its outputs is ``_publish``'s own (``_fetch_device``, interpreter
        lock released), so the loop sleeps out its interval and assembles
        the next window meanwhile, and blocks only where it would append
        past the depth. A failure is left on the window for the loop to
        raise: demoting, resetting engines and recomputing are the loop's."""
        with self._pipeline_lock:
            while self._publisher is threading.current_thread():
                if (not self._inflight
                        or self._inflight[0].failure is not None):
                    self._pipeline_cond.wait()
                    continue
                p = self._inflight[0]
                # a cycle of its own, as the loop's wait is: the legs of
                # the publication nest in it on this thread
                with telemetry.span("aggregator.publish",
                                    window=p.rec.seq):
                    try:
                        self._publish(p, on_loop=False)
                    except Exception as err:
                        p.failure = err
                if p.failure is None:
                    self._inflight.popleft()
                    self._early_unacked += 1

    def _rung_display(self, rung: int) -> str:
        """Operator-facing rung name: rung 0 reads as its multi-host or
        sharded form on a multi-device node mesh (only rung 0 has
        one), and as the "mesh minus one host" tier after a mesh
        demotion."""
        if rung == RUNG_PIPELINED:
            if self.multihost_active():
                return (RUNG_NAME_MESH_DEGRADED if self._mesh_degraded
                        else RUNG_NAME_MULTIHOST)
            if self._fused_tier_active():
                return RUNG_NAME_FUSED
            if self._shard_count > 1:
                return RUNG_NAME_SHARDED
        return RUNG_NAMES[rung]

    def _fused_tier_active(self) -> bool:
        """Whether rung 0 currently runs the fused device-resident
        window loop (aggregator.fusedWindowK > 1, packed path, single
        host, not demoted within rung 0)."""
        return (self._fused_window_k > 1 and not self._fused_degraded
                and not self._multihost_enabled and self._use_packed())

    def health(self, membership: Mapping[str, Any] | None = None) -> dict:
        """The ladder's part of the ``fleet-window`` probe: degraded while
        the device window leg runs below the full packed-pipelined rung.
        Names the rung, so operators see WHAT degraded service they are
        getting (einsum-serial = slower but exact; numpy-host = device
        fully dead, ratio attribution still correct). ``membership`` is
        the aggregator's own lines of the ``multihost`` block."""
        with self._results_lock:
            out = {
                "ok": self._rung == RUNG_PIPELINED,
                "rung": self._rung,
                "rung_name": self._rung_display(self._rung),
                "shards": (self._shard_count
                           if self._rung == RUNG_PIPELINED else 1),
                "demotions_total": self._stats["window_demotions_total"],
                "repromotions_total":
                    self._stats["window_repromotions_total"],
                "windows_since_last_failure": self._windows_since_failure,
                "fallback_enabled": self._fallback_enabled,
                "probe_backoff": self._probe_penalty,
                "windows_at_rung": self._windows_at_rung,
                "timeline_len": len(self._rung_timeline),
                # the last few transitions inline (full ring on
                # /debug/window) — enough for "what just happened"
                "timeline": list(self._rung_timeline)[-5:],
            }
            if self._last_window_failure:
                out["last_failure"] = self._last_window_failure
            if self._fused_window_k > 1:
                eng = self._engine_fused
                out["fused"] = {
                    "k": self._fused_window_k,
                    "active": (self._rung == RUNG_PIPELINED
                               and self._fused_tier_active()),
                    "degraded": self._fused_degraded,
                    # host-ring occupancy: intervals staged, not yet
                    # flushed (the next flush publishes this many + 1)
                    "pending_windows": len(self._fused_pending),
                    "sync_per_window_ms":
                        self._stats["last_sync_per_window_ms"],
                }
                if eng is not None:
                    out["fused"]["ring_occupancy"] = \
                        eng.pending_occupancy()
                if self._fused_degraded:
                    # fused is rung 0's healthy tier when configured —
                    # running packed-pipelined instead IS degraded
                    # service, mirrored on the probe like _mesh_degraded
                    out["ok"] = False
            if self._multihost_enabled:
                from kepler_tpu.parallel.mesh import multihost_status

                init = multihost_status()
                # a degraded mesh is NOT ok — the probe names the tier
                # so a half-joined or half-dead mesh is diagnosable
                out["multihost"] = {
                    "active": self.multihost_active(),
                    "mesh_degraded": self._mesh_degraded,
                    "init_joined": bool(init.joined),
                    # the DISTINCT init failure reason (joined |
                    # unconfigured | coordinator_unreachable |
                    # init_error) — never a generic decline
                    "init_reason": init.reason,
                    **(membership or {}),
                }
                if init.detail:
                    out["multihost"]["init_detail"] = init.detail
                if self._mesh_degraded:
                    out["ok"] = False
        return out

    # -- degradation ladder ------------------------------------------------

    # keplint: requires-lock=_results_lock
    def _record_rung_transition_locked(self, prev: int, rung: int,
                                       reason: str,
                                       from_name: str = "") -> None:
        """Append one ladder transition to the bounded rung timeline
        (the flight recorder's demote/re-promote history). Monotonic
        time orders transitions across wall-clock steps; wall time
        anchors them for humans. ``from_name`` overrides the from-rung
        display for the mesh demotion, whose from/to share rung 0."""
        rung_name = self._rung_display(rung)
        from_rung_name = from_name or self._rung_display(prev)
        stamp = self._journal.emit(
            "rung.transition", rung=rung, rung_name=rung_name,
            from_rung=prev, from_rung_name=from_rung_name,
            reason=reason)
        entry: dict[str, Any] = {
            "rung": rung,
            "rung_name": rung_name,
            "from_rung": prev,
            "from_rung_name": from_rung_name,
            "reason": reason,
            "wall_time": self._clock(),
            "monotonic_s": _time.monotonic(),
            "windows_at_prev_rung": self._windows_at_rung,
        }
        if stamp is not None:
            # the journal's HLC stamp, when enabled — lets /debug/window
            # rows line up against the merged fleet timeline (wall +
            # monotonic stay: humans and single-process ordering)
            entry["hlc"] = stamp.to_dict()
        self._rung_timeline.append(entry)
        self._windows_at_rung = 0

    def _handle_device_failure(self, err: Exception) -> None:
        """One device-leg failure: abandon every in-flight window (their
        handles may be poisoned — a donated buffer consumed by a failed
        dispatch can never be read or rebound), re-seed the resident ring
        and host staging from scratch, and demote one rung. The caller
        recomputes the CURRENT window at the new rung, so the interval
        still publishes."""
        reason = (err.reason if isinstance(err, DeviceWindowError)
                  else "runtime_error")
        with self._pipeline_lock:
            abandoned = len(self._inflight)
            self._inflight.clear()
            # published before the failure: no clean window of the rung
            # the ladder is about to enter
            self._early_unacked = 0
        # both packed engines re-seed: the failed rung's ring is poisoned
        # and the OTHER engine's buffers may alias handles a drained
        # window read — re-entering either rung starts from a full re-pack
        if self._engine is not None:
            self._engine.reset()
        if self._engine_serial is not None:
            self._engine_serial.reset()
        if self._engine_fused is not None:
            # the fused ring is poisoned like any other: reset drops its
            # device block AND the host pending ring — the orphaned
            # windows republish from _fused_pending snapshots at the
            # demoted tier (zero gaps)
            self._engine_fused.reset()
        self._program = None  # a failed serial program recompiles fresh
        # a failure at the MULTI-HOST rung demotes to "mesh minus one
        # host" first: rung 0 is kept, but its engine becomes the
        # surviving single-host sharded engine — the next failure (a
        # genuinely dead local device) walks the ordinary ladder
        mesh_demotion = (self.multihost_active()
                         and not self._mesh_degraded
                         and self._rung == RUNG_PIPELINED)
        # likewise a failure at the FUSED tier demotes WITHIN rung 0
        # first: the fused flag flips, rung 0's engine becomes the
        # packed-pipelined one, and the next failure walks the ladder
        fused_demotion = (not mesh_demotion
                          and self._rung == RUNG_PIPELINED
                          and self._fused_tier_active())
        with self._results_lock:
            prev = self._rung
            prev_name = self._rung_display(prev)  # before any flag flip
            from_name = ""
            if mesh_demotion:
                from_name = prev_name
                self._mesh_degraded = True
                rung = prev  # rung 0 stays; its engine changes tier
            elif fused_demotion:
                from_name = RUNG_NAME_FUSED
                self._fused_degraded = True
                rung = prev  # rung 0 stays; its engine changes tier
            else:
                self._rung = min(prev + 1, RUNG_NUMPY)
                rung = self._rung
            self._clean_windows = 0
            self._windows_since_failure = 0
            if self._just_promoted:
                # a failed PROBE (the promoted rung died before proving
                # itself): back off the next probe exponentially
                self._probe_penalty = min(self._probe_penalty * 2,
                                          self._probe_penalty_cap)
                self._just_promoted = False
            self._demotions_by_reason[reason] = \
                self._demotions_by_reason.get(reason, 0) + 1
            self._stats["window_demotions_total"] += 1
            self._stats["window_rung"] = rung
            self._last_window_failure = f"{reason}: {err}"[:240]
            self._record_rung_transition_locked(prev, rung, reason,
                                                from_name=from_name)
        if mesh_demotion:
            # the "mesh minus one host" tier: the survivors' rung 0 becomes
            # their own single-host sharded engine (full ring re-seed via
            # the engine rebuild), sticky within this fabric incarnation.
            # Healing the ring is membership's: told once, after the drop
            self._engine = None  # next window rebuilds over the local mesh
            self._engine_serial = None  # its pinned device must be LOCAL
            self._mesh_elastic = None  # the submesh died with the peer
            log.error("multi-host mesh degraded (%s): demoting to the "
                      "single-host engine over this process's devices; "
                      "displaced agents will be redirected by epoch bump",
                      reason)
            self._on_mesh_lost(reason)
        log.error("fleet window device leg failed (%s) at rung %s; "
                  "demoting to %s, %d in-flight window(s) abandoned, "
                  "resident ring re-seeded: %s", reason,
                  from_name or prev_name, self._rung_display(rung),
                  abandoned, err)

    def _ladder_window_ok(self) -> None:
        """One window published without a device failure. At a demoted
        rung, ``repromote_after`` consecutive clean windows retry the
        rung above (one step at a time — the breaker's half-open probe,
        ladder-shaped). A failure during the retried rung demotes right
        back and restarts the count."""
        promoted = None
        with self._results_lock:
            self._windows_since_failure += 1
            self._windows_at_rung += 1
            if self._just_promoted:
                self._just_promoted = False  # the rung proved itself
                if self._rung == RUNG_PIPELINED:
                    # reset only AFTER the healthy rung publishes a clean
                    # window — resetting at promotion time would let a
                    # rung-0-specific failure probe at a constant ~2×
                    # cadence forever instead of decaying to the cap
                    self._probe_penalty = 1
            if self._rung != RUNG_PIPELINED:
                self._clean_windows += 1
                needed = self._repromote_after * self._probe_penalty
                if self._clean_windows >= needed:
                    self._rung -= 1
                    self._clean_windows = 0
                    self._just_promoted = True
                    self._stats["window_repromotions_total"] += 1
                    self._stats["window_rung"] = self._rung
                    promoted = self._rung
                    self._record_rung_transition_locked(
                        self._rung + 1, self._rung, "repromoted")
            elif self._fused_degraded and self._fused_window_k > 1:
                # within-rung-0 probe back to the fused tier, on the
                # ladder's own hysteresis; the fused engine re-seeds its
                # ring on the next interval: one full re-pack
                self._clean_windows += 1
                needed = self._repromote_after * self._probe_penalty
                if self._clean_windows >= needed:
                    from_name = self._rung_display(RUNG_PIPELINED)
                    self._fused_degraded = False
                    self._clean_windows = 0
                    self._just_promoted = True
                    self._stats["window_repromotions_total"] += 1
                    promoted = RUNG_PIPELINED
                    self._record_rung_transition_locked(
                        RUNG_PIPELINED, RUNG_PIPELINED, "repromoted",
                        from_name=from_name)
        if promoted is not None:
            log.info("fleet window ladder: clean-window threshold met — "
                     "re-promoted to rung %d (%s)", promoted,
                     self._rung_display(promoted))

    def _fetch_device(self, fn: "Callable[[], object]") -> object:
        """Blocking device fetch with stall detection: it runs on the
        persistent ``_FetchWorker`` thread, bounded by ``dispatch_timeout``
        — a hung dispatch (dead device runtime, lost chip) DEMOTES instead
        of wedging the loop forever. On a stall the worker is abandoned
        and replaced lazily. ``device.stall`` injects a deterministic
        hang of ``arg`` seconds ahead of the real fetch."""
        spec = fault.fire("device.stall")

        def work() -> object:
            if spec is not None and spec.arg:
                _time.sleep(float(spec.arg))
            return fn()

        timeout = self._dispatch_timeout
        if timeout <= 0:
            return work()
        worker = self._fetch_worker
        if worker is None or not worker.alive():
            worker = self._fetch_worker = _FetchWorker()
        outcome = worker.run(work, timeout)
        if outcome is None:
            # abandon the occupied worker, but queue its stop sentinel:
            # a TRANSIENTLY stuck fetch that eventually completes lets
            # the thread exit instead of parking forever; a truly wedged
            # one is no worse off
            self._fetch_worker = None
            worker.stop()
            raise DeviceWindowError(
                "stall", f"window fetch exceeded aggregator."
                f"dispatchTimeout {timeout:g}s")
        kind, value = outcome
        if kind == "error":
            raise value
        return value

    # -- the loop's side -----------------------------------------------------

    def step(self, stored_sorted: Reports,
             zone_names: list[str], now: float,
             rec: WindowRecord) -> "FleetResults | None":
        """One pipeline step: dispatch this interval's window (the live
        reports sorted by node name, the zone axis their sorted union),
        publish the oldest in flight if it is still there.

        At ``pipeline_depth`` 1 every call publishes the window it was
        given. At depth D ≥ 2 the dispatched window stays in flight while
        the host assembles the next: without the publisher, call N+1
        publishes window N, and the blocking fetch
        (``window.pipeline_wait``) pays only what the device hasn't
        finished; with it, window N is usually published by then. Returns
        what THIS call published (None: the pipeline is still filling, or
        the publisher was there first).

        A device-leg failure demotes one rung and RECOMPUTES this window
        there: a dead device costs latency, never a publish. Bounded: the
        rung strictly increases per retry and the bottom rung's failures
        re-raise (a NumPy bug is a bug, not degradation)."""
        self._window_seq += 1  # rec took this window's number
        while True:
            try:
                # republish windows a fused-tier failure orphaned
                # (no-op while the fused ring is intact or empty);
                # a failure HERE re-enters the same demote+retry
                # loop with the un-replayed snapshots preserved
                self._replay_fused_pending()
                return self._window_step(stored_sorted, zone_names,
                                         now, rec)
            except Exception as err:
                if (not self._fallback_enabled
                        or self._rung >= RUNG_NUMPY):
                    raise
                self._handle_device_failure(err)

    def _window_step(self, stored_sorted: Reports,
                     zone_names: list[str], now: float,
                     rec: WindowRecord) -> "FleetResults | None":
        """One dispatch+publish pass at the CURRENT ladder rung."""
        rung = self._rung
        rec.restart()  # a retry keeps none of the failed rung's marks
        if rung >= RUNG_NUMPY:
            pending = self._dispatch_numpy(stored_sorted, zone_names,
                                           now, rec)
        elif rung >= RUNG_EINSUM or not self._use_packed():
            pending = self._dispatch_legacy(stored_sorted, zone_names,
                                            now, rec)
        elif rung == RUNG_PIPELINED and self._fused_tier_active():
            # the fused tier publishes on its own cadence (K windows
            # per flush, all inside the flush call) — it never enters
            # the per-window pipeline deque below
            return self._window_step_fused(stored_sorted, zone_names,
                                           now, rec)
        else:
            pending = self._dispatch_packed(stored_sorted, zone_names,
                                            now, rec, rung)
        # every demoted rung drains each window (no in-flight handle
        # outlives its interval); only rung 0 pipelines, the serial path
        # included. The depth bounds the windows in flight: the step
        # publishes (or, where the publisher holds the lock, waits for) the
        # oldest until fewer than `depth` are left, and raises a failure
        # the publisher left on the oldest whatever the depth
        depth = self._pipeline_depth if rung == RUNG_PIPELINED else 1
        with self._pipeline_lock:
            self._inflight.append(pending)
            # prune cumulative totals while the device computes —
            # host work needing no outputs overlaps the window
            for name, seen in list(self._cum_last_seen.items()):
                if now - seen > self._cum_retention:
                    del self._cum_last_seen[name]
                    self._cum.pop(name)
            published = None
            while self._inflight and (
                    len(self._inflight) >= depth
                    or self._inflight[0].failure is not None):
                published = self._publish_oldest()
            early, self._early_unacked = self._early_unacked, 0
            if self._inflight:
                self._pipeline_cond.notify()  # the publisher's turn
        if early:
            # the publisher leaves the engines to the thread that owns
            # them: their snapshot follows here, one step behind
            with self._results_lock:
                self._engine_stats_locked()
        for _ in range(early + (published is not None)):
            self._ladder_window_ok()
        return published

    # keplint: requires-lock=_pipeline_lock
    def _publish_oldest(self) -> "FleetResults":
        """Publish the oldest window in flight, or raise what the
        publisher thread caught on it; either way it leaves the deque."""
        p = self._inflight.popleft()
        if p.failure is not None:
            raise p.failure
        return self._publish(p)

    def _use_packed(self) -> bool:
        """Packed-f16 resident path is the default; the serial einsum-f32
        path serves accuracy mode (the 0.5%-budget validation config),
        temporal mode (no packed layout for [N, W, T, F] histories), and
        training-dump capture (which needs the assembled host batch)."""
        return (not self._accuracy_mode and self._model_mode != "temporal"
                and not self._dump_dir)

    def drain(self) -> "FleetResults | None":
        """Publish every window in flight, oldest first (an emptied
        fleet, the loop's exit, shutdown): results never rot in flight
        when reports stop. → the last one published."""
        published = None
        failure: Exception | None = None
        eng = self._engine_fused
        if eng is not None and eng.pending_occupancy():
            # reports stopped arriving (or shutdown): force-flush the
            # fused ring so its staged windows publish instead of
            # rotting host-side — results never rot in flight, fused
            # tier included
            try:
                zones = self._fused_pending[-1][1]
                flush = eng.flush(self._packed_params(len(zones)))
                if flush is not None:
                    published = self._dispatch_fused_flush(
                        eng, flush, staged=False)
            except Exception as err:
                failure = err
        with self._pipeline_lock:
            while self._inflight:
                try:
                    published = self._publish_oldest()
                except Exception as err:
                    # a drain has no current window to recompute (empty
                    # fleet or shutdown) — abandon what's left, demote,
                    # and let the next live window run at the lower rung
                    failure = err
                    break
        if failure is not None:
            if not self._fallback_enabled:
                raise failure
            self._handle_device_failure(failure)
            # windows a failed fused flush orphaned republish at the
            # demoted tier right away (a drain has no next interval to
            # carry them); repeated failures walk the ladder like step's
            # retry loop, and the bottom rung re-raises
            while True:
                try:
                    published = self._replay_fused_pending() or published
                    break
                except Exception as err:
                    if (not self._fallback_enabled
                            or self._rung >= RUNG_NUMPY):
                        raise
                    self._handle_device_failure(err)
        return published

    # -- dispatch half ------------------------------------------------------

    def _engine_kwargs(self, **tier: Any) -> dict[str, Any]:
        return dict(backend=self._backend, model_mode=self._model_mode,
                    node_bucket=self._node_bucket,
                    workload_bucket=self._workload_bucket,
                    shrink_after=self._bucket_shrink_after, **tier)

    @staticmethod
    def _row_inputs(stored_sorted: Reports) -> list[RowInput]:
        # CONTENT identity, not delivery identity: a v2 FLAG_SAME delta
        # bumps seq but not content_seq, so an unchanged node stages zero
        # rows end to end
        return [RowInput(name=s.report.node_name, report=s.report,
                         zone_names=s.zone_names,
                         ident=((s.run, s.content_seq or s.seq)
                                if s.run and s.seq > 0 else None))
                for s in stored_sorted]

    def _packed_params(self, n_zones: int) -> Any:
        params = self._params_for_zones(n_zones)
        if params is None:
            params = np.zeros((), np.float32)  # ratio-only: unused leaf
        return params

    def _fused_engine(self) -> FusedWindowEngine:
        """Rung 0's fused-tier engine (lazy, like the packed engines).
        Runs on the FULL configured mesh — the resident block and scan
        operands are global arrays with node-axis shardings, so XLA
        shards the scan body exactly like the unfused packed program."""
        if self._engine_fused is None:
            self._engine_mesh = self.mesh
            self._engine_fused = FusedWindowEngine(
                self.mesh, **self._engine_kwargs(
                    fused_k=self._fused_window_k))
        return self._engine_fused

    def _window_step_fused(self, stored_sorted: Reports,
                           zone_names: list[str], now: float,
                           rec: WindowRecord) -> "FleetResults | None":
        """One interval at the fused tier: HOST-ONLY staging, and — on
        every K-th interval (or a forced shape-change flush) — one
        device dispatch + one batched fetch publishing all pending
        windows. Non-flush intervals return None (the ring is filling,
        same contract as a filling pipeline) and cost no device sync at
        all: that is the amortization this tier exists for."""
        engine = self._fused_engine()
        rows = self._row_inputs(stored_sorted)
        params = self._packed_params(len(zone_names))
        # snapshot BEFORE staging: if anything below fails, the ladder
        # retry recomputes THIS interval itself, so only the snapshot is
        # popped back off; EARLIER snapshots stay until their windows
        # actually publish (the zero-gaps invariant)
        self._fused_pending.append((stored_sorted, zone_names, now, rec))
        try:
            with telemetry.span("window.h2d_delta", window=rec.seq):
                _meta, flush = engine.stage(rows, zone_names, params)
            rec.assembled = _time.monotonic()
            # consulted AFTER the host staging, covering both flush and
            # accumulate intervals — a mid-scan fault abandons the ring
            # and the pending windows republish at the demoted tier
            if fault.fire("device.dispatch_error") is not None:
                raise DeviceWindowError(
                    "dispatch_error",
                    "injected dispatch failure (fused window scan)")
        except BaseException:
            self._fused_pending.pop()
            raise
        if flush is None:
            # ring filling: no device leg this interval. The per-call
            # leg stats say so honestly (the previous flush's batch
            # cost must not read as THIS interval's device time).
            with self._results_lock:
                self._stats.update(
                    last_assembly_ms=rec.ms("begin", "assembled"),
                    last_dispatch_ms=0.0, last_wait_ms=0.0,
                    last_fetch_ms=0.0, last_device_ms=0.0, last_h2d_rows=0)
            return None
        published = self._dispatch_fused_flush(engine, flush, staged=True)
        if published is not None:
            self._ladder_window_ok()
        return published

    def _dispatch_fused_flush(self, engine: FusedWindowEngine,
                              flush: FusedFlush,
                              staged: bool) -> "FleetResults | None":
        """Dispatch one fused batch, fetch ALL its outputs in one
        transfer, publish every live window oldest-first. The batch's
        whole device cost lands on its LAST window's stats sample
        (earlier windows ride free — that is the measured amortization);
        ``sync_per_window_ms`` carries the averaged per-window figure.
        ``staged``: the last window is this interval's, staged just now
        (a drain's is an earlier interval's, and has no assembly leg)."""
        seq = self._fused_pending[-1][3].seq
        t0 = _time.monotonic()
        with telemetry.span("window.fused_scan", window=seq):
            if flush.cold:
                # first dispatch of this (buckets, zones, mode, K, DB)
                # key blocks on trace + XLA compile
                with telemetry.span("window.compile", window=seq):
                    outs = engine.dispatch(flush)
            else:
                outs = engine.dispatch(flush)
        fetch_box = [0.0]

        def _materialize() -> np.ndarray:
            with telemetry.span("window.publish_fetch", window=seq):
                t_f = _time.monotonic()
                plane = np.asarray(outs)
                fetch_box[0] = (_time.monotonic() - t_f) * 1e3
            return plane

        with telemetry.span("window.pipeline_wait", window=seq):
            plane = self._fetch_device(_materialize)
        t_done = _time.monotonic()
        spw = (t_done - t0) * 1e3 / max(1, flush.k_live)
        published = None
        with self._pipeline_lock:
            for j, meta in enumerate(flush.metas):
                # each published window keeps ITS OWN interval's clock
                # (snapshotted at stage time) — staleness is visible in
                # the timestamps, exactly like pipeline-depth staleness
                _, _, w_now, rec = self._fused_pending[0]
                last = j == len(flush.metas) - 1
                if not last:
                    rec.assembled = rec.dispatched = rec.begin
                else:
                    if not staged:
                        rec.begin = rec.assembled = t0
                    rec.dispatched = t_done
                    rec.compiled = flush.cold
                published = self._publish(_Pending(
                    kind="fused", out=plane[j], meta=meta, now=w_now,
                    rec=rec,
                    h2d_rows=flush.h2d_rows if last else 0,
                    sync_per_window_ms=spw,
                    fused_fetch_ms=fetch_box[0] if last else 0.0))
                self._fused_pending.pop(0)
        return published

    def _replay_fused_pending(self) -> "FleetResults | None":
        """Republish windows ORPHANED by a fused-tier failure: the
        engine reset dropped its ring, so every remaining snapshot in
        ``_fused_pending`` is a staged-but-never-published window.
        Peek-publish-pop, oldest first — a snapshot is only popped
        after its window published, so a failure mid-replay (this
        raises; the caller demotes and retries) loses nothing. No-op
        while the fused ring is intact (its snapshots are live, not
        orphaned) or when there is nothing pending."""
        if not self._fused_pending:
            return None
        eng = self._engine_fused
        if eng is not None and eng.pending_occupancy():
            return None
        published = None
        while self._fused_pending:
            snap = self._fused_pending[0]
            published = self._window_step(*snap) or published
            self._fused_pending.pop(0)
        return published

    def _packed_engine(self, rung: int) -> PackedWindowEngine:
        """The packed engine for ``rung``: the sharded engine owns rung 0
        on a multi-device node mesh; the packed-serial rung then demotes
        to a SINGLE-device engine pinned to the mesh's first device, so
        a demoted window no longer touches the other shards' devices.
        (Which shard failed is unknowable from a mesh-wide SPMD error —
        if the pinned device is itself the dead one, this rung fails too
        and the ladder walks on to einsum and then the device-free NumPy
        rung; every interval still publishes.)"""
        if self._engine is None:
            kwargs = self._engine_kwargs(
                staging_slots=self._pipeline_depth + 1)
            if self.multihost_active() and not self._mesh_degraded:
                # the multi-host tier: host-local rings over the LIVE
                # mesh (the elastic submesh after a membership change,
                # else the full configured mesh), one SPMD dispatch,
                # owned-rows publish fetch
                mh_mesh = self._live_mesh()
                self._engine_mesh = mh_mesh
                self._shard_count = mh_mesh.devices.size
                self._engine = MultiHostWindowEngine(
                    mh_mesh,
                    process_index=self._mh_process_index,
                    device_process=self._mh_device_process,
                    fabric=self._mh_fabric, **kwargs)
            else:
                mesh = self.mesh
                if self._multihost_enabled and self._mesh_degraded:
                    # "mesh minus one host": the survivors' own devices
                    mesh = self._local_mesh()
                self._engine_mesh = mesh
                self._shard_count = self._mesh_shard_count(mesh)
                cls = (ShardedWindowEngine if self._shard_count > 1
                       else PackedWindowEngine)
                self._engine = cls(mesh, **kwargs)
        if rung == RUNG_PIPELINED or self._shard_count == 1:
            return self._engine
        if self._engine_serial is None:
            base = self._engine_mesh or self.mesh
            self._engine_serial = PackedWindowEngine(
                make_mesh([1], devices=[base.devices.flat[0]]),
                **self._engine_kwargs(
                    staging_slots=self._pipeline_depth + 1))
        return self._engine_serial

    def _dispatch_packed(self, stored_sorted: Reports, zone_names: list[str],
                         now: float, rec: WindowRecord,
                         rung: int = RUNG_PIPELINED) -> _Pending:
        """Sync the device-resident packed batch (delta H2D) and dispatch
        the packed-f16 program asynchronously."""
        engine = self._packed_engine(rung)
        rows = self._row_inputs(stored_sorted)
        params = self._packed_params(len(zone_names))
        with telemetry.span("window.h2d_delta", window=rec.seq):
            plan = engine.plan_window(rows, zone_names, params)
        rec.assembled = _time.monotonic()
        # consulted AFTER the donated ring update ran: a dispatch that
        # dies here leaves a consumed donated buffer behind — exactly the
        # poisoned-ring state the ladder's reset() re-seed exists for
        if fault.fire("device.dispatch_error") is not None:
            raise DeviceWindowError(
                "dispatch_error",
                "injected dispatch failure (packed window program)")
        if plan.cold:
            # first dispatch of this (buckets, zones, mode) key: the call
            # blocks on trace+XLA-compile; execution itself stays async
            with telemetry.span("window.compile", window=rec.seq):
                out = plan.program(*plan.args)
        else:
            out = plan.program(*plan.args)
        copy_async = getattr(out, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()  # D2H queues behind the compute, off the host
        rec.dispatched = _time.monotonic()
        rec.compiled = plan.cold
        return _Pending(
            kind="packed", out=out, meta=plan.meta, now=now, rec=rec,
            h2d_rows=plan.h2d_rows,
            h2d_shards=plan.h2d_shards, shards=plan.n_shards,
            fetch=plan.fetch)

    def _assemble(self, stored_sorted: Reports,
                  zone_names: list[str]) -> tuple[list[NodeReport], Any]:
        """→ (the reports in batch order, the padded host batch)."""
        aligned = [s.report for s in stored_sorted]
        zd_mat, zv_mat = align_zone_matrices(
            aligned, [s.zone_names for s in stored_sorted], zone_names)
        return aligned, assemble_fleet_batch(
            aligned, n_zones=len(zone_names), node_bucket=self._node_bucket,
            workload_bucket=self._workload_bucket,
            zone_deltas_mat=zd_mat, zone_valid_mat=zv_mat)

    def _dispatch_legacy(self, stored_sorted: Reports, zone_names: list[str],
                         now: float, rec: WindowRecord) -> _Pending:
        """Serial-path dispatch: full assemble, one big H2D, the sharded
        einsum/temporal program, async output copies. Every leg is a
        span with the window's id that lies on two marks of its record
        (the batch leg starts at the snapshot's end, so it also holds the
        autoscale observation and the sort of the reports)."""
        temporal = self._model_mode == "temporal"
        with rec.leg("window.batch"):
            n_zones = len(zone_names)
            aligned, batch = self._assemble(stored_sorted, zone_names)
            cold = self._program is None
            if cold:
                if fault.fire("device.compile_error") is not None:
                    raise DeviceWindowError(
                        "compile_error",
                        "injected compile failure (serial fleet program)")
                if temporal:
                    # in both forms: the history goes up compact wherever
                    # that is fewer rows, dense where it is not
                    self._program = {
                        compact: make_temporal_fleet_program(
                            self.mesh, backend=self._backend,
                            accuracy_mode=self._accuracy_mode,
                            compact=compact)
                        for compact in (False, True)}
                else:
                    self._program = make_fleet_program(
                        self.mesh, model_mode=self._model_mode,
                        backend=self._backend,
                        accuracy_mode=self._accuracy_mode)
            program = self._program
            params = self._params_on_mesh(n_zones)
        feat_hist = t_valid = None
        # the loop thread's CPU time is read inside the wall-clock leg, so
        # that wall − CPU (time off the processor) cannot come out negative
        if temporal:
            with rec.leg("window.history"):
                feat_hist, t_valid = self._history_windows(batch)
                cpu_end_ns = _time.thread_time_ns()
        else:
            cpu_end_ns = _time.thread_time_ns()
            rec.assembled = rec.batch
        rec.assembly_cpu_s = (cpu_end_ns - rec.cpu_begin_ns) / 1e9
        if fault.fire("device.dispatch_error") is not None:
            raise DeviceWindowError(
                "dispatch_error",
                "injected dispatch failure (serial fleet program)")
        # every device is sent its own nodes' rows, and nothing else
        rec.devices = int(self.mesh.devices.size)
        # and of the history only the rows that hold a tick: the leg is the
        # compaction on the host and the put's return (the bytes land later)
        with rec.leg("window.h2d", devices=rec.devices):
            history = (feat_hist, t_valid)
            rows = None
            if temporal:
                spare = self._history_spare
                rows = compact_history(feat_hist, t_valid,
                                       self.mesh.shape[NODE_AXIS],
                                       self._history_rows.fit,
                                       out=spare.pop() if spare else None)
                program = program[rows is not None]
                history = rows or history
            args = put_fleet_batch(batch, params, *history, mesh=self.mesh)
        # ASYNC dispatch: jax returns device futures at once, and the D2H
        # copies start NOW (queued behind the compute on the device
        # stream), not at _publish's np.asarray. The FIRST dispatch blocks
        # on trace + XLA compile: the window.compile stage (later per-shape
        # recompiles hide inside jax's own cache; the packed path's keyed
        # program cache counts its own exactly)
        with rec.leg("window.dispatch"):
            if cold:
                with telemetry.span("window.compile", window=rec.seq):
                    result = program(*args)
                self._legacy_compiles += 1
            else:
                result = program(*args)
            for arr in (result.node_power_uw, result.node_energy_uj,
                        result.workload_power_uw,
                        result.workload_energy_uj):
                copy_async = getattr(arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        # the counts, after the last mark: they are on no gauge's clock
        rec.compiled = cold
        rec.rows_program = batch.cpu_deltas.size
        if self._model_mode:
            counts = np.asarray(batch.workload_counts)
            rec.rows_work = int(counts[
                batch.mode[:len(counts)] == MODE_MODEL].sum())
        if temporal:  # S · R; of the dense window, N · W: the estimator's
            rec.hist_rows_sent = rec.rows_program = math.prod(
                args[9].shape[:2])
            if fused_trunk_chosen(self.mesh.devices.flat[0].platform,
                                  self._accuracy_mode):
                rec.rows_fused = rec.rows_program
        rec.h2d_bytes = sum(int(a.nbytes) for a in args[1:])
        # a NamedSharding's shards are all of one shape, so the device
        # that was sent most was sent one shard of every argument
        rec.h2d_bytes_max_device = sum(
            math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
            for a in args[1:])
        return _Pending(
            kind="legacy", out=result, meta=None, now=now, rec=rec,
            h2d_rows=batch.n_nodes,
            batch=batch, aligned=aligned, zone_names=zone_names,
            feat_hist=feat_hist, t_valid=t_valid, history_rows=rows)

    def _dispatch_numpy(self, stored_sorted: Reports, zone_names: list[str],
                        now: float, rec: WindowRecord) -> _Pending:
        """Bottom ladder rung: the whole window in host NumPy — no jax,
        no device, no compile. Ratio attribution is exact; model rows are
        served for the NumPy-mirrored estimators (linear, mlp) when the
        trained params fit this window's zone axis, and publish zero
        watts otherwise (``parallel.packed.numpy_fleet_window``). Output
        reuses the packed scatter path, so publication is identical to
        the device rungs' minus the f16 wire quantization."""
        from kepler_tpu.parallel.packed import (numpy_fleet_window,
                                                pack_fleet_inputs)

        n_zones = len(zone_names)
        aligned, batch = self._assemble(stored_sorted, zone_names)
        packed = pack_fleet_inputs(batch)
        rec.assembled = _time.monotonic()
        params = None
        if (self._model_mode in ("linear", "mlp")
                and self._params is not None
                and self._model_out_dim() == n_zones):
            params = self._params
        watts = numpy_fleet_window(packed, batch.cpu_deltas.shape[1],
                                   n_zones, params, self._model_mode)
        rec.dispatched = _time.monotonic()
        n_real = batch.n_nodes
        names = list(batch.node_names[:n_real])
        meta = WindowMeta(
            zones=list(zone_names),
            names=names,
            rows={name: i for i, name in enumerate(names)},
            mode=np.asarray(batch.mode, np.int32),
            dt=np.asarray(batch.dt_s, np.float32),
            counts=list(batch.workload_counts),
            ids=list(batch.workload_ids),
            kinds=([a.workload_kinds for a in aligned]
                   + [None] * (watts.shape[0] - n_real)),
            n_live=n_real,
            n_rows=watts.shape[0],
        )
        return _Pending(
            kind="numpy", out=watts, meta=meta, now=now, rec=rec,
            h2d_rows=0)

    # -- publish half -------------------------------------------------------

    # keplint: requires-lock=_pipeline_lock
    def _publish(self, p: _Pending, on_loop: bool = True) -> "FleetResults":
        """Fetch one in-flight window (the pipeline's only blocking point),
        scatter it into a :class:`FleetResults`, publish, account legs.
        Holding the pipeline lock keeps the publisher thread, the loop's
        own step and a lifecycle-thread drain from interleaving publishes
        (out-of-order ``_results``). ``on_loop`` is False on the publisher
        thread, which reads no engine state: the loop is planning the
        next window on the engines meanwhile."""
        rec = p.rec
        seq = rec.seq
        rec.kind = p.kind
        rec.publish_begin = _time.monotonic()
        # no later window has taken its sequence number: this one did not
        # wait for the loop to come round again
        rec.published_early = int(self._window_seq == seq + 1)
        fetch_ms = 0.0
        if p.kind == "packed":
            # the engine's plan may override the fetch (per-shard
            # addressable materialization; owned shards only on the
            # multi-host engine — publish cost scales with owned rows)
            fetch_fn = p.fetch or np.asarray

            def _materialize() -> np.ndarray:
                with telemetry.span("window.publish_fetch", window=seq):
                    t_f = _time.monotonic()
                    plane = fetch_fn(p.out)
                    nonlocal_box[0] = (_time.monotonic() - t_f) * 1e3
                return plane

            nonlocal_box = [0.0]
            with rec.leg("window.pipeline_wait"):
                packed = self._fetch_device(_materialize)
            fetch_ms = nonlocal_box[0]
            results = self._scatter_packed(p, packed)
        elif p.kind in ("numpy", "fused"):
            # p.out is already a host array: no fetch, and no stall site
            # to consult (there is no device leg to hang). A fused window
            # looks the same by now: the flush fetched the K-batch in one
            # transfer and sliced this plane out (cost: fused_fetch_ms)
            rec.fetched = _time.monotonic()
            fetch_ms = p.fused_fetch_ms
            results = self._scatter_packed(p, p.out)
        else:
            result = p.out
            # np.asarray of a node-sharded result copies each device's
            # rows from that device into their place in one host array:
            # a shard at a time, nothing gathered on a device
            with rec.leg("window.pipeline_wait", devices=rec.devices):
                fetched = self._fetch_device(lambda: (
                    np.asarray(result.node_power_uw),
                    np.asarray(result.node_energy_uj),
                    np.asarray(result.workload_power_uw),
                    np.asarray(result.workload_energy_uj)))
            node_power, node_energy, wl_power, wl_energy = fetched
            if p.history_rows is not None:
                # the program has run: the device is done with its inputs
                self._history_spare.append(p.history_rows)
            with rec.leg("window.scatter"):
                results = self._scatter_legacy(p, node_power, node_energy,
                                               wl_power, wl_energy)
        if rec.scattered is None:  # the packed scatter is no leg
            rec.scattered = _time.monotonic()
        assembly_ms = rec.ms("begin", "assembled")
        dispatch_ms = rec.ms("assembled", "dispatched")
        wait_ms = rec.ms("publish_begin", "fetched")
        scatter_ms = rec.ms("fetched", "scattered")
        n_workloads = sum(results.counts)
        with TraceAnnotation("window.publish",
                             window=seq), self._results_lock:
            self._results = results
            self._last_window_at = p.now
            self._stats["attributions_total"] += 1
            self._stats["published_early_total"] += rec.published_early
            self._stats.update(
                last_batch_nodes=len(results.names),
                last_batch_workloads=int(n_workloads),
                last_assembly_ms=assembly_ms, last_dispatch_ms=dispatch_ms,
                last_wait_ms=wait_ms, last_fetch_ms=fetch_ms,
                last_device_ms=dispatch_ms + wait_ms,
                last_scatter_ms=scatter_ms,
                last_attribution_ms=(assembly_ms + dispatch_ms + wait_ms
                                     + scatter_ms),
                last_h2d_rows=p.h2d_rows,
                last_h2d_device_bytes=rec.h2d_bytes_max_device,
                window_shards=p.shards, last_h2d_shards=list(p.h2d_shards))
            if p.sync_per_window_ms >= 0.0:
                self._stats["last_sync_per_window_ms"] = (
                    p.sync_per_window_ms)
            if on_loop:
                self._engine_stats_locked()
            # the record is complete once the results are stored (they
            # are visible when this lock is released, a moment later)
            rec.published = _time.monotonic()
            self._window_ledger.add(rec)
        # the two legs no with-block covers: how long the dispatched
        # window waited for its publication to begin (for the publisher to
        # have the lock; without one, for the next step's dispatch), and
        # the lock section that made the results visible
        telemetry.mark_span("window.queued", rec.dispatched,
                            rec.publish_begin, window=seq)
        telemetry.mark_span("window.publish", rec.scattered, rec.published,
                            window=seq)
        log.debug("fleet attribution: %d nodes, %d workloads, %.2f ms "
                  "(h2d rows %d)", len(results.names), n_workloads,
                  self._stats["last_attribution_ms"], p.h2d_rows)
        if p.kind == "legacy" and self._dump_dir:
            # AFTER results publication — file I/O must not delay /v1/results
            try:
                self._dump_training_window(p.batch, wl_power, p.zone_names,
                                           p.now, p.feat_hist, p.t_valid)
            except OSError as err:
                log.warning("training dump failed: %s", err)
        return results

    # keplint: requires-lock=_results_lock
    def _engine_stats_locked(self) -> None:
        """The engines' compile count and introspection snapshot, taken
        on the aggregation loop (the only thread that owns engine state)
        so /debug/window and collect() read a coherent copy off-thread
        without touching live engine internals."""
        # the engines' program caches count their own compiles; the
        # serial path's one program is counted at its cold dispatch
        self._stats["window_compiles_total"] = (
            self._legacy_compiles + sum(
                e.compile_count for e in (
                    self._engine, self._engine_serial,
                    self._engine_fused) if e is not None))
        engines: dict[str, dict] = {}
        for label, eng in (("pipelined", self._engine),
                           ("serial", self._engine_serial),
                           ("fused", self._engine_fused)):
            if eng is not None:
                engines[label] = eng.introspect()
        primary = _primary_introspect(engines)
        skew = 0.0
        if primary is not None:
            occupied = [s["rows"] for s in primary["shards"]]
            if any(occupied):
                skew = max(occupied) / (sum(occupied) / len(occupied))
        self._stats["shard_skew"] = round(skew, 4)
        self._introspect_cache = engines

    def _scatter_packed(self, p: _Pending,
                        packed: np.ndarray) -> "FleetResults":
        """One f16 D2H array → the published column-oriented results,
        indexed by RESIDENT ROW (``results.rows`` maps names to rows; free
        rows hold zeros). Node energy is power × dt: exact for ratio nodes
        (their power was measured energy / dt), definitional for model
        nodes, modulo the f16 watt quantization budgeted at ≤ 0.5%."""
        from kepler_tpu.parallel.packed import unpack_fleet_window

        m = p.meta
        wl_watts, _active_w, total_w = unpack_fleet_window(packed)
        node_power = np.multiply(total_w, 1e6, dtype=np.float32)  # W → µW
        node_energy = node_power * m.dt[:, None]  # µW·s = µJ
        row_idx = np.asarray([m.rows[name] for name in m.names],
                             np.intp)
        joules = np.zeros_like(node_power)
        if row_idx.size:
            joules[row_idx] = self._accumulate_node_energy(
                m.names, m.zones, node_energy[row_idx], p.now)
        return FleetResults(
            timestamp=p.now,
            zones=m.zones,
            names=m.names,
            rows=m.rows,
            mode=m.mode,
            node_power_uw=node_power,
            node_energy_uj=node_energy,
            node_joules_total=joules,
            workload_ids=m.ids,
            workload_kinds=m.kinds,
            counts=m.counts,
            wl_watts_f16=wl_watts,
            dt=m.dt,
        )

    def _scatter_legacy(self, p: _Pending, node_power: np.ndarray,
                        node_energy: np.ndarray, wl_power: np.ndarray,
                        wl_energy: np.ndarray) -> "FleetResults":
        """Dense-layout scatter: per-node array views published as-is;
        JSON materializes lazily in ``/v1/results`` (VERDICT r3 weak #3:
        the old per-workload dict scatter was O(nodes × workloads)
        Python per window)."""
        batch = p.batch
        n_real = batch.n_nodes
        names = batch.node_names[:n_real]
        joules = self._accumulate_node_energy(names, p.zone_names,
                                              node_energy[:n_real], p.now)
        return FleetResults(
            timestamp=p.now,
            zones=p.zone_names,  # shared ref; treated immutable
            names=names,
            rows={name: i for i, name in enumerate(names)},
            mode=batch.mode,
            node_power_uw=node_power,
            node_energy_uj=node_energy,
            node_joules_total=joules,
            workload_ids=batch.workload_ids,
            workload_kinds=[a.workload_kinds for a in p.aligned],
            counts=batch.workload_counts,
            wl_power_uw=wl_power,
            wl_energy_uj=wl_energy,
        )

    def _accumulate_node_energy(self, names: list[str],
                                zone_names: list[str],
                                node_energy: np.ndarray,
                                now: float) -> np.ndarray:
        """store[names] += node_energy → cumulative joules [n, Z].

        Steady state (same fleet, same zone axis) is one cached gather,
        one add, one scatter (RowStore). A zone-axis change remaps the
        store's columns by name; new nodes allocate (or reuse) rows."""
        if self._cum_zones != zone_names:
            self._cum.remap_columns(self._cum_zones, zone_names)
            self._cum_zones = list(zone_names)
        vals = self._cum.accumulate(tuple(names), node_energy)
        last_seen = self._cum_last_seen
        for name in names:
            last_seen[name] = now
        return vals / 1e6

    def _params_for_zones(self, n_zones: int) -> Any:
        """Trained params when their output dim matches the canonical zone
        axis this window; otherwise a cached untrained fallback — the
        trained params are kept, so a transient zone-set change (one node
        reporting an extra zone) doesn't destroy them."""
        if not self._model_mode:
            return None
        if self._params is not None and self._model_out_dim() == n_zones:
            return self._params
        fallback = self._fallback_params.get(n_zones)
        if fallback is None:
            import jax

            from kepler_tpu.models.estimator import initializer
            log.warning("model output dim %s != fleet zones %d; using "
                        "untrained %s fallback for this window",
                        self._model_out_dim(), n_zones, self._model_mode)
            kwargs = {}
            if self._model_mode == "temporal":
                # the fallback's positional table must cover the window
                kwargs["t_max"] = max(128, self._history_window)
            fallback = initializer(self._model_mode)(
                jax.random.PRNGKey(0), n_zones=n_zones, **kwargs)
            self._fallback_params[n_zones] = fallback
        return fallback

    def _params_on_mesh(self, n_zones: int) -> Any:
        """:meth:`_params_for_zones` replicated over the mesh, placed once
        per params object and kept: the serial program finds them on
        every device and no window sends them again."""
        params = self._params_for_zones(n_zones)
        if params is None:
            return None
        held = self._params_placed
        if held is None or held[0] is not params:
            import jax

            replicated, _by_node = fleet_shardings(self.mesh)
            held = self._params_placed = (
                params, jax.device_put(params, replicated))
        return held[1]

    def _dump_training_window(self, batch: Any, wl_power_uw: np.ndarray,
                              zone_names: list[str], now: float,
                              feat_hist: np.ndarray | None = None,
                              t_valid: np.ndarray | None = None) -> None:
        """Write one training file: RAPL rows' inputs + their ratio watts.

        Only MODE_RATIO rows carry trustworthy labels (the estimator's own
        output would be circular); rows keep the padded [n, W] layout with
        ``workload_valid`` masking. The file records its OWN zone axis
        (``zone_names``) and per-row ``zone_valid``: the zone union varies
        across rounds, so cmd/train aligns columns by name and masks zones
        a node didn't report (0-watt rows there are absence, not labels).
        In temporal mode the ratio rows' feature-HISTORY windows
        ([n, W, T, F] + t_valid) are saved too, for ``cmd/train --model
        temporal``. Oldest files beyond the cap are pruned."""
        import os

        ratio_rows = np.flatnonzero(
            (np.asarray(batch.mode[:batch.n_nodes]) != MODE_MODEL))
        if ratio_rows.size == 0:
            return
        os.makedirs(self._dump_dir, exist_ok=True)
        self._dump_seq += 1
        path = os.path.join(
            self._dump_dir, f"window-{int(now * 1e3):014d}-"
            f"{self._dump_seq:06d}.npz")
        r = ratio_rows
        arrays = dict(
            zone_names=np.asarray(zone_names),
            zone_valid=batch.zone_valid[r],
            cpu_deltas=batch.cpu_deltas[r],
            workload_valid=batch.workload_valid[r],
            node_cpu_delta=batch.node_cpu_delta[r],
            usage_ratio=batch.usage_ratio[r],
            dt_s=batch.dt_s[r],
            target_watts=wl_power_uw[r] / 1e6,  # labels in watts
        )
        if feat_hist is not None:
            arrays["feat_hist"] = feat_hist[r]
            arrays["t_valid"] = t_valid[r]
        np.savez_compressed(path, **arrays)
        # prune via an in-process ledger (seeded from disk once) — no
        # per-dump directory scan
        if self._dump_files is None:
            self._dump_files = sorted(
                os.path.join(self._dump_dir, f)
                for f in os.listdir(self._dump_dir)
                if f.startswith("window-") and f.endswith(".npz"))
        else:
            self._dump_files.append(path)
        while len(self._dump_files) > self._dump_max_files:
            try:
                os.unlink(self._dump_files.pop(0))
            except OSError:
                pass

    def _check_params_shape(self) -> None:
        """Fail at startup (not first window) on params/model mismatch."""
        if self._model_mode not in _REQUIRED_PARAM_KEYS:
            raise ValueError(
                f"unknown aggregator model {self._model_mode!r}; valid: "
                f"{', '.join(_REQUIRED_PARAM_KEYS)}")
        if self._params is None:
            return
        required = _REQUIRED_PARAM_KEYS[self._model_mode]
        missing = [k for k in required if k not in self._params]
        if missing:
            raise ValueError(
                f"params are missing {missing} for model "
                f"{self._model_mode!r} — were they saved from a different "
                "model kind?")
        # the input projection's feature axis must match THIS build's
        # feature vector — a checkpoint trained before a feature-set change
        # (e.g. F 6→7, node_cpu_log) must fail HERE, not as an XLA shape
        # error inside the first window's jit
        from kepler_tpu.models.features import NUM_FEATURES

        in_key, f_axis = {"mlp": ("w0", 0), "linear": ("weight", 0),
                          "moe": ("w0", 1), "deep": ("in_proj", 0),
                          "temporal": ("in_proj", 0)}[self._model_mode]
        got_f = int(np.asarray(self._params[in_key]).shape[f_axis])
        if got_f != NUM_FEATURES:
            raise ValueError(
                f"params' {in_key} has feature dim {got_f} but this build's "
                f"feature vector is F={NUM_FEATURES} — the checkpoint "
                "predates a feature-set change; retrain it "
                "(models.features.build_features documents the vector)")
        if self._model_mode == "temporal":
            t_max = int(np.asarray(self._params["pos_emb"]).shape[0])
            if t_max < self._history_window:
                raise ValueError(
                    f"temporal params were trained with t_max={t_max} < "
                    f"aggregator.historyWindow={self._history_window} — "
                    "shrink the window or retrain with a longer t_max")

    def _model_out_dim(self) -> int | None:
        if self._params is None:
            return None
        # the mode's output bias — its LAST axis length is Z (moe's b1 is
        # [E, Z], so probing by key alone would confuse it with mlp's b1)
        key = _OUTPUT_BIAS_KEY.get(self._model_mode)
        if key is None or key not in self._params:
            return None
        return int(np.asarray(self._params[key]).shape[-1])

    # -- read side -----------------------------------------------------------

    def results(self) -> "FleetResults | None":
        """The latest published window (swapped wholesale: safe to read)."""
        with self._results_lock:
            return self._results

    def stats(self) -> dict[str, Any]:
        """A copy of the window's stats."""
        with self._results_lock:
            return dict(self._stats)

    def last_window_at(self) -> float | None:
        with self._results_lock:
            return self._last_window_at

    def rung_timeline(self) -> tuple[int, list[dict]]:
        """→ (the current rung, every kept ladder transition)."""
        with self._results_lock:
            return self._rung, list(self._rung_timeline)

    def debug(self) -> tuple[dict[str, Any], list[WindowRecord]]:
        """The window's part of ``GET /debug/window``, the device plane's
        flight recorder: rung + transition timeline, shard layout, bucket
        ladders, compile-cache keys with their cost stats, last H2D per
        shard, sticky-map skew (engine state from the per-window
        introspection snapshot: no live engine access). → (the body up to
        ``counts``, the windows' sums since start; the last complete
        window records, to be rendered outside the lock)."""
        with self._results_lock:
            payload: dict = {
                **self.device_fields(),
                "rung": self._rung,
                "rung_name": self._rung_display(self._rung),
                "shards": (self._shard_count
                           if self._rung == RUNG_PIPELINED else 1),
                "windows_at_rung": self._windows_at_rung,
                "windows_since_last_failure": self._windows_since_failure,
                "fallback_enabled": self._fallback_enabled,
                "probe_backoff": self._probe_penalty,
                "timeline": list(self._rung_timeline),
                "demotions_by_reason": dict(self._demotions_by_reason),
                "engines": self._introspect_cache,
                "stats": {k: self._stats[k] for k in (
                    "last_assembly_ms", "last_dispatch_ms",
                    "last_wait_ms", "last_fetch_ms",
                    "last_sync_per_window_ms", "last_scatter_ms",
                    "last_attribution_ms", "last_h2d_rows",
                    "last_h2d_device_bytes", "last_h2d_shards",
                    "window_shards", "shard_skew",
                    "window_compiles_total", "window_rung",
                    "window_demotions_total",
                    "window_repromotions_total", "last_batch_nodes",
                    "last_batch_workloads")},
            }
            if self._fused_window_k > 1:
                eng = self._engine_fused
                payload["fused"] = {
                    "k": self._fused_window_k,
                    "active": self._fused_tier_active(),
                    "degraded": self._fused_degraded,
                    "pending_windows": len(self._fused_pending),
                    "ring_occupancy": (eng.pending_occupancy()
                                       if eng is not None else 0),
                }
            if self._last_window_failure:
                payload["last_failure"] = self._last_window_failure
            records, payload["counts"] = self._window_ledger.snapshot()
        return payload, records

    def collect(self) -> "Iterator[Any]":
        """The window's ``kepler_fleet_*`` families, in scrape order (the
        scrape ends on the per-node ones: :meth:`collect_nodes`)."""
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )
        with self._results_lock:
            stats = dict(self._stats)
            demotions_snap = sorted(self._demotions_by_reason.items())
            # replaced wholesale per published window; nested dicts are
            # never mutated after construction, so reading out is safe
            introspect_snap = self._introspect_cache

        def scalar(key: str) -> Any:
            kind, name, doc = _SCALAR_FAMILIES[key]
            family = (CounterMetricFamily if kind == "counter"
                      else GaugeMetricFamily)(name, doc)
            family.add_metric([], stats[key])
            return family

        yield scalar("last_batch_nodes")
        yield scalar("last_batch_workloads")
        yield scalar("last_attribution_ms")
        legs = GaugeMetricFamily(
            "kepler_fleet_window_leg_ms",
            "Last fleet window's latency by leg (device = dispatch + "
            "pipeline wait; assembly includes the delta-H2D staging)",
            labels=["leg"])
        for leg in ("assembly", "device", "dispatch", "wait", "scatter"):
            legs.add_metric([leg], stats[f"last_{leg}_ms"])
        yield legs
        yield scalar("last_h2d_rows")
        yield scalar("last_h2d_device_bytes")
        yield scalar("last_fetch_ms")
        yield scalar("last_sync_per_window_ms")
        yield scalar("window_shards")
        yield scalar("shard_skew")
        primary = _primary_introspect(introspect_snap)
        shard_rows = GaugeMetricFamily(
            "kepler_fleet_window_shard_rows",
            "Resident-row occupancy per device shard, split by row mode "
            "(shard-count-bounded cardinality)", labels=["shard", "mode"])
        if primary is not None:
            for k, occ in enumerate(primary["shards"]):
                shard_rows.add_metric([str(k), "model"],
                                      occ["model_rows"])
                shard_rows.add_metric([str(k), "ratio"],
                                      occ["rows"] - occ["model_rows"])
        yield shard_rows
        h2d_by_shard = GaugeMetricFamily(
            "kepler_fleet_window_shard_h2d_rows",
            "Rows staged + uploaded per device shard for the last fleet "
            "window (delta H2D; a hot shard here means churn is landing "
            "unevenly)", labels=["shard"])
        for k, n in enumerate(stats["last_h2d_shards"]):
            h2d_by_shard.add_metric([str(k)], n)
        yield h2d_by_shard
        staleness = GaugeMetricFamily(
            "kepler_fleet_window_buffer_staleness_windows",
            "Windows since each ping-pong ring slot last served (0 = served "
            "the latest window; a slot stuck high means the donation "
            "rotation is wedged)", labels=["slot"])
        if primary is not None:
            for slot, age in enumerate(
                    primary["resident"]["staleness_windows"]):
                staleness.add_metric([str(slot)], age)
        yield staleness
        programs = {
            "flops": GaugeMetricFamily(
                "kepler_fleet_window_program_flops",
                "XLA cost_analysis FLOPs of each cached fleet-window program "
                "(captured at cold compile; label cardinality bounded by "
                "the compile-cache cap)", labels=["program"]),
            "bytes_accessed": GaugeMetricFamily(
                "kepler_fleet_window_program_bytes",
                "XLA cost_analysis bytes accessed per execution of each "
                "cached fleet-window program", labels=["program"]),
            "device_memory_bytes": GaugeMetricFamily(
                "kepler_fleet_window_program_device_memory_bytes",
                "XLA memory_analysis device footprint (arguments + outputs + "
                "temps + generated code) of each cached fleet-window "
                "program", labels=["program"]),
        }
        seen_programs: set[str] = set()
        for eng in introspect_snap.values():
            prog_lists = [eng.get(kind, ())
                          for kind in ("programs", "updates")]
            fused_sub = eng.get("fused")
            if fused_sub:
                prog_lists.append(fused_sub.get("programs", ()))
            for prog in (p for progs in prog_lists for p in progs):
                cost = prog.get("cost")
                if (not cost or "flops" not in cost
                        or cost["label"] in seen_programs):
                    continue  # (the serial engine mirrors a key)
                seen_programs.add(cost["label"])
                for field, family in programs.items():
                    if field in cost:
                        family.add_metric([cost["label"]], cost[field])
        yield from programs.values()
        yield scalar("window_compiles_total")
        yield scalar("window_rung")
        demotions = CounterMetricFamily(
            "kepler_fleet_window_demotions_total",
            "Window device-leg ladder demotions, by failure reason",
            labels=["reason"])
        for reason, count in demotions_snap:
            demotions.add_metric([reason], count)
        yield demotions
        yield scalar("window_repromotions_total")
        yield scalar("attributions_total")
        yield scalar("published_early_total")

    def collect_nodes(self) -> "Iterator[Any]":
        """Per-node power and cumulative energy of the latest results."""
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )
        results = self.results()
        node_watts = GaugeMetricFamily(
            "kepler_fleet_node_cpu_watts",
            "Per-node power attributed by the fleet aggregator",
            labels=["node_name", "zone", "mode"])
        node_joules = CounterMetricFamily(
            "kepler_fleet_node_cpu_joules_total",
            "Per-node cumulative energy seen by the fleet aggregator",
            labels=["node_name", "zone", "mode"])
        if results is not None:
            zones = results.zones
            for name in results.names:
                # rows map, not enumerate: the packed-resident layout
                # keeps nodes at stable row indices with holes
                i = results.rows[name]
                mode = "model" if results.mode[i] else "ratio"
                power = results.node_power_uw[i]
                joules = results.node_joules_total[i]
                for j, zone in enumerate(zones):
                    node_watts.add_metric([name, zone, mode],
                                          float(power[j]) / 1e6)
                    node_joules.add_metric([name, zone, mode],
                                           float(joules[j]))
        yield node_watts
        yield node_joules
