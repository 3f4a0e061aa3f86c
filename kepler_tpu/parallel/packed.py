"""Packed-transfer fleet attribution: one H2D, one dispatch, one D2H.

Motivation: every host↔device transfer and every dispatch pays a fixed
cost (on the local chip: not measured), and a step that moves 9 input
arrays and 2 outputs pays it eleven times for ~0.1 ms of compute. This
module packs the whole fleet window into ONE f32 input array and the whole
scatter-back payload into ONE f16 output array:

  input  [N, W + 2Z + 4]  — cpu | zone | zone_valid | ratio, denom, dt, mode
  output [N, W + 2, Z]    — per-workload watts, with node ACTIVE watts and
                            node TOTAL watts as the two extra rows (f16:
                            watts stay well inside half range and carry
                            ~0.05% error, inside the 0.5%-of-RAPL budget;
                            µW or µJ would overflow)

The unpack/slice lives inside the jitted program, so XLA fuses it with the
attribution math and the device sees exactly one executable.

Sparse model evaluation (``model_bucket``): mixed fleets evaluate BOTH
paths for every node in the dense program ("cheaper than a branch on
TPU"), but the estimator is the whole device leg at fleet shapes — an MLP
forward over [N·W] rows whose output is discarded for every MODE_RATIO
node. The sparse variant takes an extra ``model_rows`` index vector
(padded with N — gather clamps, scatter drops) and runs the estimator
only on the gathered MODE_MODEL rows: bit-identical outputs at half the
FLOPs on a 50/50 fleet. The row-index gather has no shard_map story, so
the sparse variant is einsum-backend only; pallas keeps the dense
program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kepler_tpu.parallel.aggregator_core import (
    FleetResult,
    fleet_attribution_program,
    mix_model_watts,
    resolve_attribute_fn,
    shard_by_node,
)
from kepler_tpu.parallel.fleet import MODE_MODEL, FleetBatch, NodeReport
from kepler_tpu.parallel.mesh import NODE_AXIS
from kepler_tpu.models.estimator import predictor

# packed output layout: the two synthetic rows appended after the W
# workload rows (kept as named offsets so unpackers and the window
# engine agree by construction)
ROW_NODE_ACTIVE = -2
ROW_NODE_TOTAL = -1


# keplint: layout-definition
@dataclass(frozen=True)
class PackedLayout:
    """THE packed input-row layout — the single source of truth.

    One f32 row is ``cpu[W] | zone[Z] | zone_valid[Z] | ratio, denom,
    dt, mode``. Every producer and consumer of packed rows — the jitted
    device programs here, the ``fleet.window`` staging engines, and the
    pure-NumPy rung-3 mirror (:func:`numpy_fleet_window`) — derives its
    offsets from this class, so the jax program and its host fallback
    cannot drift apart silently. Raw layout-offset arithmetic anywhere
    outside this class is a keplint finding (KTL114 ``packed-layout``);
    this is the only ``layout-definition``-marked scope.
    """

    n_workloads: int
    n_zones: int

    @property
    def width(self) -> int:
        """Total packed row width."""
        return self.n_workloads + 2 * self.n_zones + 4

    @property
    def cpu(self) -> slice:
        """Per-workload cpu-delta columns (NaN = invalid slot)."""
        return slice(0, self.n_workloads)

    @property
    def zone(self) -> slice:
        """Per-zone energy-delta columns (µJ)."""
        return slice(self.n_workloads, self.n_workloads + self.n_zones)

    @property
    def zone_valid(self) -> slice:
        """Per-zone validity columns (0.0/1.0)."""
        return slice(self.n_workloads + self.n_zones,
                     self.n_workloads + 2 * self.n_zones)

    @property
    def col_ratio(self) -> int:
        return self.n_workloads + 2 * self.n_zones + 0

    @property
    def col_denom(self) -> int:
        return self.n_workloads + 2 * self.n_zones + 1

    @property
    def col_dt(self) -> int:
        return self.n_workloads + 2 * self.n_zones + 2

    @property
    def col_mode(self) -> int:
        return self.n_workloads + 2 * self.n_zones + 3

    def empty_row(self) -> np.ndarray:
        """One packed row holding no node: zeros, cpu columns NaN (no
        valid workload slots) — what cleared resident rows scatter."""
        row = np.zeros(self.width, np.float32)
        row[self.cpu] = np.nan
        return row


def packed_width(n_workloads: int, n_zones: int) -> int:
    """Row width of the packed INPUT layout."""
    return PackedLayout(n_workloads, n_zones).width


def pack_fleet_inputs(batch: FleetBatch,
                      out: np.ndarray | None = None) -> np.ndarray:
    """FleetBatch → one f32 [N, W + 2Z + 4] host array (one H2D).

    ``out``: optional preallocated destination (the window engine's
    reusable staging buffer); a fresh array is returned when absent or
    mis-shaped.
    """
    n, w, z = batch.shape
    lay = PackedLayout(w, z)
    if out is None or out.shape != (n, lay.width):
        out = np.empty((n, lay.width), np.float32)
    # invalid workload slots ride as NaN in the cpu column — no separate
    # mask plane needed in the packed layout
    out[:, lay.cpu] = np.where(batch.workload_valid, batch.cpu_deltas,
                               np.nan)
    out[:, lay.zone] = batch.zone_deltas_uj
    out[:, lay.zone_valid] = batch.zone_valid
    out[:, lay.col_ratio] = batch.usage_ratio
    out[:, lay.col_denom] = batch.node_cpu_delta
    out[:, lay.col_dt] = batch.dt_s
    out[:, lay.col_mode] = batch.mode
    return out


def pack_reports_into(out: np.ndarray, reports: Sequence[NodeReport],
                      zone_deltas_mat: np.ndarray,
                      zone_valid_mat: np.ndarray,
                      n_workloads: int) -> None:
    """Pack ragged reports straight into ``out[:len(reports)]`` (packed
    row layout) without materializing an intermediate FleetBatch — the
    delta-H2D staging path packs every window, so the extra cpu/valid
    planes and the NaN-merge pass the two-step route pays are real
    milliseconds at fleet scale. Rows beyond each report's workload
    count stay NaN (invalid)."""
    n = len(reports)
    lay = PackedLayout(n_workloads, zone_deltas_mat.shape[1])
    out[:n, lay.cpu] = np.nan
    lengths = np.fromiter((len(r.cpu_deltas) for r in reports),
                          np.int64, n)
    total = int(lengths.sum())
    if total:
        flat = np.concatenate(
            [np.asarray(r.cpu_deltas, np.float32) for r in reports])
        rows = np.repeat(np.arange(n), lengths)
        starts = np.concatenate(([0], np.cumsum(lengths)[:-1]))
        cols = np.arange(total) - np.repeat(starts, lengths)
        out[rows, cols] = flat
    out[:n, lay.zone] = zone_deltas_mat
    out[:n, lay.zone_valid] = zone_valid_mat
    out[:n, lay.col_ratio] = np.fromiter(
        (r.usage_ratio for r in reports), np.float64, n)
    out[:n, lay.col_denom] = np.fromiter(
        (r.node_cpu_delta for r in reports), np.float64, n)
    out[:n, lay.col_dt] = np.fromiter(
        (r.dt_s for r in reports), np.float64, n)
    out[:n, lay.col_mode] = np.fromiter(
        (r.mode for r in reports), np.int64, n)


def _unpack_fields(packed: jax.Array, w: int, z: int) -> tuple[
        jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
        jax.Array, jax.Array]:
    lay = PackedLayout(w, z)
    cpu_nan = packed[:, lay.cpu]
    workload_valid = ~jnp.isnan(cpu_nan)
    cpu = jnp.where(workload_valid, cpu_nan, 0.0)
    zone = packed[:, lay.zone]
    zone_valid = packed[:, lay.zone_valid] > 0.5
    ratio = packed[:, lay.col_ratio]
    denom = packed[:, lay.col_denom]
    dt = packed[:, lay.col_dt]
    mode = packed[:, lay.col_mode].astype(jnp.int32)
    return cpu, workload_valid, zone, zone_valid, ratio, denom, dt, mode


def _pack_watts_f16(res: FleetResult) -> jax.Array:
    """FleetResult → one f16 [N, W+2, Z] output (one D2H), in watts."""
    watts = res.workload_power_uw * 1e-6  # µW → W for f16 range
    active = res.node_active_power_uw[:, None, :] * 1e-6
    total = res.node_power_uw[:, None, :] * 1e-6
    return jnp.concatenate([watts, active, total],
                           axis=1).astype(jnp.float16)


def _window_step_fns(mesh: Mesh, n_workloads: int, n_zones: int,
                     model_mode: str | None, backend: str,
                     model_bucket: int | None) -> tuple[
                         Callable, Callable | None]:
    """The shared UNJITTED packed window-step bodies → (dense, sparse).

    ``sparse`` is None unless ``model_bucket`` is set with a model mode
    (einsum backend required — the row-index gather has no shard story).
    Both the per-window packed builder (:func:`make_packed_fleet_program`)
    and the fused K-window scan builder (:func:`make_fused_window_program`)
    compose these same closures, so the two programs cannot drift."""
    predict_fn = predictor(model_mode) if model_mode else None
    if predict_fn is not None and model_mode != "linear" \
            and mesh.devices.flat[0].platform != "tpu":
        # bf16 trunks are an MXU throughput feature; off-TPU, bf16 is
        # emulated — measurably SLOWER than f32 and noisier. Serve f32
        # compute on CPU/GPU hosts (output dtype unchanged: the f16
        # packed wire format is the quantizer either way).
        base_fn = predict_fn

        def predict_fn(params: Any, feats: jax.Array, valid: jax.Array,
                       _fn: Callable = base_fn) -> jax.Array:
            return _fn(params, feats, valid, compute_dtype=jnp.float32)

    w, z = n_workloads, n_zones
    attribute_fn = resolve_attribute_fn(mesh, backend)
    sparse = model_bucket is not None and predict_fn is not None
    if sparse and backend != "einsum":
        raise ValueError(
            "sparse model evaluation (model_bucket) requires the einsum "
            f"backend; got {backend!r}")

    def unpack_and_attribute(model_params: Any,
                             packed: jax.Array) -> jax.Array:
        fields = _unpack_fields(packed, w, z)
        cpu, workload_valid, zone, zone_valid, ratio, denom, dt, mode = fields
        res = fleet_attribution_program(
            model_params, zone, zone_valid, ratio, cpu, workload_valid,
            denom, dt, mode, predict_fn=predict_fn,
            attribute_fn=attribute_fn)
        return _pack_watts_f16(res)

    def unpack_and_attribute_sparse(model_params: Any, packed: jax.Array,
                                    model_rows: jax.Array) -> jax.Array:
        from kepler_tpu.models.features import build_features

        fields = _unpack_fields(packed, w, z)
        cpu, workload_valid, zone, zone_valid, ratio, denom, dt, mode = fields
        ratio_res = attribute_fn(zone, zone_valid, ratio, cpu,
                                 workload_valid, denom, dt)
        sub_valid = workload_valid[model_rows]
        feats = build_features(cpu[model_rows], sub_valid,
                               denom[model_rows], ratio[model_rows],
                               dt[model_rows])
        sub_watts = predict_fn(model_params, feats, sub_valid)
        # padding entries (index N) drop on the scatter; MODE_RATIO rows
        # keep zeros here, which mix_model_watts' where() never selects
        model_watts = jnp.zeros(cpu.shape + (z,), jnp.float32).at[
            model_rows].set(sub_watts)
        return _pack_watts_f16(mix_model_watts(ratio_res, model_watts,
                                               mode, dt))

    return unpack_and_attribute, (unpack_and_attribute_sparse
                                  if sparse else None)


def make_packed_fleet_program(mesh: Mesh, n_workloads: int, n_zones: int,
                              model_mode: str | None = None,
                              backend: str = "einsum",
                              model_bucket: int | None = None,
                              local_model_rows: bool = False) -> Callable:
    """→ jitted ``packed_in [N, W+2Z+4] → packed_watts_f16 [N, W+2, Z]``.

    W and Z are static (they define the packing layout); N stays dynamic
    per compilation, sharded over the mesh's node axis.

    ``model_bucket``: when given (and ``model_mode`` is set), the program
    takes a third ``model_rows`` int32 [model_bucket] argument and
    evaluates the estimator ONLY on those rows (sparse mixed-fleet
    evaluation; see module docstring). Entries ≥ N are padding: the
    gather clamps them to a real row whose scatter-back is then dropped.

    ``local_model_rows``: SHARDED sparse evaluation for multi-device
    meshes. The replicated-``model_rows`` gather above has no shard
    story — GSPMD would all-gather the whole packed batch to satisfy
    arbitrary global indices. With ``local_model_rows`` the program runs
    under ``shard_map`` over the node axis: ``model_rows`` is int32
    [n_shards × model_bucket] sharded over ``node``, each shard's
    segment holding SHARD-LOCAL row indices (pad = the shard's local row
    count, gather-clamped / scatter-dropped per shard). The estimator
    gather, forward, and scatter-back all stay shard-local; the only
    cross-shard step left in a window is the caller's result fetch.
    """
    unpack_and_attribute, unpack_and_attribute_sparse = _window_step_fns(
        mesh, n_workloads, n_zones, model_mode, backend, model_bucket)
    sparse = unpack_and_attribute_sparse is not None
    if sparse and local_model_rows:
        # per-shard body: every array is the shard's LOCAL block, so the
        # pad/clamp/drop index space is the local row count and no
        # collective is ever emitted — XLA runs K independent partitions
        local = jax.shard_map(
            unpack_and_attribute_sparse, mesh=mesh,
            in_specs=(P(), P(NODE_AXIS, None), P(NODE_AXIS)),
            out_specs=P(NODE_AXIS, None, None))
        return jax.jit(
            local,
            in_shardings=(NamedSharding(mesh, P()),
                          NamedSharding(mesh, P(NODE_AXIS, None)),
                          NamedSharding(mesh, P(NODE_AXIS))),
            out_shardings=NamedSharding(mesh, P(NODE_AXIS)),
        )
    if sparse:
        return jax.jit(
            unpack_and_attribute_sparse,
            in_shardings=(NamedSharding(mesh, P()),
                          NamedSharding(mesh, P(NODE_AXIS, None)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P(NODE_AXIS)),
        )
    fn = unpack_and_attribute
    if backend == "pallas":
        fn = shard_by_node(fn, mesh, in_specs=(P(), P(NODE_AXIS, None)))
    return jax.jit(
        fn,
        in_shardings=(NamedSharding(mesh, P()),
                      NamedSharding(mesh, P(NODE_AXIS, None))),
        out_shardings=NamedSharding(mesh, P(NODE_AXIS)),
    )


def make_fused_window_program(mesh: Mesh, n_workloads: int, n_zones: int,
                              model_mode: str | None = None,
                              backend: str = "einsum",
                              model_bucket: int | None = None) -> Callable:
    """→ jitted DEVICE-RESIDENT window loop: one dispatch per K windows.

    ``fused(params, resident, delta_rows, delta_idx[, model_rows])``:

      resident    f32 [N, width]      — DONATED packed resident block
      delta_rows  f32 [K, DB, width]  — per-interval staged delta rows
      delta_idx   i32 [K, DB]         — target rows (pad = N → dropped)
      model_rows  i32 [K, MB]         — sparse variant only (pad = N)

      → (resident' f32 [N, width], outs f16 [K, N, W+2, Z])

    One ``lax.scan`` applies each interval's delta rows to the resident
    block and runs the shared packed window body on the result — the
    host dispatches ONCE per K windows and the publish fetch
    materializes all K packed outputs in one transfer, amortizing the
    per-window host↔device sync floor K×. K and DB ride on the argument
    shapes (static per compilation, bucketed by the window engine).

    The resident block is donated (argnum 1): the scan carry aliases the
    input buffer, so the device never holds two fleet-sized residents
    and the caller must rebind its handle to the returned one.

    With ``backend="pallas"`` on a single-device mesh and no model, each
    scan step runs the fused mega-kernel
    (``ops.pallas_attribution.fused_window_step``): scatter + unpack +
    attribution in ONE kernel body. Everywhere else the step composes
    the drop-mode scatter with the shared window body and XLA fuses the
    pair per step (still one executable for the whole K-window batch).
    """
    dense_fn, sparse_fn = _window_step_fns(
        mesh, n_workloads, n_zones, model_mode, backend, model_bucket)
    repl = NamedSharding(mesh, P())
    by_node = NamedSharding(mesh, P(NODE_AXIS, None))
    out_shardings = (by_node, NamedSharding(mesh, P(None, NODE_AXIS)))

    if sparse_fn is not None:
        def fused_scan_sparse(model_params: Any, resident: jax.Array,
                              delta_rows: jax.Array, delta_idx: jax.Array,
                              model_rows: jax.Array) -> tuple[
                                  jax.Array, jax.Array]:
            def step(res, xs):
                rows, idx, mrows = xs
                res = res.at[idx].set(rows, mode="drop")
                return res, sparse_fn(model_params, res, mrows)

            return jax.lax.scan(step, resident,
                                (delta_rows, delta_idx, model_rows))

        return jax.jit(
            fused_scan_sparse,
            donate_argnums=(1,),
            in_shardings=(repl, by_node, repl, repl, repl),
            out_shardings=out_shardings,
        )

    lay = PackedLayout(n_workloads, n_zones)
    use_kernel = (backend == "pallas" and model_mode is None
                  and len(list(mesh.devices.flat)) == 1)
    if use_kernel:
        from kepler_tpu.ops.pallas_attribution import fused_window_step
        interpret = mesh.devices.flat[0].platform != "tpu"
    body_fn = dense_fn
    if backend == "pallas" and not use_kernel:
        # pallas_call has no SPMD rule: the per-step body runs per-shard
        # (the scatter stays outside — its indices are global row ids)
        body_fn = shard_by_node(dense_fn, mesh,
                                in_specs=(P(), P(NODE_AXIS, None)))

    def fused_scan(model_params: Any, resident: jax.Array,
                   delta_rows: jax.Array,
                   delta_idx: jax.Array) -> tuple[jax.Array, jax.Array]:
        def step(res, xs):
            rows, idx = xs
            if use_kernel:
                return fused_window_step(res, rows, idx, lay,
                                         interpret=interpret)
            res = res.at[idx].set(rows, mode="drop")
            return res, body_fn(model_params, res)

        return jax.lax.scan(step, resident, (delta_rows, delta_idx))

    # keep_unused: ratio mode (and the mega-kernel path) never reads
    # model_params, but pruning it would renumber the flat arguments and
    # detach the donate_argnums=(1,) contract from the resident block
    # (KTL121 checks declared vs realized donation by flat position)
    return jax.jit(
        fused_scan,
        donate_argnums=(1,),
        keep_unused=True,
        in_shardings=(repl, by_node, repl, repl),
        out_shardings=out_shardings,
    )


def _numpy_gelu(x: np.ndarray) -> np.ndarray:
    """jax.nn.gelu's default (tanh-approximate) formulation in NumPy."""
    c = np.float32(np.sqrt(2.0 / np.pi))
    return np.float32(0.5) * x * (
        np.float32(1.0) + np.tanh(c * (x + np.float32(0.044715) * x ** 3)))


def _numpy_features(cpu: np.ndarray, valid: np.ndarray, denom: np.ndarray,
                    ratio: np.ndarray, dt: np.ndarray) -> np.ndarray:
    """NumPy mirror of models.features.build_features → f32 [N, W, F]."""
    deltas = np.where(valid, cpu, 0.0).astype(np.float32)
    d = denom[:, None]
    share = np.where(d > 0.0, deltas / np.maximum(d, 1e-30), 0.0)
    dtc = dt[:, None]
    rate = np.where(dtc > 0.0, deltas / np.maximum(dtc, 1e-30), 0.0)
    w_shape = deltas.shape
    node_log = np.log1p(np.maximum(denom, 0.0))
    feats = np.stack([
        deltas,
        share,
        np.broadcast_to(ratio[:, None], w_shape),
        np.broadcast_to(dt[:, None], w_shape),
        rate,
        np.ones_like(deltas),
        np.broadcast_to(node_log[:, None], w_shape),
    ], axis=-1).astype(np.float32)
    return np.where(valid[..., None], feats, 0.0)


def _numpy_model_watts(model_mode: str, params: Any, feats: np.ndarray,
                       valid: np.ndarray) -> np.ndarray | None:
    """NumPy forward for the estimators the host rung can serve (linear,
    mlp — the shipped default). → watts f32 [N, W, Z], or None when the
    mode has no NumPy mirror (moe/deep; temporal never takes the packed
    path at all)."""
    if params is None:
        return None
    try:
        p = {k: np.asarray(v, np.float32) for k, v in dict(params).items()}
    except Exception:
        return None
    if model_mode == "linear":
        if "weight" not in p or "bias" not in p:
            return None
        watts = feats @ p["weight"] + p["bias"]
    elif model_mode == "mlp":
        if any(k not in p for k in ("w0", "b0", "w1", "b1", "w2", "b2",
                                    "w_skip")):
            return None
        h = _numpy_gelu(feats @ p["w0"] + p["b0"])
        h = _numpy_gelu(h @ p["w1"] + p["b1"])
        watts = h @ p["w2"] + feats @ p["w_skip"] + p["b2"]
    else:
        return None
    watts = np.maximum(watts.astype(np.float32), 0.0)
    return np.where(valid[..., None], watts, 0.0)


def numpy_fleet_window(packed: np.ndarray, n_workloads: int, n_zones: int,
                       params: Any = None,
                       model_mode: str | None = None) -> np.ndarray:
    """Pure-NumPy mirror of the packed fleet program — the aggregator's
    host-fallback rung (docs/developer/resilience.md "Device-plane
    faults"): same packed input layout in, same ``[N, W+2, Z]`` watts
    layout out (f32, not f16 — there is no wire-format quantizer to
    satisfy on host), touching no jax API at all so it keeps publishing
    with the device plane completely dead.

    Ratio-node attribution is exact (the same masked outer product the
    device program runs). Model rows are served for the NumPy-mirrored
    estimators (linear, mlp); modes without a host mirror (moe, deep)
    publish zero watts for their model rows — absence, not fabrication,
    and the ladder's health probe names the degraded rung.
    """
    w, z = n_workloads, n_zones
    lay = PackedLayout(w, z)
    cpu_nan = packed[:, lay.cpu]
    valid = ~np.isnan(cpu_nan)
    cpu = np.where(valid, cpu_nan, 0.0).astype(np.float32)
    zone = packed[:, lay.zone]
    zone_valid = packed[:, lay.zone_valid] > 0.5
    ratio = packed[:, lay.col_ratio]
    denom = packed[:, lay.col_denom]
    dt = packed[:, lay.col_dt]
    mode = packed[:, lay.col_mode].astype(np.int32)

    # node split (ops.attribution._node_split, NumPy)
    deltas = np.where(zone_valid, zone, 0.0).astype(np.float32)
    r = np.clip(ratio, 0.0, 1.0)[:, None]
    active = deltas * r
    dtc = dt[:, None]
    safe_dt = np.where(dtc > 0.0, dtc, 1.0)
    total_power_uw = np.where(dtc > 0.0, deltas / safe_dt, 0.0)
    active_power_uw = np.where(dtc > 0.0, active / safe_dt, 0.0)
    # workload ratios + the [W] ⊗ [Z] outer product, batched
    d = denom[:, None]
    ratios = np.where(d > 0.0,
                      cpu / np.maximum(d, 1e-30), 0.0).astype(np.float32)
    wl_power_uw = np.einsum("nw,nz->nwz", ratios, active_power_uw)

    node_active_w = active_power_uw * 1e-6  # µW → W (packed wire unit)
    node_total_w = total_power_uw * 1e-6
    wl_watts = wl_power_uw * 1e-6

    model_rows = np.flatnonzero(mode == MODE_MODEL)
    if model_rows.size and model_mode:
        feats = _numpy_features(cpu[model_rows], valid[model_rows],
                                denom[model_rows], ratio[model_rows],
                                dt[model_rows])
        watts = _numpy_model_watts(model_mode, params, feats,
                                   valid[model_rows])
        if watts is None:
            watts = np.zeros((model_rows.size, w, z), np.float32)
        wl_watts[model_rows] = watts
        est_node = watts.sum(axis=1)
        node_active_w[model_rows] = est_node
        node_total_w[model_rows] = est_node
    return np.concatenate(
        [wl_watts, node_active_w[:, None, :], node_total_w[:, None, :]],
        axis=1).astype(np.float32)


def unpack_fleet_watts(packed_watts: np.ndarray) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """One D2H array → (workload_watts [N, W, Z], node_active_watts [N, Z])."""
    return packed_watts[:, :ROW_NODE_ACTIVE, :], \
        packed_watts[:, ROW_NODE_ACTIVE, :]


def unpack_fleet_window(packed_watts: np.ndarray) -> tuple[
        np.ndarray, np.ndarray, np.ndarray]:
    """One D2H array → (workload_watts [N, W, Z], node_active_watts [N, Z],
    node_total_watts [N, Z]) — the aggregator's scatter-back triple."""
    return (packed_watts[:, :ROW_NODE_ACTIVE, :],
            packed_watts[:, ROW_NODE_ACTIVE, :],
            packed_watts[:, ROW_NODE_TOTAL, :])
