"""The sharded cluster-attribution program.

BASELINE.json north star: gather per-node feature rows, evaluate
ratio-attribution AND learned estimators as one batched computation over
``[nodes × pods × features]`` on TPU, scatter watts back per node.

Sharding: the node axis spreads across the mesh's ``node`` axis (each device
attributes its slice of the fleet — pure data parallelism, zero collectives
in the forward program since every reduction is within one node's row).
Model params are replicated (tiny) or tensor-sharded over ``model``
(see ``kepler_tpu.parallel.trainer``). XLA GSPMD propagates shardings from
the input annotations; there are no hand-placed collectives here.

Mixed fleets (config 5): both paths evaluate for every node (the model is a
pair of matmuls — cheaper than a branch on TPU, and `lax.cond` over a
batched axis would serialize anyway); `jnp.where` on the per-node mode code
selects the result. RAPL nodes get ratio watts, non-RAPL nodes get model
watts scaled onto their (unknown) zone axis.
"""

from __future__ import annotations

import functools
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kepler_tpu.models.estimator import predictor
from kepler_tpu.models.features import build_features
from kepler_tpu.ops.attribution import AttributionResult, attribute_fleet
from kepler_tpu.parallel.fleet import MODE_MODEL, FleetBatch
from kepler_tpu.parallel.mesh import NODE_AXIS


class FleetResult(NamedTuple):
    node_energy_uj: jax.Array  # [N, Z]
    node_active_uj: jax.Array  # [N, Z]
    node_idle_uj: jax.Array  # [N, Z]
    node_power_uw: jax.Array  # [N, Z]
    node_active_power_uw: jax.Array  # [N, Z]
    node_idle_power_uw: jax.Array  # [N, Z]
    workload_energy_uj: jax.Array  # [N, W, Z]
    workload_power_uw: jax.Array  # [N, W, Z]


def _ratio_only_result(ratio: AttributionResult) -> FleetResult:
    return FleetResult(
        node_energy_uj=ratio.node.energy_uj,
        node_active_uj=ratio.node.active_uj,
        node_idle_uj=ratio.node.idle_uj,
        node_power_uw=ratio.node.power_uw,
        node_active_power_uw=ratio.node.active_power_uw,
        node_idle_power_uw=ratio.node.idle_power_uw,
        workload_energy_uj=ratio.workloads.energy_uj,
        workload_power_uw=ratio.workloads.power_uw,
    )


def mix_model_watts(
    ratio: AttributionResult,
    model_watts: jax.Array,  # f32 [N, W, Z] estimator output (watts)
    mode: jax.Array,  # int32 [N]
    dt_s: jax.Array,  # f32 [N]
) -> FleetResult:
    """Per-node select: RAPL nodes keep ratio watts, MODE_MODEL nodes take
    the estimator's. Shared by the single-tick and temporal fleet programs."""
    model_power_uw = model_watts * 1e6  # watts → µW
    model_energy_uj = model_power_uw * dt_s[:, None, None]  # µW·s = µJ
    is_model = (mode == MODE_MODEL)[:, None, None]
    wl_power = jnp.where(is_model, model_power_uw, ratio.workloads.power_uw)
    wl_energy = jnp.where(is_model, model_energy_uj,
                          ratio.workloads.energy_uj)
    # model-mode nodes have no RAPL; their node totals are the sum of
    # model-estimated workload power (active == total, idle unknown → 0)
    est_node_power = jnp.sum(model_power_uw, axis=1)  # [N, Z]
    est_node_energy = jnp.sum(model_energy_uj, axis=1)
    is_model_nz = (mode == MODE_MODEL)[:, None]
    return FleetResult(
        node_energy_uj=jnp.where(is_model_nz, est_node_energy,
                                 ratio.node.energy_uj),
        node_active_uj=jnp.where(is_model_nz, est_node_energy,
                                 ratio.node.active_uj),
        node_idle_uj=jnp.where(is_model_nz, 0.0, ratio.node.idle_uj),
        node_power_uw=jnp.where(is_model_nz, est_node_power,
                                ratio.node.power_uw),
        node_active_power_uw=jnp.where(is_model_nz, est_node_power,
                                       ratio.node.active_power_uw),
        node_idle_power_uw=jnp.where(is_model_nz, 0.0,
                                     ratio.node.idle_power_uw),
        workload_energy_uj=wl_energy,
        workload_power_uw=wl_power,
    )


def fleet_attribution_program(
    model_params: Any,
    zone_deltas_uj: jax.Array,  # f32 [N, Z]
    zone_valid: jax.Array,  # bool [N, Z]
    usage_ratio: jax.Array,  # f32 [N]
    cpu_deltas: jax.Array,  # f32 [N, W]
    workload_valid: jax.Array,  # bool [N, W]
    node_cpu_delta: jax.Array,  # f32 [N]
    dt_s: jax.Array,  # f32 [N]
    mode: jax.Array,  # int32 [N] MODE_RATIO / MODE_MODEL
    *,
    predict_fn,
    attribute_fn=attribute_fleet,
) -> FleetResult:
    """The pure program; wrap with jit+shardings via ``make_fleet_program``."""
    ratio = attribute_fn(
        zone_deltas_uj, zone_valid, usage_ratio, cpu_deltas,
        workload_valid, node_cpu_delta, dt_s,
    )
    if predict_fn is None:
        return _ratio_only_result(ratio)
    feats = build_features(cpu_deltas, workload_valid, node_cpu_delta,
                           usage_ratio, dt_s)
    model_watts = predict_fn(model_params, feats, workload_valid)
    return mix_model_watts(ratio, model_watts, mode, dt_s)


def temporal_fleet_program(
    model_params: Any,
    zone_deltas_uj: jax.Array,  # f32 [N, Z]
    zone_valid: jax.Array,  # bool [N, Z]
    usage_ratio: jax.Array,  # f32 [N]
    cpu_deltas: jax.Array,  # f32 [N, W]
    workload_valid: jax.Array,  # bool [N, W]
    node_cpu_delta: jax.Array,  # f32 [N]
    dt_s: jax.Array,  # f32 [N]
    mode: jax.Array,  # int32 [N]
    # the dense windows: feat_hist f32 [N, W, T, F] per-workload history,
    # t_valid bool [N, W, T]; or compact_history's three arrays
    *history: jax.Array,
    attribute_fn=attribute_fleet,
    accuracy_mode: bool = False,
    last_query_fn=None,
) -> FleetResult:
    """Mixed fleet with the TEMPORAL estimator: the aggregator accretes each
    workload's feature history (`kepler_tpu.monitor.history`) and the model
    predicts from the whole window instead of the last tick. Handed
    :func:`compact_history`'s blocks in the dense windows' place, it runs
    the estimator on the rows that were sent (:func:`estimate_sent_rows`).
    ``last_query_fn`` reaches ``predict_temporal`` (the fused trunk:
    :func:`fused_trunk_chosen`).
    """
    from kepler_tpu.models.temporal import predict_temporal

    # the scope names reach the HLO's op metadata, so a profile of the
    # program reads by stage (models/temporal.py names the estimator's)
    with jax.named_scope("attribute"):
        ratio = attribute_fn(
            zone_deltas_uj, zone_valid, usage_ratio, cpu_deltas,
            workload_valid, node_cpu_delta, dt_s,
        )
    pfn = (accuracy_mode_predictor(predict_temporal, "temporal")
           if accuracy_mode else predict_temporal)
    if last_query_fn is not None:
        pfn = functools.partial(pfn, last_query_fn=last_query_fn)
    if len(history) == 3:  # hist_rows, tv_rows, row_of
        watts = estimate_sent_rows(pfn, model_params, workload_valid,
                                   *history)
    else:
        feat_hist, t_valid = history
        watts = pfn(model_params, feat_hist, workload_valid, t_valid=t_valid)
    with jax.named_scope("attribute"):
        return mix_model_watts(ratio, watts, mode, dt_s)


def resolve_attribute_fn(mesh: Mesh, backend: str):
    """→ the fleet-attribution contraction for ``backend``.

    "einsum" lets XLA fuse it; "pallas" binds the Mosaic kernel with
    interpret mode engaged automatically off-TPU. Shared by the sharded
    and packed-transfer program builders.
    """
    if backend == "pallas":
        from kepler_tpu.ops.pallas_attribution import attribute_fleet_pallas
        interpret = mesh.devices.flat[0].platform != "tpu"
        return functools.partial(attribute_fleet_pallas, interpret=interpret)
    if backend == "einsum":
        return attribute_fleet
    raise ValueError(f"unknown attribution backend {backend!r}; "
                     "valid: einsum, pallas")


def shard_by_node(fn, mesh: Mesh, in_specs):
    """shard_map ``fn`` over the node axis (pallas-backend program builders).

    pallas_call has no SPMD partitioning rule, so the kernel must run
    per-shard; the fleet forward has no cross-node math, so this changes
    layout, not semantics. check_vma=False because pallas_call defeats the
    varying-axes checker.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(NODE_AXIS), check_vma=False)


def accuracy_mode_predictor(predict_fn, model_mode: str):
    """Wrap a registry predictor for ACCURACY-mode serving: f32 compute
    dtype (bf16 trunks carry ~1e-3 relative noise — twice the whole 0.5%
    budget) and matmul precision HIGHEST for the estimator's ops (TPU
    "f32" matmuls otherwise run one bf16 MXU pass). Estimator shapes are
    tiny, so the 3-pass cost is invisible; the bulk ratio-attribution
    contraction stays OUTSIDE the wrapper at default precision — this is
    the configuration `benchmarks/accuracy.py` validates to p99 ≤ 0.5%.
    """
    kw = {} if model_mode == "linear" else {"compute_dtype": jnp.float32}

    def wrapped(params, feats, workload_valid, **extra):
        with jax.default_matmul_precision("highest"):
            return predict_fn(params, feats, workload_valid, **kw, **extra)

    return wrapped


def fleet_shardings(mesh: Mesh) -> tuple[NamedSharding, NamedSharding]:
    """→ (replicated, by_node): what the fleet programs over ``mesh``
    declare for the params and for every per-node argument (the leading
    axis over ``node``, the rest whole)."""
    return NamedSharding(mesh, P()), NamedSharding(mesh, P(NODE_AXIS))


def make_fleet_program(mesh: Mesh, model_mode: str | None = None,
                       backend: str = "einsum",
                       accuracy_mode: bool = False):
    """jit the fleet program with node-axis shardings over ``mesh``.

    ``model_mode``: None = ratio only; "linear"/"mlp" compiles that
    predictor into the program for mixed fleets.

    ``accuracy_mode``: serve the estimator at f32/highest precision (see
    :func:`accuracy_mode_predictor`); default bf16 is the throughput mode.

    ``backend``: "einsum" lets XLA fuse the attribution contraction;
    "pallas" runs it as the hand-written Mosaic kernel
    (``ops.pallas_attribution``), wrapped in ``shard_map`` over the node
    axis so each device executes the kernel on its local shard (the
    forward has no cross-node math, so this changes layout, not
    semantics; interpret mode engages automatically off-TPU).
    """
    predict_fn = predictor(model_mode) if model_mode else None
    if predict_fn is not None and accuracy_mode:
        predict_fn = accuracy_mode_predictor(predict_fn, model_mode)
    replicated, by_node = fleet_shardings(mesh)

    attribute_fn = resolve_attribute_fn(mesh, backend)
    fn = functools.partial(fleet_attribution_program,
                           predict_fn=predict_fn,
                           attribute_fn=attribute_fn)
    if backend == "pallas":
        data_specs = (P(NODE_AXIS, None), P(NODE_AXIS, None), P(NODE_AXIS),
                      P(NODE_AXIS, None), P(NODE_AXIS, None), P(NODE_AXIS),
                      P(NODE_AXIS), P(NODE_AXIS))
        fn = shard_by_node(fn, mesh, in_specs=(P(),) + data_specs)

    # a named function, not the bare partial: the profiler's module line
    # then reads jit_fleet_window(...), not jit__unknown(...)
    def fleet_window(*args):
        return fn(*args)

    return jax.jit(
        fleet_window,
        # model params (tiny; tensor-sharded in trainer), then zone_deltas,
        # zone_valid, usage_ratio, cpu_deltas, workload_valid,
        # node_cpu_delta, dt, mode
        in_shardings=(replicated,) + (by_node,) * 8,
        out_shardings=by_node,
    )


# the smallest bucket of history rows a compact put sends a shard, and so
# the fewest rows the compact program runs its estimator on (the base of
# the ladder whose ``fit`` the caller hands to compact_history)
HISTORY_ROWS_BASE = 1024


def _rows_with_a_tick(t_valid: np.ndarray) -> np.ndarray:
    """``t_valid.any(-1)``. NumPy's reduction over a short last axis costs
    80 ns a row (22 ms at 5000 nodes x 256 slots), so where the layout
    allows, a row's T bools are read as T/8 words and the words or-ed."""
    t = t_valid.shape[-1]
    if t % 8 or not t_valid.flags.c_contiguous:
        return t_valid.any(-1)
    words = t_valid.view(np.uint64)
    hit = words[..., 0]
    for k in range(1, t // 8):
        hit = hit | words[..., k]
    return hit != 0


def compact_history(
    feat_hist: np.ndarray,  # f32 [N, W, T, F]
    t_valid: np.ndarray,  # bool [N, W, T]
    n_shards: int,
    fit_rows: Callable[[int], int],
    out: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """The dense history windows as their valid rows only, a block a
    shard of the node axis: → (hist_rows f32 [S, R, T·F], tv_rows bool
    [S, R, T], row_of int32 [S, N/S·W]); None where that is no fewer rows
    than the dense window has (a shard's rows fit the smallest bucket, or
    nearly all hold a tick): the dense arrays are the put then.

    A row is sent if any of its ticks is valid. The others are all zeros
    (``Aggregator._history_windows`` writes into ``np.zeros`` and
    ``HistoryBuffer.window_arrays`` leaves an empty slot's rows zero), so
    the estimate of every one of them is that of an empty window, which
    the compact program computes once (:func:`estimate_sent_rows`).
    ``row_of`` says, for every dense row of the shard, which compact row
    it is; ``R``, past the end, for a row that was not sent: the device
    reads the empty window's estimate there, so no padding entry can touch
    a real row. ``R``
    is ``fit_rows`` of the fullest shard's count (a ``BucketLadder.fit``:
    the program sees one shape while the count wanders). The rows go up flat,
    ``[R, T·F]``: on a TPU v5e that lands in 9 ms where ``[R, T, F]``
    takes 15 (PERF.md section 5).

    ``out``: three arrays an earlier call returned, to write into if their
    shapes still fit, and ONLY once the device has consumed them: the put
    returns before the bytes have left the host and two windows are in
    flight, so a block is its window's until that window's outputs are
    fetched. Without it the arrays are new."""
    n, w, t, f = feat_hist.shape
    per = n // n_shards * w  # a shard's dense rows
    dense_hist = feat_hist.reshape(n_shards, per, t * f)
    dense_tv = t_valid.reshape(n_shards, per, t)
    sent = [np.flatnonzero(hit) for hit in _rows_with_a_tick(dense_tv)]
    r = fit_rows(max(len(at) for at in sent))
    if r >= per:
        return None
    if out is not None and out[0].shape == (n_shards, r, t * f) \
            and out[2].shape == (n_shards, per):
        hist_rows, tv_rows, row_of = out
    else:
        hist_rows = np.empty((n_shards, r, t * f), feat_hist.dtype)
        tv_rows = np.empty((n_shards, r, t), bool)
        row_of = np.empty((n_shards, per), np.int32)
    # every element is written once: the index, then rows and padding
    row_of.fill(r)
    for s, at in enumerate(sent):
        k = len(at)
        # any mode but "raise" lets take() write straight into ``out``
        np.take(dense_hist[s], at, axis=0, out=hist_rows[s, :k], mode="clip")
        np.take(dense_tv[s], at, axis=0, out=tv_rows[s, :k], mode="clip")
        hist_rows[s, k:] = 0.0
        tv_rows[s, k:] = False
        row_of[s, at] = np.arange(k, dtype=np.int32)
    return hist_rows, tv_rows, row_of


def estimate_sent_rows(
    predict,
    params: Any,
    workload_valid: jax.Array,  # bool [N, W]
    hist_rows: jax.Array,  # f32 [S, R, T·F]: a block a shard
    tv_rows: jax.Array,  # bool [S, R, T]
    row_of: jax.Array,  # int32 [S, N/S·W]
) -> jax.Array:
    """The estimator watts ``[N, W, Z]`` from :func:`compact_history`'s
    blocks: ``predict`` runs on each block's R rows and a few zero rows
    past them (rows on the leading axis), and the watts go back to the
    dense slots by ``row_of``. A slot that was not sent (index R) reads
    the first zero row: the estimate of an empty window, what the dense
    program gives such a slot. Every step is within one block, so over
    the node axis' shards (``S`` of them; one where the caller is per
    shard) no row crosses devices."""
    s, r, t = tv_rows.shape
    f = hist_rows.shape[-1] // t
    # zero rows up to a whole multiple of 8 rows, one at least: a full
    # sublane tile, and on XLA's CPU backend a row's arithmetic is then
    # the dense program's to the bit (under 8 rows its dot kernels differ)
    pad = 8 - r % 8
    rows = jnp.pad(hist_rows, ((0, 0), (0, pad), (0, 0)))
    tv = jnp.pad(tv_rows, ((0, 0), (0, pad), (0, 0)))
    # a block row is a pod's if it holds a tick; the zero rows are the
    # empty window of a pod that has none
    valid = tv.any(-1) | (jnp.arange(r + pad) >= r)
    b = s * (r + pad)
    watts = predict(params, rows.reshape(b, 1, t, f), valid.reshape(b, 1),
                    t_valid=tv.reshape(b, 1, t))
    watts = jax.vmap(functools.partial(jnp.take, axis=0, mode="fill"))(
        watts.reshape(s, r + pad, -1), row_of)
    watts = watts.reshape(*workload_valid.shape, -1)
    return jnp.where(workload_valid[..., None], watts, 0.0)


def fused_trunk_chosen(platform: str, accuracy_mode: bool,
                       compute_dtype: jnp.dtype = jnp.bfloat16) -> bool:
    """Whether the served temporal program runs its trunk as the fused
    kernel (``ops.pallas_temporal``): on a TPU, in bf16, and not in
    accuracy mode (f32 at precision HIGHEST keeps the XLA trunk). Off a
    TPU the XLA trunk runs, the one every other caller differentiates,
    shards or pins bit for bit."""
    return (platform == "tpu" and not accuracy_mode
            and jnp.dtype(compute_dtype) == jnp.dtype(jnp.bfloat16))


def _fused_trunk(mesh: Mesh, per_shard: bool):
    """→ the fused kernel as a ``last_query_fn``. Unless the caller already
    runs per shard, it is mapped over the node axis: rows are shard-major,
    so each device runs the kernel on its own block of rows. Off a TPU it
    runs interpreted."""
    from kepler_tpu.ops.pallas_temporal import last_query_trunk_pallas

    interpret = mesh.devices.flat[0].platform != "tpu"

    def kernel_fn(params, feat_hist, t_valid, compute_dtype):
        return last_query_trunk_pallas(params, feat_hist, t_valid,
                                       compute_dtype, interpret=interpret)

    if per_shard:
        return kernel_fn

    def sharded(params, feat_hist, t_valid, compute_dtype):
        kernel = functools.partial(kernel_fn, compute_dtype=compute_dtype)
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=(P(), P(NODE_AXIS), P(NODE_AXIS)),
            out_specs=P(NODE_AXIS), check_vma=False,
        )(params, feat_hist, t_valid)

    return sharded


def make_temporal_fleet_program(mesh: Mesh, backend: str = "einsum",
                                accuracy_mode: bool = False,
                                compact: bool = False):
    """jit the TEMPORAL fleet program (extra ``feat_hist``/``t_valid``
    inputs, node-axis sharded). Params replicate — the model is tiny; for
    very long windows serve through ``parallel.sequence`` instead.

    ``compact``: the program the aggregator serves. In the place of the
    dense history it takes :func:`compact_history`'s three arrays, each
    shard's block on its own device, runs the estimator on the rows that
    were sent and gathers their watts to the dense slots there
    (:func:`estimate_sent_rows`); the ratio attribution and the mix are
    the dense program's.

    The estimator's trunk is the fused kernel where
    :func:`fused_trunk_chosen` says so for the mesh's devices."""
    replicated, by_node = fleet_shardings(mesh)
    n_history = 3 if compact else 2
    last_query_fn = None
    if fused_trunk_chosen(mesh.devices.flat[0].platform, accuracy_mode):
        last_query_fn = _fused_trunk(mesh, per_shard=backend == "pallas")
    fn = functools.partial(temporal_fleet_program,
                           attribute_fn=resolve_attribute_fn(mesh, backend),
                           accuracy_mode=accuracy_mode,
                           last_query_fn=last_query_fn)
    if backend == "pallas":
        data_specs = (P(NODE_AXIS, None), P(NODE_AXIS, None), P(NODE_AXIS),
                      P(NODE_AXIS, None), P(NODE_AXIS, None), P(NODE_AXIS),
                      P(NODE_AXIS), P(NODE_AXIS)) \
            + (P(NODE_AXIS),) * n_history
        fn = shard_by_node(fn, mesh, in_specs=(P(),) + data_specs)

    def temporal_fleet_window(*args):  # named as fleet_window is
        return fn(*args)

    return jax.jit(
        temporal_fleet_window,
        in_shardings=(replicated,) + (by_node,) * (8 + n_history),
        out_shardings=by_node,
    )


def put_fleet_batch(
    batch: FleetBatch,
    model_params: Any = None,
    feat_hist=None,  # [N, W, T, F] — temporal programs only
    t_valid=None,  # [N, W, T]
    row_of=None,  # [S, N/S·W]: the history is compact_history's blocks
    mesh: Mesh | None = None,
) -> list:
    """The H2D half of the host entry: every argument of a fleet program
    as a device array, in the program's order (the params first).

    With the program's ``mesh`` every per-node argument is put with the
    sharding the program declares for it (:func:`fleet_shardings`): each
    device is sent its own nodes' rows straight from the host, holds no
    other device's, and the jit has nothing to move; over one device that
    is the whole array on that device. Without it every argument goes
    whole to the default device and the jit moves what belongs elsewhere
    (the library entry, :func:`run_fleet_attribution`).

    With ``row_of``, ``feat_hist`` and ``t_valid`` are the blocks of
    :func:`compact_history` (a block a shard: the mesh's node axis), for
    the ``compact`` temporal program."""
    if model_params is None:
        model_params = jnp.zeros(())
    data = [batch.zone_deltas_uj, batch.zone_valid, batch.usage_ratio,
            batch.cpu_deltas, batch.workload_valid, batch.node_cpu_delta,
            batch.dt_s, batch.mode]
    if feat_hist is not None:
        data += [feat_hist, t_valid]
    if row_of is not None:
        data.append(row_of)
    if mesh is None:
        return [model_params] + [jnp.asarray(a) for a in data]
    replicated, by_node = fleet_shardings(mesh)
    return [jax.device_put(model_params, replicated)] \
        + jax.device_put(data, by_node)


def run_fleet_attribution(
    program,
    batch: FleetBatch,
    model_params: Any = None,
    feat_hist=None,
    t_valid=None,
) -> FleetResult:
    """Host entry: device_put the padded batch and run one sharded step."""
    return program(*put_fleet_batch(batch, model_params, feat_hist,
                                    t_valid))
