"""Pallas TPU kernel for the fleet-attribution hot op.

The core contraction is ``energy[n,w,z] = ratio[n,w] × active[n,z]`` (+ the
same shape for power) — a bandwidth-bound rank-1 outer product over the
fleet batch. XLA fuses the einsum path well; this kernel exists to pin the
best layout and fuse BOTH outputs in one pass over the inputs:

- grid ``(Z, N/TN, W/TW)`` — each program computes a ``[TN, TW]`` tile, a
  clean (8, 128)-aligned 2-D block. Emitting ``[N, W, Z]`` directly would
  put Z(=4) on the lane axis and waste 32× of every VMEM tile; instead the
  kernel writes ``[Z, N, W]`` and the wrapper transposes (one cheap XLA
  relayout) to keep the public ``[N, W, Z]`` contract.
- energy and power tiles read the same ratio block from VMEM once —
  the einsum path reads it twice.

CPU tests run the same kernel with ``interpret=True``
(tests/conftest.py forces the CPU backend); on TPU it compiles with
Mosaic. Sharded use goes through ``shard_map`` over the node axis (see
``kepler_tpu.parallel.aggregator_core.make_fleet_program``) so each device
runs the kernel on its local node shard — no cross-device communication,
matching the einsum path's zero-collective forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kepler_tpu.ops.attribution import (
    AttributionResult,
    WorkloadAttribution,
    _node_split,
    _workload_ratios,
)


def _tile(n: int, preferred: int, align: int) -> int:
    """Largest Mosaic-legal tile for a dim of size ``n``.

    Legal means: a divisor of ``n`` that is a multiple of ``align`` (lane
    dim must be 128-divisible, sublane 8-divisible) — or ``n`` itself, since
    a block spanning the whole array dim is always accepted. Fleet batches
    are bucketed so the aligned-divisor case is the norm; the full-dim
    fallback keeps odd shapes correct at worst a little more VMEM.
    """
    if n <= preferred:
        return n
    t = preferred - preferred % align
    while t > 0:
        if n % t == 0:
            return t
        t -= align
    return n


def _outer_kernel(ratio_ref, a_ref, p_ref, energy_ref, power_ref):
    ratio = ratio_ref[...]  # [TN, TW]
    energy_ref[0] = ratio * a_ref[0]  # a_ref: [1, TN, 1] → [TN, 1] broadcasts
    power_ref[0] = ratio * p_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def outer_product_attribution(
    ratio: jax.Array,  # f32 [N, W]
    active_uj: jax.Array,  # f32 [N, Z]
    active_power_uw: jax.Array,  # f32 [N, Z]
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """→ (energy_uj [N,W,Z], power_uw [N,W,Z]) in one fused kernel pass."""
    n, w = ratio.shape
    z = active_uj.shape[1]
    tn = _tile(n, 8, 8)
    tw = _tile(w, 512, 128)  # wide lanes amortize the per-program overhead
    grid = (z, n // tn, w // tw)

    # zone columns as [Z, N, 1] so each program's block is a legal tile
    # (Mosaic wants the last block dim ≡ 128-divisible OR equal to the
    # array's — a trailing singleton qualifies); the relayout is a few KB
    active_zn1 = jnp.transpose(active_uj)[..., None]
    power_zn1 = jnp.transpose(active_power_uw)[..., None]
    zone_spec = pl.BlockSpec((1, tn, 1), lambda zi, i, j: (zi, i, 0))
    out_shape = jax.ShapeDtypeStruct((z, n, w), ratio.dtype)
    out_spec = pl.BlockSpec((1, tn, tw), lambda zi, i, j: (zi, i, j))
    energy_znw, power_znw = pl.pallas_call(
        _outer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, tw), lambda zi, i, j: (i, j)),
            zone_spec,
            zone_spec,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(ratio, active_zn1, power_zn1)
    # relayout to the public [N, W, Z] contract
    return (jnp.transpose(energy_znw, (1, 2, 0)),
            jnp.transpose(power_znw, (1, 2, 0)))


def _fused_window_kernel(res_ref, rows_ref, idx_ref, newres_ref, watts_ref,
                         active_ref, total_ref, *, lay, tn):
    """One grid step of the fused window mega-kernel (node tile ``i``).

    Does the WHOLE rung-0 window for its ``[TN, width]`` resident tile in
    one pass: scatter the interval's delta rows into the tile, unpack the
    packed fields, run ratio attribution, and emit the window's watts
    (workload rows, node ACTIVE, node TOTAL) — the three device
    round-trips of the unfused path collapsed into one kernel body.

    The scatter has no in-kernel gather: a ``[TN, DB]`` hit matrix
    (global row id == delta index) turns row selection into a 0/1 matmul
    — exact at HIGHEST precision, since delta indices are unique per
    interval, so every output row sums at most one product. NaN (the
    invalid-slot encoding in the cpu columns) would poison ``0 × NaN``;
    the NaN mask rides through a second matmul and is re-applied after.

    Every intermediate stays rank-2 with the node tile on sublanes:
    Mosaic has no relayout from a lane-major ``[TN]`` vector to a
    ``[TN, 1]`` column, so single columns are width-1 slices and the hit
    matrix is built already in ``[TN, DB]`` orientation from a ``[1, DB]``
    index row.
    """
    i = pl.program_id(0)
    res = res_ref[...]  # [TN, width] f32
    drows = rows_ref[...]  # [DB, width] f32
    didx = idx_ref[...]  # [1, DB] i32 (pad = N: matches no row id)
    db = drows.shape[0]
    row_ids = i * tn + jax.lax.broadcasted_iota(jnp.int32, (tn, db), 0)
    hitf = (row_ids == didx).astype(jnp.float32)  # [TN, DB]
    anyhit = jnp.sum(hitf, axis=1, keepdims=True) > 0.5  # [TN, 1]
    nan_mask = jnp.isnan(drows)
    exact = dict(preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    sel = jnp.dot(hitf, jnp.where(nan_mask, 0.0, drows), **exact)
    sel_nan = jnp.dot(hitf, nan_mask.astype(jnp.float32), **exact)
    sel = jnp.where(sel_nan > 0.5, jnp.float32(jnp.nan), sel)
    rows = jnp.where(anyhit, sel, res)  # [TN, width]
    newres_ref[...] = rows

    # unpack (PackedLayout-derived slices, passed in statically) + the
    # exact ops.attribution formula chain, tile-local
    def col(c):
        return rows[:, c:c + 1]  # [TN, 1]

    cpu_nan = rows[:, lay.cpu]
    workload_valid = ~jnp.isnan(cpu_nan)
    cpu = jnp.where(workload_valid, cpu_nan, 0.0)
    zone = rows[:, lay.zone]
    zone_valid = rows[:, lay.zone_valid] > 0.5
    ratio = col(lay.col_ratio)
    denom = col(lay.col_denom)
    dt = col(lay.col_dt)

    deltas = jnp.where(zone_valid, zone, 0.0)  # [TN, Z]
    active = deltas * jnp.clip(ratio, 0.0, 1.0)
    safe_dt = jnp.where(dt > 0.0, dt, 1.0)
    total_uw = jnp.where(dt > 0.0, deltas / safe_dt, 0.0)
    active_uw = jnp.where(dt > 0.0, active / safe_dt, 0.0)
    ratios = jnp.where(denom > 0.0, cpu / jnp.maximum(denom, 1e-30),
                       0.0)  # [TN, W]
    active_ref[...] = active_uw * 1e-6  # µW → W, as _pack_watts_f16
    total_ref[...] = total_uw * 1e-6
    for zi in range(lay.n_zones):  # static unroll (Z is tiny)
        watts_ref[zi] = (ratios * active_uw[:, zi:zi + 1]) * 1e-6


def fused_window_step(
    resident: jax.Array,  # f32 [N, width] packed resident block
    delta_rows: jax.Array,  # f32 [DB, width] interval delta rows
    delta_idx: jax.Array,  # i32 [DB] target rows (pad = N → dropped)
    lay,  # PackedLayout (static: width + field offsets)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One FUSED window step: scatter + unpack + ratio attribution as a
    single Pallas kernel over the packed resident block.

    → ``(resident' [N, width] f32, packed_watts [N, W+2, Z] f16)`` — the
    same contract as ``scatter_rows`` followed by the packed ratio
    program, with zero intermediate device round-trips. Ratio-only by
    design (the dense-model fused path composes XLA ops instead); used
    as the ``lax.scan`` body of the pallas-backend fused window program.

    The kernel grid is 1-D over node tiles. Workload watts land as f32
    ``[Z, N, W]`` (lane-aligned tiles, same trick as
    ``outer_product_attribution``) and the two node rows as ``[N, Z]``
    each; the wrapper assembles the packed ``[N, W+2, Z]`` layout and
    quantizes to f16 in one XLA pass (the TPU vector unit has no f16,
    and a lane-axis concatenate at W is not a Mosaic layout).
    """
    n = resident.shape[0]
    db = delta_rows.shape[0]
    z, w = lay.n_zones, lay.n_workloads
    tn = _tile(n, 512, 8)
    grid = (n // tn,)
    kernel = functools.partial(_fused_window_kernel, lay=lay, tn=tn)
    res_spec = pl.BlockSpec((tn, lay.width), lambda i: (i, 0))
    node_spec = pl.BlockSpec((tn, z), lambda i: (i, 0))
    node_shape = jax.ShapeDtypeStruct((n, z), jnp.float32)
    newres, watts_znw, active_w, total_w = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            res_spec,
            pl.BlockSpec((db, lay.width), lambda i: (0, 0)),
            pl.BlockSpec((1, db), lambda i: (0, 0)),
        ],
        out_specs=[
            res_spec,
            pl.BlockSpec((z, tn, w), lambda i: (0, i, 0)),
            node_spec,
            node_spec,
        ],
        out_shape=[jax.ShapeDtypeStruct((n, lay.width), jnp.float32),
                   jax.ShapeDtypeStruct((z, n, w), jnp.float32),
                   node_shape, node_shape],
        interpret=interpret,
    )(resident, delta_rows, delta_idx[None, :])
    packed = jnp.concatenate(
        [jnp.transpose(watts_znw, (1, 2, 0)), active_w[:, None, :],
         total_w[:, None, :]], axis=1)
    return newres, packed.astype(jnp.float16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def attribute_fleet_pallas(
    zone_deltas_uj: jax.Array,  # f32 [N, Z]
    zone_valid: jax.Array,  # bool [N, Z]
    usage_ratio: jax.Array,  # f32 [N]
    cpu_deltas: jax.Array,  # f32 [N, W]
    workload_valid: jax.Array,  # bool [N, W]
    node_cpu_delta: jax.Array,  # f32 [N]
    dt_s: jax.Array,  # f32 [N]
    *,
    interpret: bool = False,
) -> AttributionResult:
    """Drop-in for ``ops.attribution.attribute_fleet`` with the outer
    product running as the Pallas kernel (identical results to f32
    rounding)."""
    node = _node_split(zone_deltas_uj, zone_valid, usage_ratio, dt_s)
    ratios = _workload_ratios(cpu_deltas, workload_valid, node_cpu_delta)
    energy, power = outer_product_attribution(
        ratios, node.active_uj, node.active_power_uw, interpret=interpret)
    return AttributionResult(
        node=node,
        workloads=WorkloadAttribution(
            energy_uj=energy, power_uw=power, cpu_ratio=ratios
        ),
    )
