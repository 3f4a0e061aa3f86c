"""Accuracy harness: the forgotten half of the north star.

BASELINE.json's target is two-axis: <1 ms p99 attribution latency AND
"within 0.5% of per-node RAPL ground truth". This module measures the
second axis against an independent float64 NumPy reference implementation
of the attribution semantics (reference parity:
``internal/monitor/node.go:10-84`` for the active/idle split,
``internal/monitor/process.go:123-145`` for the per-workload ratio
formula — re-derived here in f64, sharing no code with the device path).

Measured paths:
  * einsum f32 (`ops.attribution.attribute_fleet`) — the default backend
  * packed f16 transfer path (`parallel.packed`) — the bench/serving path
  * linear + MLP estimator families after a short jitted-scan fit

Error metric: max relative error over entries whose reference magnitude
exceeds ``floor`` (tiny watts drown in representation noise; the north
star is a percentage-of-ground-truth bound, so percentage is measured
where ground truth is meaningfully nonzero), plus the max absolute error
everywhere. Conservation (Σ workload energy == node active energy, the
executable spec of the reference's
``monitor_snapshot_integration_test.go``) is reported as its own relative
error.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

RATIO_TOL = 0.005  # the 0.5%-of-RAPL north-star budget


class RefAttribution(NamedTuple):
    """f64 ground truth for one fleet window."""

    node_energy_uj: np.ndarray  # [N, Z]
    node_active_uj: np.ndarray  # [N, Z]
    node_idle_uj: np.ndarray  # [N, Z]
    node_power_uw: np.ndarray  # [N, Z]
    node_active_power_uw: np.ndarray  # [N, Z]
    workload_energy_uj: np.ndarray  # [N, W, Z]
    workload_power_uw: np.ndarray  # [N, W, Z]


def reference_attribution_f64(
    zone_deltas_uj: np.ndarray,  # [N, Z]
    zone_valid: np.ndarray,  # bool [N, Z]
    usage_ratio: np.ndarray,  # [N]
    cpu_deltas: np.ndarray,  # [N, W]
    workload_valid: np.ndarray,  # bool [N, W]
    node_cpu_delta: np.ndarray,  # [N]
    dt_s: np.ndarray,  # [N]
) -> RefAttribution:
    """Independent f64 reimplementation of the ratio-attribution semantics."""
    deltas = np.where(zone_valid, zone_deltas_uj, 0.0).astype(np.float64)
    ratio = np.clip(usage_ratio.astype(np.float64), 0.0, 1.0)[:, None]
    active = deltas * ratio
    idle = deltas - active
    dt = dt_s.astype(np.float64)[:, None]
    pos = dt > 0.0
    safe_dt = np.where(pos, dt, 1.0)
    power = np.where(pos, deltas / safe_dt, 0.0)
    active_power = np.where(pos, active / safe_dt, 0.0)

    cpu = np.where(workload_valid, cpu_deltas, 0.0).astype(np.float64)
    denom = node_cpu_delta.astype(np.float64)[:, None]
    shares = np.where(denom > 0.0, cpu / np.where(denom > 0.0, denom, 1.0),
                      0.0)
    return RefAttribution(
        node_energy_uj=deltas,
        node_active_uj=active,
        node_idle_uj=idle,
        node_power_uw=power,
        node_active_power_uw=active_power,
        workload_energy_uj=shares[:, :, None] * active[:, None, :],
        workload_power_uw=shares[:, :, None] * active_power[:, None, :],
    )


def max_rel_err(measured: np.ndarray, reference: np.ndarray,
                floor: float) -> float:
    """Max |measured−ref|/|ref| over entries with |ref| > floor."""
    ref = np.asarray(reference, np.float64)
    got = np.asarray(measured, np.float64)
    sig = np.abs(ref) > floor
    if not sig.any():
        return 0.0
    return float(np.max(np.abs(got[sig] - ref[sig]) / np.abs(ref[sig])))


def max_abs_err(measured: np.ndarray, reference: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(measured, np.float64)
                               - np.asarray(reference, np.float64))))


def conservation_rel_err(workload_energy_uj: np.ndarray,
                         node_active_uj: np.ndarray,
                         floor: float = 1.0) -> float:
    """Σ_w energy[n,w,z] vs active[n,z] — the reference's conservation
    invariant, as a relative error on nodes with meaningful active energy."""
    total = np.asarray(workload_energy_uj, np.float64).sum(axis=1)
    return max_rel_err(total, np.asarray(node_active_uj, np.float64),
                       floor=floor)


def synthetic_fleet(n_nodes: int, n_workloads: int, n_zones: int,
                    seed: int = 0, full_cpu: bool = False):
    """Ground-truth-friendly synthetic fleet window as host arrays.

    ``full_cpu=True`` makes every node's workload CPU sum exactly equal
    the node delta (the conservation-test configuration).
    """
    rng = np.random.default_rng(seed)
    cpu = rng.uniform(0.01, 5.0, (n_nodes, n_workloads)).astype(np.float32)
    valid = np.zeros((n_nodes, n_workloads), bool)
    for i in range(n_nodes):
        valid[i, : rng.integers(1, n_workloads + 1)] = True
    cpu = np.where(valid, cpu, 0.0).astype(np.float32)
    masked_sum = cpu.sum(axis=1, dtype=np.float64)
    if full_cpu:
        node_cpu = masked_sum.astype(np.float32)
    else:
        node_cpu = (masked_sum * rng.uniform(1.0, 1.3, n_nodes)).astype(
            np.float32)
    return dict(
        zone_deltas_uj=rng.uniform(1e6, 5e8, (n_nodes, n_zones)).astype(
            np.float32),
        zone_valid=rng.random((n_nodes, n_zones)) > 0.05,
        usage_ratio=rng.uniform(0.05, 0.95, n_nodes).astype(np.float32),
        cpu_deltas=cpu,
        workload_valid=valid,
        node_cpu_delta=node_cpu,
        dt_s=np.full(n_nodes, 5.0, np.float32),
    )


def measure_ratio_accuracy(n_nodes: int = 256, n_workloads: int = 64,
                           n_zones: int = 4, seed: int = 0) -> dict:
    """Run the einsum-f32 device path on a synthetic fleet and compare to
    the f64 reference. → dict of error fields (keys prefixed ratio_f32_)."""
    import jax.numpy as jnp

    from kepler_tpu.ops.attribution import attribute_fleet

    fleet = synthetic_fleet(n_nodes, n_workloads, n_zones, seed)
    ref = reference_attribution_f64(**fleet)
    res = attribute_fleet(
        jnp.asarray(fleet["zone_deltas_uj"]),
        jnp.asarray(fleet["zone_valid"]),
        jnp.asarray(fleet["usage_ratio"]),
        jnp.asarray(fleet["cpu_deltas"]),
        jnp.asarray(fleet["workload_valid"]),
        jnp.asarray(fleet["node_cpu_delta"]),
        jnp.asarray(fleet["dt_s"]),
    )
    wl_power = np.asarray(res.workloads.power_uw)
    wl_energy = np.asarray(res.workloads.energy_uj)
    # 1000 µW = 1 mW floor: watts below that are attribution dust
    rel_power = max_rel_err(wl_power, ref.workload_power_uw, floor=1e3)
    rel_energy = max_rel_err(wl_energy, ref.workload_energy_uj, floor=1e3)
    rel_node = max_rel_err(np.asarray(res.node.active_power_uw),
                           ref.node_active_power_uw, floor=1e3)
    # conservation holds when workload CPU sums to the node delta — use a
    # full-CPU fleet for that invariant (same shapes → jit cache hit)
    full = synthetic_fleet(n_nodes, n_workloads, n_zones, seed + 1,
                           full_cpu=True)
    res_full = attribute_fleet(*(jnp.asarray(full[k]) for k in (
        "zone_deltas_uj", "zone_valid", "usage_ratio", "cpu_deltas",
        "workload_valid", "node_cpu_delta", "dt_s")))
    cons = conservation_rel_err(np.asarray(res_full.workloads.energy_uj),
                                np.asarray(res_full.node.active_uj),
                                floor=1e3)
    return {
        "ratio_f32_max_rel_err": rel_power,
        "ratio_f32_energy_max_rel_err": rel_energy,
        "ratio_f32_node_max_rel_err": rel_node,
        "ratio_f32_conservation_rel_err": cons,
        "ratio_f32_ok": bool(max(rel_power, rel_energy, rel_node)
                             <= RATIO_TOL),
    }


def measure_packed_accuracy(program, batch, params) -> dict:
    """Error of the packed f16 transfer path vs the f64 reference, on the
    caller's (already-compiled) packed program and FleetBatch."""
    import jax.numpy as jnp

    from kepler_tpu.parallel.packed import (pack_fleet_inputs,
                                            unpack_fleet_window)

    ratio_nodes = np.asarray(batch.mode) == 0
    ref = reference_attribution_f64(
        zone_deltas_uj=np.asarray(batch.zone_deltas_uj),
        zone_valid=np.asarray(batch.zone_valid),
        usage_ratio=np.asarray(batch.usage_ratio),
        cpu_deltas=np.asarray(batch.cpu_deltas),
        workload_valid=np.asarray(batch.workload_valid),
        node_cpu_delta=np.asarray(batch.node_cpu_delta),
        dt_s=np.asarray(batch.dt_s),
    )
    out = np.asarray(
        program(params, jnp.asarray(pack_fleet_inputs(batch))), np.float64)
    watts, node_watts, node_total = unpack_fleet_window(out)
    # compare only RAPL-ratio nodes: estimator-mode nodes have no RAPL
    # ground truth by construction
    ref_w = ref.workload_power_uw[ratio_nodes] * 1e-6  # µW → W
    ref_n = ref.node_active_power_uw[ratio_nodes] * 1e-6
    ref_t = ref.node_power_uw[ratio_nodes] * 1e-6
    rel = max_rel_err(watts[ratio_nodes], ref_w, floor=1e-3)  # > 1 mW
    rel_node = max_rel_err(node_watts[ratio_nodes], ref_n, floor=1e-3)
    # the TOTAL row is what the aggregator's packed path publishes as
    # node power (energy = total × dt) — hold it to the same budget
    rel_total = max_rel_err(node_total[ratio_nodes], ref_t, floor=1e-3)
    return {
        "packed_f16_max_rel_err": rel,
        "packed_f16_node_max_rel_err": rel_node,
        "packed_f16_node_total_max_rel_err": rel_total,
        "packed_f16_ok": bool(max(rel, rel_node, rel_total) <= RATIO_TOL),
    }


ESTIMATOR_P99_TOL = 0.005  # every family gates on p99 ≤ 0.5%


def fit_scan(forward, params, workload_valid, target_watts,
             steps: int, learning_rate: float = 1e-2):
    """Full-batch fit as ONE device program (`lax.scan` over the train
    step) — the host pays one dispatch, not one per step.

    ``forward(params) → pred_watts`` closes over the (family-specific)
    inputs. Loss is the RELATIVE masked MSE — the north star is a
    percent-of-ground-truth bound, so the optimizer must weight the small
    workloads' tail, not just the big ones. Adam + cosine decay, no weight
    decay: decay regularizes toward zero weights, which is a systematic
    bias away from the exact fit the accuracy gate demands. The scan
    carries the best-loss params seen, so a warm-started model can only be
    improved by fine-tuning, never degraded by a wandering step.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from kepler_tpu.models.train import masked_relative_mse

    schedule = optax.cosine_decay_schedule(learning_rate, steps, alpha=1e-3)
    optimizer = optax.adam(schedule)

    def loss_fn(p):
        return masked_relative_mse(forward(p), target_watts, workload_valid)

    @jax.jit
    def run(params):
        opt_state = optimizer.init(params)
        best = (params, loss_fn(params))

        def step(carry, _):
            params, opt_state, best = carry
            loss, grads = jax.value_and_grad(loss_fn)(params)
            best_p, best_l = best
            keep = loss < best_l
            best = (jax.tree.map(
                lambda new, old: jnp.where(keep, new, old), params, best_p),
                jnp.minimum(loss, best_l))
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state,
                    best), loss

        (params, _, best), _ = jax.lax.scan(
            step, (params, opt_state, best), jnp.arange(steps))
        # the final step's params were never themselves evaluated
        final_l = loss_fn(params)
        best_p, best_l = best
        keep = final_l < best_l
        return (jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                             params, best_p),
                jnp.minimum(final_l, best_l))

    return run(params)


def _learnable_fleet(n_nodes, n_workloads, n_zones, seed,
                     k_uw_per_cpu_s: np.ndarray):
    """Synthetic fleet whose ground truth IS predictable from the features
    (the model-serving premise). ``k_uw_per_cpu_s`` is [Z] or [N, Z]:
    setting zone_delta[n,z] = k[n,z] · node_cpu · dt / usage_ratio gives
    active_power[n,z] = k[n,z] · node_cpu, hence workload watts =
    k[n,z] · cpu_delta[n,w] — power proportional to CPU time."""
    fleet = synthetic_fleet(n_nodes, n_workloads, n_zones, seed)
    k = np.broadcast_to(np.asarray(k_uw_per_cpu_s, np.float64),
                        (n_nodes, n_zones))
    fleet["zone_deltas_uj"] = (
        k * (fleet["node_cpu_delta"][:, None].astype(np.float64)
             * fleet["dt_s"][:, None]
             / np.clip(fleet["usage_ratio"], 0.05, 1.0)[:, None])
    ).astype(np.float32)
    fleet["zone_valid"] = np.ones((n_nodes, n_zones), bool)
    return fleet


def _err_stats(pred, refw, vmask) -> tuple[float, float]:
    """(median, p99) relative error over valid rows with |ref| > 0.1 W."""
    sig = vmask[:, :, None] & (np.abs(refw) > 0.1)
    err = (np.abs(np.asarray(pred, np.float64) - refw)
           / np.maximum(np.abs(refw), 1e-12))[sig]
    return float(np.median(err)), float(np.quantile(err, 0.99))


def measure_estimator_accuracy(n_nodes: int = 64, n_workloads: int = 32,
                               n_zones: int = 2, steps: int = 1500,
                               seed: int = 3) -> dict:
    """See _measure_estimator_accuracy. Runs under matmul precision
    HIGHEST: TPU "f32" matmuls default to one bf16 MXU pass (~1e-3 relative
    noise — twice the whole 0.5% budget); the accuracy-mode configuration
    pays the 3-pass cost, which is invisible at estimator sizes."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _measure_estimator_accuracy(n_nodes, n_workloads, n_zones,
                                           steps, seed)


def _measure_estimator_accuracy(n_nodes: int = 64, n_workloads: int = 32,
                                n_zones: int = 2, steps: int = 1500,
                                seed: int = 3) -> dict:
    """Fit ALL FIVE estimator families against RAPL-ratio labels on a
    synthetic fleet (the reference train/serve split: learn on RAPL nodes,
    serve no-RAPL nodes) and report median + p99 relative error of
    predicted vs f64 ground-truth watts. Every family must land p99 within
    the 0.5% north-star budget (`*_fit_p99_rel_err` ≤ ESTIMATOR_P99_TOL).

    linear solves in closed form (`fit_linear_exact` — how linear
    regression is actually fit); the nonlinear families train their
    wide-and-deep skip + trunk with the relative loss. Evaluation runs the
    f32 compute path (the accuracy-mode serving configuration; bf16 is the
    throughput mode).
    """
    import functools

    import jax
    import jax.numpy as jnp

    from kepler_tpu.models import build_features, init_linear, init_mlp
    from kepler_tpu.models.deep import init_deep, predict_deep
    from kepler_tpu.models.linear import fit_linear_exact, predict_linear
    from kepler_tpu.models.mlp import predict_mlp
    from kepler_tpu.models.moe import init_moe, predict_moe
    from kepler_tpu.models.temporal import init_temporal, predict_temporal

    f32 = jnp.float32
    k_z = np.linspace(2e6, 6e6, n_zones)  # µW per cpu-second, per zone
    fleet = _learnable_fleet(n_nodes, n_workloads, n_zones, seed, k_z)
    ref = reference_attribution_f64(**fleet)
    refw = ref.workload_power_uw * 1e-6  # W
    target = jnp.asarray(refw, jnp.float32)
    feats = build_features(
        jnp.asarray(fleet["cpu_deltas"]),
        jnp.asarray(fleet["workload_valid"]),
        jnp.asarray(fleet["node_cpu_delta"]),
        jnp.asarray(fleet["usage_ratio"]),
        jnp.asarray(fleet["dt_s"]),
    )
    valid = jnp.asarray(fleet["workload_valid"])
    vmask = fleet["workload_valid"]
    out = {}

    # -- linear: closed-form least squares --------------------------------
    fitted = fit_linear_exact(feats, valid, target)
    med, p99 = _err_stats(predict_linear(fitted, feats, valid), refw, vmask)
    out["linear_fit_median_rel_err"] = med
    out["linear_fit_p99_rel_err"] = p99

    # -- mlp / deep: wide-and-deep fit on the same fleet ------------------
    from kepler_tpu.models.train import warm_start_moe, warm_start_wide

    for name, init, predict, lr in (
        ("mlp", init_mlp, predict_mlp, 1e-3),
        ("deep", init_deep, predict_deep, 1e-3),
    ):
        params = init(jax.random.PRNGKey(0), n_zones=n_zones)
        params = warm_start_wide(params, feats, valid, target)
        pfn = functools.partial(predict, features=feats,
                                workload_valid=valid, clamp=False,
                                compute_dtype=f32)
        fitted, loss = fit_scan(pfn, params, valid, target, steps=steps,
                                learning_rate=lr)
        med, p99 = _err_stats(
            predict(fitted, feats, valid, compute_dtype=f32), refw, vmask)
        out[f"{name}_fit_median_rel_err"] = med
        out[f"{name}_fit_p99_rel_err"] = p99
        out[f"{name}_fit_loss"] = float(loss)

    # -- moe: heterogeneous fleet, per-node-type coefficients, explicit
    #    routing (the kepler-model-server per-platform-model capability) --
    n_experts = 4
    rng = np.random.default_rng(seed + 10)
    expert_id = rng.integers(0, n_experts, n_nodes)
    k_per_type = k_z[None, :] * (1.0 + 0.4 * np.arange(n_experts))[:, None]
    moe_fleet = _learnable_fleet(n_nodes, n_workloads, n_zones, seed + 11,
                                 k_per_type[expert_id])
    moe_ref = reference_attribution_f64(**moe_fleet)
    moe_refw = moe_ref.workload_power_uw * 1e-6
    moe_target = jnp.asarray(moe_refw, jnp.float32)
    moe_feats = build_features(
        jnp.asarray(moe_fleet["cpu_deltas"]),
        jnp.asarray(moe_fleet["workload_valid"]),
        jnp.asarray(moe_fleet["node_cpu_delta"]),
        jnp.asarray(moe_fleet["usage_ratio"]),
        jnp.asarray(moe_fleet["dt_s"]),
    )
    moe_valid = jnp.asarray(moe_fleet["workload_valid"])
    eid = jnp.asarray(expert_id, jnp.int32)
    params = init_moe(jax.random.PRNGKey(0), n_zones=n_zones,
                      n_experts=n_experts)
    params = warm_start_moe(params, moe_feats, moe_valid, moe_target, eid)
    moe_fn = functools.partial(predict_moe, features=moe_feats,
                               workload_valid=moe_valid, clamp=False,
                               compute_dtype=f32, expert_id=eid)
    fitted, loss = fit_scan(moe_fn, params, moe_valid, moe_target,
                            steps=steps, learning_rate=1e-3)
    med, p99 = _err_stats(
        predict_moe(fitted, moe_feats, moe_valid, compute_dtype=f32,
                    expert_id=eid),
        moe_refw, moe_fleet["workload_valid"])
    out["moe_fit_median_rel_err"] = med
    out["moe_fit_p99_rel_err"] = p99
    out["moe_fit_loss"] = float(loss)

    # -- temporal: history windows, target = last tick's watts ------------
    t_hist = 8
    rngt = np.random.default_rng(seed + 20)
    lengths = rngt.integers(1, t_hist + 1, (n_nodes, n_workloads))
    ticks = [_learnable_fleet(n_nodes, n_workloads, n_zones,
                              seed + 30 + t, k_z) for t in range(t_hist)]
    feat_all = np.stack(
        [np.asarray(build_features(
            jnp.asarray(tk["cpu_deltas"]),
            jnp.asarray(tk["workload_valid"]),
            jnp.asarray(tk["node_cpu_delta"]),
            jnp.asarray(tk["usage_ratio"]),
            jnp.asarray(tk["dt_s"]),
        )) for tk in ticks], axis=-2)  # [N, W, T, F] in tick order
    # HistoryBuffer convention: ragged windows right-pad (valid PREFIX), so
    # a length-L workload holds ticks t_hist-L … t_hist-1 at positions
    # 0 … L-1 — the current tick is always the LAST VALID position
    pos = np.arange(t_hist)[None, None, :]
    idx = np.clip(t_hist - lengths[..., None] + pos, 0, t_hist - 1)
    hist_feats = jnp.asarray(
        np.take_along_axis(feat_all, idx[..., None], axis=2))
    tv = jnp.asarray(pos < lengths[..., None])
    last_tick = ticks[-1]
    tmp_ref = reference_attribution_f64(**last_tick)
    tmp_refw = tmp_ref.workload_power_uw * 1e-6
    tmp_target = jnp.asarray(tmp_refw, jnp.float32)
    tmp_valid = jnp.asarray(last_tick["workload_valid"])
    params = init_temporal(jax.random.PRNGKey(0), n_zones=n_zones,
                           t_max=t_hist)
    # warm start against the CURRENT tick's features (the skip's input)
    last_feats = jnp.asarray(feat_all[:, :, -1])
    params = warm_start_wide(params, last_feats, tmp_valid, tmp_target)
    tmp_fn = functools.partial(predict_temporal, feat_hist=hist_feats,
                               workload_valid=tmp_valid, t_valid=tv,
                               clamp=False, compute_dtype=f32)
    fitted, loss = fit_scan(tmp_fn, params, tmp_valid, tmp_target,
                            steps=steps, learning_rate=1e-3)
    med, p99 = _err_stats(
        predict_temporal(fitted, hist_feats, tmp_valid, t_valid=tv,
                         compute_dtype=f32),
        tmp_refw, last_tick["workload_valid"])
    out["temporal_fit_median_rel_err"] = med
    out["temporal_fit_p99_rel_err"] = p99
    out["temporal_fit_loss"] = float(loss)

    out["estimator_accuracy_ok"] = bool(all(
        out[f"{n}_fit_p99_rel_err"] <= ESTIMATOR_P99_TOL
        for n in ("linear", "mlp", "deep", "moe", "temporal")))
    return out


def measure_nonlinear_accuracy(n_nodes: int = 64, n_workloads: int = 32,
                               n_zones: int = 2, steps: int = 8000,
                               seed: int = 9) -> dict:
    """NONLINEAR ground truth: the wide path alone cannot fit this — the
    trunk has to learn it, so this row guards against the linear fleet
    benchmark overstating what the estimators can do.

    Construction: active_power[n,z] = k_z · node_cpu · mod(node_cpu) with
    mod = 1 + 0.3·tanh((node_cpu − 80)/40) — a smooth load-dependent
    efficiency curve (light nodes run 30% cheaper per cpu-second than
    saturated ones, the shape real power curves have). Workload watts
    k_z · cpu · mod(node_cpu) are NOT linear in the features; the wide
    path alone leaves ~15% error (reported as *_linear_only_*), the trunk
    must close the rest. Gated at a looser 2% p99 (the nonlinear-
    regression bar; the 0.5% north star applies to the ratio/linear
    serving paths measured above).
    """
    import functools

    import jax
    import jax.numpy as jnp

    from kepler_tpu.models import build_features, init_mlp
    from kepler_tpu.models.mlp import predict_mlp
    from kepler_tpu.models.train import warm_start_wide

    with jax.default_matmul_precision("highest"):
        k_z = np.linspace(2e6, 6e6, n_zones)
        # same RNG stream as _learnable_fleet(seed): probing node_cpu first
        # then rebuilding with the per-node modulated k yields one fleet
        probe = synthetic_fleet(n_nodes, n_workloads, n_zones, seed)
        mod = 1.0 + 0.3 * np.tanh(
            (probe["node_cpu_delta"].astype(np.float64) - 80.0) / 40.0)
        fleet = _learnable_fleet(n_nodes, n_workloads, n_zones, seed,
                                 k_z[None, :] * mod[:, None])
        ref = reference_attribution_f64(**fleet)
        refw = ref.workload_power_uw * 1e-6
        target = jnp.asarray(refw, jnp.float32)
        feats = build_features(
            jnp.asarray(fleet["cpu_deltas"]),
            jnp.asarray(fleet["workload_valid"]),
            jnp.asarray(fleet["node_cpu_delta"]),
            jnp.asarray(fleet["usage_ratio"]),
            jnp.asarray(fleet["dt_s"]),
        )
        valid = jnp.asarray(fleet["workload_valid"])
        params = warm_start_wide(
            init_mlp(jax.random.PRNGKey(0), n_zones=n_zones),
            feats, valid, target)
        pfn = functools.partial(predict_mlp, features=feats,
                                workload_valid=valid, clamp=False,
                                compute_dtype=jnp.float32)
        fitted, loss = fit_scan(pfn, params, valid, target, steps=steps,
                                learning_rate=3e-3)
        med, p99 = _err_stats(
            predict_mlp(fitted, feats, valid, compute_dtype=jnp.float32),
            refw, fleet["workload_valid"])
        # the wide warm start ALONE (trunk untouched): how much the trunk
        # actually contributed
        med0, p99_0 = _err_stats(
            predict_mlp(params, feats, valid, compute_dtype=jnp.float32),
            refw, fleet["workload_valid"])
    return {
        "mlp_nonlinear_fit_median_rel_err": med,
        "mlp_nonlinear_fit_p99_rel_err": p99,
        "mlp_nonlinear_fit_loss": float(loss),
        "mlp_nonlinear_linear_only_p99_rel_err": p99_0,
        "mlp_nonlinear_linear_only_median_rel_err": med0,
        "nonlinear_accuracy_ok": bool(p99 <= 0.02),
    }


def run_all(packed_program=None, packed_batch=None, packed_params=None,
            estimator_steps: int = 1500) -> dict:
    """Everything the bench JSON line needs. Caller may pass an
    already-compiled packed program (+ its batch/params) to reuse the
    headline-bench compile; otherwise the packed check is skipped."""
    out = measure_ratio_accuracy()
    if packed_program is not None:
        out.update(measure_packed_accuracy(packed_program, packed_batch,
                                           packed_params))
    out.update(measure_estimator_accuracy(steps=estimator_steps))
    out.update(measure_nonlinear_accuracy())
    out["accuracy_ok"] = bool(out["ratio_f32_ok"]
                              and out.get("packed_f16_ok", True)
                              and out["estimator_accuracy_ok"]
                              and out["nonlinear_accuracy_ok"])
    return out
