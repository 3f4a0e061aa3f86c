"""chip_smoke.py's logic at a tiny fleet, against a CPU child.

The script's own ``__main__`` always demands ``tpu``; here the expected
platform is passed in explicitly, so tier-1 covers everything but the
chip: the wire-v2 window sequence (keyframes, deltas, "nothing changed",
a leave, a join), the NumPy reference and both tolerances, the
``/debug/window`` + ``/metrics`` assertions, the clean-shutdown check —
and that a demoted window or a CPU platform FAILS the smoke.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(expect_platform="cpu", n_nodes=8, pods=(4, 4), interval=0.25,
            stale_after=3.0, node_bucket=8, workload_bucket=8)


def child_env(tmp_path, devices: int = 1) -> dict:
    """The child's environment: the CPU, ``devices`` of them, and a compile
    cache outside the checkout (the variable wins over the in-checkout
    default, so a test run leaves no ``.jax_cache`` behind)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


class TestLegOnCpuChild:
    def test_default_leg_passes_and_reports_the_device(self, tmp_path):
        out = chip_smoke.run_leg("a", workdir=str(tmp_path),
                                 env=child_env(tmp_path), **TINY)
        assert (out["platform"], out["device_kind"], out["devices"]) == (
            "cpu", "cpu", 1)
        # every window was compared: the f16 quantization is visible, and
        # inside the repo's 0.5 % budget
        assert 0 < out["ratio_workload"] <= 0.005
        assert 0 < out["conservation"] <= 0.005
        # off-TPU the engine serves f32, far inside the bf16 bound
        assert 0 < out["model_bound_use"] < 0.5
        # the child kept its compile cache where the variable said
        assert os.listdir(tmp_path / "jax_cache")
        assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))

    def test_sharded_leg_spreads_rows_and_uploads(self, tmp_path):
        """More than one device: the default path is the sharded engine,
        and the smoke checks rows and H2D are spread over every shard."""
        out = chip_smoke.run_leg(
            "a", workdir=str(tmp_path), env=child_env(tmp_path, devices=4),
            **{**TINY, "n_nodes": 16, "node_bucket": 16})
        assert out["devices"] == 4

    def test_fused_pallas_ratio_leg_passes(self, tmp_path):
        out = chip_smoke.run_leg(
            "c", workdir=str(tmp_path), env=child_env(tmp_path),
            backend="pallas", model=False, fused_k=4, **TINY)
        assert out["model_workload"] == 0.0  # no model rows in this leg
        assert 0 < out["ratio_workload"] <= 0.005

    def test_a_demoted_window_fails_the_smoke(self, tmp_path):
        """The ladder would hide a device-path failure behind NumPy
        results and exit 0; the smoke is what looks past it."""
        fault = {"fault": {"enabled": True, "specs": [
            {"site": "device.dispatch_error", "skip": 4, "count": 1}]},
            "aggregator": {"fallbackEnabled": True}}
        with pytest.raises(chip_smoke.SmokeFailure, match="demoted|rung"):
            chip_smoke.run_leg("a", workdir=str(tmp_path),
                               env=child_env(tmp_path), extra_config=fault,
                               **TINY)

    def test_a_failed_window_without_the_ladder_fails_too(self, tmp_path):
        """The smoke's own configuration (fallbackEnabled: false): the
        run loop logs the failure and carries on; the log check fails."""
        fault = {"fault": {"enabled": True, "specs": [
            {"site": "device.dispatch_error", "skip": 4, "count": 1}]}}
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.run_leg("a", workdir=str(tmp_path),
                               env=child_env(tmp_path), extra_config=fault,
                               **TINY)

    def test_the_wrong_platform_fails(self, tmp_path):
        """A CPU child under a smoke that expects the chip: the binary
        itself refuses to start (tpu.platform: tpu, no TPU here)."""
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="tpu.platform=tpu"):
            chip_smoke.run_leg("a", workdir=str(tmp_path),
                               env=child_env(tmp_path),
                               **{**TINY, "expect_platform": "tpu"})


class TestMain:
    def test_cpu_pin_in_the_environment_fails_with_one_line(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert proc.stderr.strip().count("\n") == 0
        assert "chip_smoke: FAIL" in proc.stderr
        assert '"ok"' not in proc.stdout  # no result line

    def test_alone_in_a_directory_fails(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


class TestReference:
    def test_model_reference_matches_the_repos_numpy_mirror(self):
        """The smoke's f64 forward and the aggregator's rung-3 NumPy
        mirror are written independently; they agree to f32 rounding."""
        from kepler_tpu.parallel.packed import (_numpy_features,
                                                _numpy_model_watts)

        fleet = chip_smoke.Fleet(16, (3, 6), model=True)
        fleet.advance(0)
        params = chip_smoke.make_params(chip_smoke.SEED, 4)
        node_cpu = np.asarray([fleet.node_cpu(i) for i in range(16)],
                              np.float32)
        want, bound = chip_smoke.model_reference(
            params, fleet.cpu, fleet.valid, node_cpu, fleet.ratio,
            chip_smoke.DT_S)
        feats = _numpy_features(fleet.cpu, fleet.valid, node_cpu,
                                fleet.ratio, np.full(16, chip_smoke.DT_S,
                                                     np.float32))
        mirror = _numpy_model_watts("mlp", params, feats, fleet.valid)
        np.testing.assert_allclose(want, mirror, rtol=1e-4, atol=1e-5)
        assert (want[fleet.valid] > 0.05).all()  # no vacuous model rows
        assert (bound[fleet.valid] > 0).all()
        assert (bound[~fleet.valid] == 0).all()

    def test_bf16_operands_stay_inside_the_derived_bound(self):
        """What no CPU test of the engine sees: the estimator with bf16
        operands (what the packed program runs on a TPU), held to the
        tolerance the smoke derives for it."""
        import jax.numpy as jnp

        from kepler_tpu.models.features import build_features
        from kepler_tpu.models.mlp import predict_mlp

        fleet = chip_smoke.Fleet(32, (20, 30), model=True)
        fleet.advance(0)
        params = chip_smoke.make_params(chip_smoke.SEED, 4)
        node_cpu = np.asarray([fleet.node_cpu(i) for i in range(32)],
                              np.float32)
        want, bound = chip_smoke.model_reference(
            params, fleet.cpu, fleet.valid, node_cpu, fleet.ratio,
            chip_smoke.DT_S)
        feats = build_features(
            jnp.asarray(fleet.cpu), jnp.asarray(fleet.valid),
            jnp.asarray(node_cpu), jnp.asarray(fleet.ratio),
            jnp.full(32, chip_smoke.DT_S))
        got = np.asarray(predict_mlp(
            {k: jnp.asarray(v) for k, v in params.items()}, feats,
            jnp.asarray(fleet.valid), compute_dtype=jnp.bfloat16),
            np.float64)
        err = np.abs(got - want)[fleet.valid]
        tol = (chip_smoke.SLACK * bound + chip_smoke.F16_REL * want)[
            fleet.valid]
        assert err.max() > 1e-5  # bf16 really was in play
        assert (err <= tol).all()
        # and the bound is worth having: a few percent of the value
        assert np.median(tol / want[fleet.valid]) < 0.15

    def test_a_stale_row_is_a_wrong_number(self):
        """The window check has teeth: publish window k's changed node
        with its window k-1 content and the comparison fails."""
        fleet = chip_smoke.Fleet(8, (4, 4), model=False)
        fleet.advance(0)
        live = fleet.reporting(0)

        def published() -> dict:
            nodes = {}
            for i in live:
                n_p = int(fleet.n_pods[i])
                share = fleet.cpu[i, :n_p] / fleet.node_cpu(i)
                total = np.where(fleet.zone_valid[i], fleet.zone[i],
                                 0.0) / chip_smoke.DT_S
                active = total * fleet.ratio[i]
                nodes[fleet.names[i]] = {
                    "zones": list(chip_smoke.ZONES),
                    "mode": int(fleet.mode[i]),
                    "node_power_uw": total.tolist(),
                    "workloads": [
                        {"id": wid, "power_uw": (s * active).tolist()}
                        for wid, s in zip(fleet.ids[i], share)]}
            return nodes

        stale = published()
        chip_smoke.check_window(fleet, live, stale, None)  # in sync: fine
        fleet.advance(1)
        assert fleet.changed
        with pytest.raises(chip_smoke.SmokeFailure, match="reference"):
            chip_smoke.check_window(fleet, fleet.reporting(1), stale, None)
