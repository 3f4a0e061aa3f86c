"""The train→serve loop: aggregator dump → cmd/train → serve-ready params.

Mirrors the kepler-model-server pipeline (BASELINE configs 3-4): RAPL
nodes' ratio watts become labels; the trained estimator then serves
non-RAPL nodes through the same aggregator it was trained from.
"""

import json
import urllib.request

import jax
import numpy as np
import pytest

from kepler_tpu.cmd.train import load_windows, main as train_main
from kepler_tpu.fleet import Aggregator
from kepler_tpu.fleet.wire import encode_report
from kepler_tpu.models.estimator import load_params, save_params
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
from kepler_tpu.server.http import APIServer
from kepler_tpu.parallel.mesh import make_mesh


@pytest.fixture(autouse=True)
def _compile_cache_stays_put(monkeypatch):
    """train_main places the persistent compile cache like every binary;
    in-process that would point the REST of the test session's compiles
    at <checkout>/.jax_cache. The placement itself is tested in
    tests/test_jaxenv.py."""
    calls = []
    monkeypatch.setattr("kepler_tpu.utils.jaxenv.configure_compile_cache",
                        lambda configured="": calls.append(configured))
    return calls


def feed_reports(agg, n_windows=3, nodes=2, w=4, seed=0):
    rng = np.random.default_rng(seed)

    class Req:
        command = "POST"

    for seq in range(1, n_windows + 1):
        for n in range(nodes):
            cpu = rng.uniform(0.5, 4.0, w).astype(np.float32)
            rep = NodeReport(
                node_name=f"metal-{n}",
                zone_deltas_uj=rng.uniform(1e7, 1e8, 2).astype(np.float32),
                zone_valid=np.ones(2, bool),
                usage_ratio=0.6,
                cpu_deltas=cpu,
                workload_ids=[f"m{n}-w{i}" for i in range(w)],
                node_cpu_delta=float(cpu.sum()),
                dt_s=5.0,
                mode=MODE_RATIO,
            )
            r = Req()
            r.body = encode_report(rep, ["package", "dram"], seq=seq)
            assert agg._handle_report(r)[0] == 204
        agg.aggregate_once()


class TestTrainingDump:
    def test_dump_writes_ratio_rows_with_labels(self, tmp_path):
        agg = Aggregator(APIServer(), model_mode=None,
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=2)
        data, files = load_windows(str(tmp_path / "dump"))
        assert len(files) == 2
        assert data["cpu_deltas"].shape == (4, 8)  # 2 windows × 2 nodes
        assert data["target_watts"].shape[-1] == 2
        # labels: Σ valid workload watts per node == node active power
        valid = data["workload_valid"]
        assert valid.sum() == 2 * 2 * 4
        assert (data["target_watts"][valid] > 0).any()

    def test_model_rows_are_excluded(self, tmp_path):
        agg = Aggregator(APIServer(), model_mode="mlp",
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        rng = np.random.default_rng(0)

        class Req:
            command = "POST"

        cpu = rng.uniform(0.5, 4.0, 3).astype(np.float32)
        rep = NodeReport(
            node_name="vm", zone_deltas_uj=np.zeros(2, np.float32),
            zone_valid=np.zeros(2, bool), usage_ratio=0.5, cpu_deltas=cpu,
            workload_ids=["a", "b", "c"], node_cpu_delta=float(cpu.sum()),
            dt_s=5.0, mode=MODE_MODEL)
        r = Req()
        r.body = encode_report(rep, ["package", "dram"], seq=1)
        agg._handle_report(r)
        agg.aggregate_once()
        import os

        assert not os.path.isdir(str(tmp_path / "dump")) or not os.listdir(
            str(tmp_path / "dump"))

    def test_file_cap_prunes_oldest(self, tmp_path):
        agg = Aggregator(APIServer(), model_mode=None,
                         training_dump_dir=str(tmp_path / "dump"),
                         training_dump_max_files=3,
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=5)
        _, files = load_windows(str(tmp_path / "dump"))
        assert len(files) == 3


class TestTrainCLI:
    @pytest.mark.parametrize("family", ["linear", "mlp", "moe", "deep"])
    def test_end_to_end(self, tmp_path, family):
        agg = Aggregator(APIServer(), model_mode=None,
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=3)
        out = str(tmp_path / "params.npz")
        rc = train_main([
            "--data", str(tmp_path / "dump"), "--model", family,
            "--out", out, "--steps", "30", "--lr", "1e-2",
        ])
        assert rc == 0
        params = load_params(out)
        # serve the trained params through the mixed-fleet program
        serve = Aggregator(APIServer(), model_mode=family,
                           model_params=params, node_bucket=8,
                           workload_bucket=8)
        serve.windows.mesh = make_mesh()
        serve.windows._check_params_shape()
        assert serve.windows._model_out_dim() == 2

    def test_temporal_end_to_end(self, tmp_path):
        """The fifth family closes the same loop: a TEMPORAL aggregator
        dumps ratio nodes' history windows, cmd/train fits from them, and
        a fresh aggregator serves the trained params (VERDICT r3 item 3:
        previously only 4 of 5 families were trainable from fleet
        dumps)."""
        agg = Aggregator(APIServer(), model_mode="temporal",
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8,
                         history_window=4)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=3)
        data, files = load_windows(str(tmp_path / "dump"))
        assert "feat_hist" in data  # history windows captured for training
        assert data["feat_hist"].shape[2] == 4  # T = history_window
        # windows accrete: the last dump's rows carry >1 valid timestep
        assert data["t_valid"][-1].sum() > data["workload_valid"][-1].sum()
        out = str(tmp_path / "params.npz")
        rc = train_main([
            "--data", str(tmp_path / "dump"), "--model", "temporal",
            "--out", out, "--steps", "10", "--lr", "1e-2",
        ])
        assert rc == 0
        params = load_params(out)
        serve = Aggregator(APIServer(), model_mode="temporal",
                           model_params=params, node_bucket=8,
                           workload_bucket=8, history_window=4)
        serve.windows.mesh = make_mesh()
        serve.windows._check_params_shape()
        assert serve.windows._model_out_dim() == 2
        # and the serving program actually runs on the trained params
        feed_reports(serve, n_windows=2, seed=9)
        with serve.windows._results_lock:
            assert serve.windows._results

    def test_temporal_without_history_dumps_errors(self, tmp_path):
        """Single-tick dumps (non-temporal aggregator) can't train the
        temporal family — the CLI must say so, not crash."""
        agg = Aggregator(APIServer(), model_mode=None,
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=1)
        rc = train_main([
            "--data", str(tmp_path / "dump"), "--model", "temporal",
            "--out", str(tmp_path / "p.npz"), "--steps", "5",
        ])
        assert rc == 2

    def test_checkpoint_resume(self, tmp_path):
        agg = Aggregator(APIServer(), model_mode=None,
                         training_dump_dir=str(tmp_path / "dump"),
                         node_bucket=8, workload_bucket=8)
        agg.windows.mesh = make_mesh()
        feed_reports(agg, n_windows=2)
        out = str(tmp_path / "p.npz")
        ck = str(tmp_path / "ckpt")
        train_main(["--data", str(tmp_path / "dump"), "--model", "mlp",
                    "--out", out, "--steps", "20", "--ckpt-dir", ck,
                    "--ckpt-every", "10"])
        # second invocation resumes at 20 and trains on to 40
        rc = train_main(["--data", str(tmp_path / "dump"), "--model", "mlp",
                         "--out", out, "--steps", "40", "--ckpt-dir", ck,
                         "--ckpt-every", "10"])
        assert rc == 0
        from kepler_tpu.models.checkpoint import TrainCheckpointer
        from kepler_tpu.models import init_mlp
        from kepler_tpu.models.train import (
            create_train_state,
            make_optimizer,
        )

        state = create_train_state(
            init_mlp(jax.random.PRNGKey(0), 2), make_optimizer())
        with TrainCheckpointer(ck) as c:
            assert int(c.restore_latest(state).step) == 40

    def test_missing_data_dir_errors(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="window-"):
            load_windows(str(tmp_path))

    def test_places_the_compile_cache_before_the_first_compile(
            self, tmp_path, _compile_cache_stays_put):
        with pytest.raises(FileNotFoundError):
            train_main(["--data", str(tmp_path), "--out",
                        str(tmp_path / "p.npz")])
        assert _compile_cache_stays_put == [""]  # the shared default


class TestNestedParamsRoundtrip:
    def test_deep_params_npz(self, tmp_path):
        from kepler_tpu.models import init_deep

        params = init_deep(jax.random.PRNGKey(0), 2, n_stages=2, d_model=32)
        path = str(tmp_path / "deep.npz")
        save_params(path, params)
        loaded = load_params(path)
        assert set(loaded["blocks"]) == set(params["blocks"])
        jax.tree.map(np.testing.assert_array_equal, dict(params), loaded)


class TestZoneAlignment:
    def test_mixed_zone_files_align_by_name(self, tmp_path):
        """Files from rounds with different zone unions must align columns
        by zone NAME, masking absent zones rather than reading 0-W labels."""
        d = tmp_path / "dump"
        d.mkdir()
        w = 4

        def write(name, zones, zone_valid, watts):
            rows = 1
            np.savez_compressed(
                d / name,
                zone_names=np.asarray(zones),
                zone_valid=np.asarray(zone_valid, bool).reshape(rows, -1),
                cpu_deltas=np.ones((rows, w), np.float32),
                workload_valid=np.ones((rows, w), bool),
                node_cpu_delta=np.full(rows, 4.0, np.float32),
                usage_ratio=np.full(rows, 0.5, np.float32),
                dt_s=np.full(rows, 5.0, np.float32),
                target_watts=np.asarray(watts, np.float32).reshape(
                    rows, w, -1),
            )

        write("window-1-000001.npz", ["core", "package"], [[True, True]],
              np.stack([np.full((1, w), 1.0), np.full((1, w), 2.0)], -1))
        write("window-2-000002.npz", ["dram", "package"], [[True, True]],
              np.stack([np.full((1, w), 3.0), np.full((1, w), 4.0)], -1))
        data, files = load_windows(str(d))
        assert data["zone_names"] == ["core", "dram", "package"]
        assert data["target_watts"].shape == (2, w, 3)
        # row 0 (core+package file): dram column masked, not 0-labelled
        lv = data["label_valid"]
        assert lv[0, :, 0].all() and not lv[0, :, 1].any() \
            and lv[0, :, 2].all()
        assert lv[1, :, 1].all() and not lv[1, :, 0].any()
        np.testing.assert_allclose(data["target_watts"][0, :, 2], 4.0
                                   * 0 + 2.0)
        np.testing.assert_allclose(data["target_watts"][1, :, 1], 3.0)

    def test_node_missing_zone_masks_labels(self, tmp_path):
        """zone_valid False for a row masks its labels in that zone."""
        d = tmp_path / "dump"
        d.mkdir()
        np.savez_compressed(
            d / "window-1-000001.npz",
            zone_names=np.asarray(["dram", "package"]),
            zone_valid=np.asarray([[False, True]]),
            cpu_deltas=np.ones((1, 2), np.float32),
            workload_valid=np.ones((1, 2), bool),
            node_cpu_delta=np.full(1, 2.0, np.float32),
            usage_ratio=np.full(1, 0.5, np.float32),
            dt_s=np.full(1, 5.0, np.float32),
            target_watts=np.zeros((1, 2, 2), np.float32),
        )
        data, _ = load_windows(str(d))
        assert not data["label_valid"][0, :, 0].any()  # dram invalid
        assert data["label_valid"][0, :, 1].all()
