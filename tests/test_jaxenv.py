"""The two process-level seams every binary shares (utils/jaxenv.py):
where the compile cache lives, and which platform a process may use.

``tpu.platform`` had no reader for twenty PRs: a host without a chip served
from the CPU and said nothing, and a node agent beside an aggregator took
the chip. These tests pin the wiring — at the helper, and end to end
through the two binaries in child processes (the platform must be pinned
before the backend starts, so it cannot be tested in this process).
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from kepler_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    import jax

    calls: list[tuple] = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


class TestCompileCache:
    def test_environment_variable_wins_and_no_directory_is_set_in_code(
            self, monkeypatch, updates):
        monkeypatch.setenv(jaxenv.CACHE_ENV, "/somewhere/else")
        assert jaxenv.configure_compile_cache("/from/config") == \
            "/somewhere/else"
        assert not [c for c in updates
                    if c[0] == "jax_compilation_cache_dir"]

    def test_unset_gives_the_fixed_in_checkout_path(self, monkeypatch,
                                                    updates):
        monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
        path = jaxenv.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert ("jax_compilation_cache_dir", path) in updates
        # fixed: the path is part of the cache key, so a second process
        # (and a second call) must name the very same directory
        assert jaxenv.configure_compile_cache() == path

    def test_config_value_beats_the_default_only(self, monkeypatch, updates):
        monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
        assert jaxenv.configure_compile_cache("/from/config") == \
            "/from/config"
        assert ("jax_compilation_cache_dir", "/from/config") in updates

    def test_every_compile_is_kept(self, monkeypatch, updates):
        """The default threshold would skip the sub-second compiles (the
        scatter-updates), and a restart would redo them."""
        monkeypatch.setenv(jaxenv.CACHE_ENV, "/somewhere/else")
        jaxenv.configure_compile_cache()
        assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates

    def test_the_default_is_gitignored(self):
        with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
            assert ".jax_cache/" in f.read().split()


class TestSelectPlatform:
    def test_auto_sets_nothing(self, updates):
        jaxenv.select_platform("auto")
        assert updates == []

    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_pin_sets_jax_platforms(self, updates, platform):
        jaxenv.select_platform(platform)
        assert updates == [("jax_platforms", platform)]

    def test_require_reports_what_jax_found(self):
        info = jaxenv.require_devices("auto")
        assert info.platform == "cpu" and info.count >= 1
        assert jaxenv.require_devices("cpu") == info

    def test_require_refuses_a_platform_that_is_not_there(self):
        # this process runs on the CPU (conftest): asking for the chip
        # after the fact must not pass for having one
        with pytest.raises(RuntimeError, match="tpu.platform=tpu"):
            jaxenv.require_devices("tpu")


def run_binary(module: str, *args: str, cache_dir: str,
               env: dict | None = None) -> subprocess.CompletedProcess:
    base = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    base["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return subprocess.run(
        [sys.executable, "-m", module, *args], cwd=REPO,
        env={**base, **(env or {})}, capture_output=True, text=True,
        timeout=120)


class TestBinaries:
    def test_aggregator_with_platform_tpu_refuses_to_start_without_one(
            self, tmp_path):
        proc = run_binary("kepler_tpu.cmd.aggregator", "--tpu.platform=tpu",
                          "--aggregator.listen-address=127.0.0.1:0",
                          cache_dir=str(tmp_path))
        assert proc.returncode != 0
        assert "tpu.platform=tpu but JAX found no tpu device" in proc.stderr
        assert "Traceback" not in proc.stderr

    def test_platform_tpu_overrides_a_cpu_pin_in_the_environment(
            self, tmp_path):
        """The config is what the operator wrote down; a stray
        JAX_PLATFORMS=cpu must not turn 'tpu' into a quiet CPU server."""
        proc = run_binary("kepler_tpu.cmd.aggregator", "--tpu.platform=tpu",
                          "--aggregator.listen-address=127.0.0.1:0",
                          cache_dir=str(tmp_path),
                          env={"JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert "tpu.platform=tpu" in proc.stderr

    def test_node_agent_default_initialises_only_the_cpu_backend(
            self, tmp_path):
        """``auto`` in the node binary is ``cpu``: the agent does not own
        the chip. Probe in a child that runs the binary's own start-up
        path up to the backend, then lists what jax initialised."""
        code = (
            "import sys\n"
            "from kepler_tpu.cmd import main as m\n"
            "def stop(services):\n"
            "    import jax\n"
            "    print('BACKENDS', sorted(jax._src.xla_bridge._backends))\n"
            "    print('DEFAULT', jax.devices()[0].platform)\n"
            "    sys.exit(0)\n"
            "m.init_services = stop\n"
            "m.main(['--config.file', sys.argv[1]])\n")
        cfg = tmp_path / "node.yaml"
        cfg.write_text("dev: {fake-cpu-meter: {enabled: true}}\n"
                       "web: {listenAddresses: ['127.0.0.1:0']}\n")
        base = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        base["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        proc = subprocess.run([sys.executable, "-c", code, str(cfg)],
                              cwd=REPO, env=base, capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "BACKENDS ['cpu']" in proc.stdout
        assert "DEFAULT cpu" in proc.stdout
        assert "jax platform=cpu" in proc.stdout + proc.stderr
