"""The root entry scripts under the one-process-per-chip rule
(``bench.py``, ``__graft_entry__.py``).

A chip belongs to one process: a parent that has touched JAX holds it, and
a child that needs it then fails or hangs. So ``bench.py`` is ONE process
that fails when it finds no accelerator (unless its caller pinned the CPU
on purpose), its host legs run as CPU-pinned children, and the dry runs
of ``__graft_entry__.py`` start their CPU children without ever touching
JAX in the parent.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import __graft_entry__ as ge  # noqa: E402
import bench  # noqa: E402


class FakeJax:
    """Just enough of jax for ``bench._init_jax``."""

    def __init__(self, platform: str) -> None:
        self._platform = platform
        self.config = self
        self.updates: list = []

    def update(self, key, value):
        self.updates.append((key, value))

    def devices(self):
        class Dev:
            platform = self._platform

        return [Dev()]


class TestBenchIsOneProcess:
    def test_no_child_probe_no_cpu_rerun(self):
        """What used to live here and answers a chip that is gone."""
        for gone in ("_supervise", "_relay_child", "SANITIZE_ENV_VARS",
                     "TPU_ATTEMPT_TIMEOUT_S", "CPU_ATTEMPT_TIMEOUT_S"):
            assert not hasattr(bench, gone), gone
        for gone in ("_ensure_responsive_backend", "_backend_initialized",
                     "SANITIZE_ENV_VARS", "_PROBE_TIMEOUT_S"):
            assert not hasattr(ge, gone), gone

    def test_fails_when_jax_finds_no_accelerator(self, monkeypatch):
        monkeypatch.setitem(sys.modules, "jax", FakeJax("cpu"))
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/unused")
        with pytest.raises(SystemExit) as exc:
            bench._init_jax()
        assert "no accelerator" in str(exc.value)

    def test_cpu_on_purpose_is_allowed_and_named(self, monkeypatch):
        monkeypatch.setitem(sys.modules, "jax", FakeJax("cpu"))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/unused")
        _, platform = bench._init_jax()
        assert platform == "cpu"

    def test_accelerator_needs_no_pin(self, monkeypatch):
        fake = FakeJax("tpu")
        monkeypatch.setitem(sys.modules, "jax", fake)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        _, platform = bench._init_jax()
        assert platform == "tpu"
        # it never pins a platform itself, and shares the binaries' cache
        assert not [u for u in fake.updates if u[0] == "jax_platforms"]
        assert ("jax_compilation_cache_dir",
                os.path.join(REPO, ".jax_cache")) in fake.updates

    def test_script_exits_nonzero_without_a_chip(self):
        """The whole script, as the driver would start it on a host with
        no accelerator and no CPU pin: non-zero, one reason, no row."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["JAX_PLATFORMS"] = ""  # jax's own choice: the CPU, here
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        assert "no accelerator" in proc.stderr
        assert '"metric"' not in proc.stdout

    def test_host_legs_stay_cpu_pinned_children(self):
        """The legs that do not need the chip must not take it: each is a
        child with JAX_PLATFORMS=cpu in its environment."""
        import inspect

        src = inspect.getsource(bench.main)
        assert '"JAX_PLATFORMS": "cpu"' in src
        assert src.count("host_leg(") >= 4  # node path, aggwin, ingest, soak


class TestDryrunChild:
    @pytest.fixture
    def popen(self, monkeypatch):
        captured: dict = {}

        class FakeProc:
            stdout = iter(())
            stderr = iter(())

            def wait(self, timeout=None):
                return 0

        def fake(cmd, env=None, **kw):
            captured.update(cmd=cmd, env=dict(env or {}))
            return FakeProc()

        monkeypatch.setattr("subprocess.Popen", fake)
        return captured

    def test_child_is_cpu_pinned_with_n_virtual_devices(self, popen):
        ge._dryrun_in_subprocess(4)
        assert popen["env"]["JAX_PLATFORMS"] == "cpu"
        assert ("--xla_force_host_platform_device_count=4"
                in popen["env"]["XLA_FLAGS"])
        assert "_dryrun_multichip_impl(4)" in popen["cmd"][-1]

    def test_existing_xla_flags_are_kept(self, popen, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/x")
        ge._dryrun_in_subprocess(2)
        assert popen["env"]["XLA_FLAGS"].startswith("--xla_dump_to=/tmp/x ")

    @pytest.mark.parametrize("fn,impl", [
        (ge.dryrun_multichip, "_dryrun_multichip_impl"),
        (ge.dryrun_fleet_sharded, "_dryrun_fleet_sharded_impl"),
    ])
    def test_dry_runs_always_take_the_child(self, popen, fn, impl):
        """Even when this process already has eight devices up (it does:
        conftest), the dry run goes to a CPU child — the parent's backend
        is never consulted, so a caller that holds the chip is safe."""
        fn(8)
        assert f"{impl}(8)" in popen["cmd"][-1]

    def test_parent_of_a_dry_run_never_initialises_jax(self):
        code = (
            "import subprocess, sys\n"
            "class P:\n"
            "    stdout = iter(()); stderr = iter(())\n"
            "    def wait(self, timeout=None): return 0\n"
            "subprocess.Popen = lambda *a, **k: P()\n"
            "import __graft_entry__ as ge\n"
            "ge.dryrun_multichip(8); ge.dryrun_fleet_sharded(8)\n"
            "jax = sys.modules.get('jax')\n"
            "print('BACKENDS', sorted(jax._src.xla_bridge._backends) "
            "if jax else [])\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-500:]
        assert "BACKENDS []" in proc.stdout

    def test_failing_child_raises(self, monkeypatch):
        class Failing:
            stdout = iter(())
            stderr = iter(())

            def wait(self, timeout=None):
                return 3

        monkeypatch.setattr("subprocess.Popen", lambda *a, **k: Failing())
        with pytest.raises(RuntimeError, match="rc=3"):
            ge._dryrun_in_subprocess(2)
