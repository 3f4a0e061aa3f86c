"""The aggregator as a child process, and the HTTP the benchmark speaks.

Copied from ``chip_smoke.py``'s ``AggregatorChild`` (PR 21) and pointed at
``chipbench/launch.py``. The parent never imports JAX: a parent that has
touched JAX holds the chip.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Any

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCHER = os.path.join(REPO, "chipbench", "launch.py")


class BenchFailure(Exception):
    """One-line reason a run produces no result. ``phase`` is the part of
    the run it was raised in (``ready``, ``fill``, ``warmup``, ``window``
    or ``close``), where the raiser knows."""

    phase: str | None = None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class AggregatorChild:
    def __init__(self, config: dict, workdir: str, traced: bool,
                 env: dict | None = None, launcher: str = LAUNCHER) -> None:
        self.port = int(
            config["aggregator"]["listenAddress"].rsplit(":", 1)[1])
        self.log_path = os.path.join(workdir, "aggregator.log")
        self.out_path = os.path.join(workdir, "launch.json")
        self.trace_dir = os.path.join(workdir, "trace") if traced else ""
        cfg_path = os.path.join(workdir, "aggregator.yaml")
        with open(cfg_path, "w", encoding="utf-8") as f:
            json.dump(config, f, indent=1)  # JSON is YAML
        cmd = [sys.executable, launcher, "--config.file", cfg_path,
               "--out", self.out_path]
        if traced:
            cmd += ["--trace-dir", self.trace_dir]
        self.started = time.time()
        with open(self.log_path, "w", encoding="utf-8") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=REPO, env=env if env is not None
                else dict(os.environ), stdout=log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()

    def log_tail(self, lines: int = 3) -> str:
        return " | ".join(self.log_text().strip().splitlines()[-lines:])[-400:]

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = 120.0) -> tuple[int, bytes]:
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str) -> Any:
        status, body = self.request("GET", path)
        if status != 200:
            raise BenchFailure(f"GET {path} -> {status}")
        return json.loads(body)

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise BenchFailure(f"aggregator exited {self.proc.returncode}: "
                               f"{self.log_tail()}")

    def wait_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.alive()
            try:
                status, _ = self.request("GET", "/readyz", timeout=2.0)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.1)
        raise BenchFailure(f"aggregator not ready after {timeout:.0f}s")

    def trace(self, what: str, timeout: float = 120.0) -> float:
        """Ask the launcher to start or stop the profiler and wait for its
        marker → the host time it wrote."""
        sig = signal.SIGUSR1 if what == "start" else signal.SIGUSR2
        self.proc.send_signal(sig)
        mark = os.path.join(self.trace_dir, f"{what}.mark")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.alive()
            if os.path.exists(mark):
                with open(mark, encoding="utf-8") as f:
                    text = f.read()
                if text:
                    return float(text)
            time.sleep(0.02)
        raise BenchFailure(f"the profiler did not {what} in {timeout:.0f}s")

    def stop(self) -> int | None:
        """SIGTERM and wait → the exit code (None: it had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def launch_report(self) -> dict:
        try:
            with open(self.out_path, encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError) as err:
            raise BenchFailure(f"the launcher left no report: {err}; "
                               f"{self.log_tail()}") from err
