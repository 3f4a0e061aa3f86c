"""Multi-process worker for the cross-host fleet-program test.

Each process initializes ``jax.distributed`` (CPU backend, Gloo
collectives — the DCN stand-in), joins a GLOBAL mesh spanning both
processes' devices, device_puts its node-axis shard of one deterministic
fleet batch, and runs the SAME sharded attribution program the
aggregator serves. It prints a JSON line with conservation figures and a
digest of the node powers; the parent test asserts both processes agree
with each other and with a single-process reference.

Run by ``tests/test_multihost.py`` — not a test module itself.
"""

from __future__ import annotations

import hashlib
import json
import sys

# -- shared two-process spawn/skip/retry vocabulary -------------------------
# THE one copy used by both tests/test_multihost.py and the
# `make multihost` gate (__graft_entry__._dryrun_multihost_two_process):
# the skip markers and the bind-collision retry must never diverge
# between the two gates.

# error-text markers that mean the jax build simply cannot run
# cross-process computations on CPU (no Gloo collective backend) — a
# clean SKIP, not an error: the gate is environmental there by design
UNSUPPORTED_MARKERS = (
    "multiprocess computations aren't implemented",
    "not implemented on the cpu backend",
    "unimplemented",
    "gloo",
    "distributed service is not supported",
)

# a coordinator port raced by another process: retry on a fresh port
BIND_MARKERS = ("address already in use", "failed to bind", "bind error")

# hard wall-clock bound per two-process attempt: a wedged coordinator
# must produce a captured-stderr failure, never a hung run
WORKER_TIMEOUT_S = 240


def unsupported_reason(stderr: str) -> str | None:
    """The matched no-multiprocess-backend marker, or None."""
    low = stderr.lower()
    for marker in UNSUPPORTED_MARKERS:
        if marker in low:
            return marker
    return None


def bind_collision(stderr: str) -> bool:
    low = stderr.lower()
    return any(m in low for m in BIND_MARKERS)


def run_workers(repo: str, n_proc: int, port: int) -> list:
    """Spawn ``n_proc`` workers against one coordinator port; → per-
    worker (rc, stdout, stderr) with a HARD timeout (kill + stderr
    capture — a dead coordinator must not leave its peer blocked
    forever)."""
    import os
    import subprocess

    pythonpath = repo + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": pythonpath.rstrip(os.pathsep)}
    workers = [
        subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "tests", "multihost_worker.py"),
             str(i), str(n_proc), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=repo)
        for i in range(n_proc)
    ]
    results = []
    try:
        for w in workers:
            try:
                out, err = w.communicate(timeout=WORKER_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                w.kill()
                out, err = w.communicate(timeout=30)
                err = (f"[killed after {WORKER_TIMEOUT_S}s timeout]\n"
                       + (err or ""))
                results.append((124, out or "", err))
                continue
            results.append((w.returncode, out, err))
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait(timeout=30)
    return results


def main() -> int:
    pid = int(sys.argv[1])
    n_proc = int(sys.argv[2])
    port = sys.argv[3]

    import jax

    jax.config.update("jax_platforms", "cpu")
    # the same entry point cmd/aggregator calls (env-driven in prod).
    # NOT inside an assert: python -O must still initialize
    from kepler_tpu.parallel import initialize_multihost

    joined = initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=n_proc, process_id=pid)
    if not joined:
        raise RuntimeError("initialize_multihost declined to initialize")

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kepler_tpu.models import init_mlp
    from kepler_tpu.parallel.aggregator_core import make_fleet_program
    from kepler_tpu.parallel.mesh import make_mesh
    from tests.test_multihost import make_global_batch

    devs = jax.devices()  # GLOBAL device list across processes
    mesh = make_mesh()  # the production helper must span every host
    batch = make_global_batch(n_nodes=len(devs) * 4)
    params = init_mlp(jax.random.PRNGKey(0), n_zones=2)
    program = make_fleet_program(mesh, model_mode="mlp")

    by_node_2d = NamedSharding(mesh, P("node", None))
    by_node_1d = NamedSharding(mesh, P("node"))
    args = [
        jax.device_put(params, NamedSharding(mesh, P())),
        jax.device_put(batch.zone_deltas_uj, by_node_2d),
        jax.device_put(batch.zone_valid, by_node_2d),
        jax.device_put(batch.usage_ratio, by_node_1d),
        jax.device_put(batch.cpu_deltas, by_node_2d),
        jax.device_put(batch.workload_valid, by_node_2d),
        jax.device_put(batch.node_cpu_delta, by_node_1d),
        jax.device_put(batch.dt_s, by_node_1d),
        jax.device_put(batch.mode.astype(np.int32), by_node_1d),
    ]
    result = program(*args)
    # replicate the outputs so every process holds the full value (the
    # all_gather rides the cross-process collective backend)
    gather = jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))
    node_power = np.asarray(
        gather(result.node_power_uw).addressable_data(0))
    wl_power = np.asarray(
        gather(result.workload_power_uw).addressable_data(0))
    print(json.dumps({
        "process": pid,
        "global_devices": len(devs),
        "local_devices": len(jax.local_devices()),
        "node_power_digest": hashlib.sha256(
            np.ascontiguousarray(node_power, np.float32).tobytes()
        ).hexdigest(),
        "node_power_sum": float(node_power.sum()),
        "wl_power_sum": float(wl_power.sum()),
        "finite": bool(np.isfinite(node_power).all()
                       and np.isfinite(wl_power).all()),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
