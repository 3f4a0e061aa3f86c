#!/usr/bin/env python3
"""chip_smoke.py — the aggregator's production window path on the chip.

The quickest proof that the system still starts and serves on a TPU. It
does what a fleet does, through the entry point a user calls:

  python -m kepler_tpu.cmd.aggregator --config.file <yaml written here>

with ``tpu.platform: tpu`` and ``aggregator.fallbackEnabled: false``, then
over HTTP: POSTs wire-v2 reports for 1024 nodes × 80–120 pods × 4 zones
(half the nodes model-estimated; ≈102k pods, the README's heavy shape) for
several windows whose inputs differ window to window — a few percent of
nodes change some of their pods, the rest send "nothing changed" deltas,
one node leaves, one joins — reads every published window from
``/v1/results`` and checks it against a NumPy reference of THAT window's
inputs, and reads ``/debug/window`` + ``/metrics`` to make sure the numbers
came from rung 0 on the TPU with no demotion and no compile after warm-up.
A stale or aliased device buffer shows as a wrong number.

Legs, each its own child, one at a time (a chip belongs to one process):

  a  the code default (einsum backend, sparse MLP rows)
  b  tpu.fleetBackend: pallas — what manifests/k8s ships
  c  pallas, ratio-only, fusedWindowK: 4 — the route to fused_window_step
  d  leg a again: must add no entry to the persistent compile cache
  k  device checks in a child of their own: flash_block_pallas under
     Mosaic at the temporal model's shapes, and the donated resident
     buffer really deleted after a window update

This process never touches the JAX backend: a parent that has touched JAX
holds the chip, and its child would fail or hang. Everything it needs —
the params ``.npz``, the YAML, the reports — is generated here from a
seed; nothing git would not commit is read.

Exit 0 and, as the LAST stdout line, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": …, "count": …}}``
with the device as JAX reported it to the aggregator. Any failure —
including no TPU visible — exits non-zero with a one-line reason on
stderr and prints no result. It never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Sequence

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# -- the fleet ---------------------------------------------------------------
SEED = 21
N_NODES = 1024
PODS = (80, 120)  # per node, inclusive
# sorted, as the aggregator publishes its zone axis
ZONES = ("core", "dram", "package", "uncore")
WORKLOAD_BUCKET = 128  # bench.py's N_WORKLOADS_LARGE: W=128, width 140
WINDOWS = 7  # ≥ 6; the last ones run after warm-up and after the leave
WARMUP_WINDOWS = 3  # buckets settle: full pack, first deltas, the join
# (before window 0 one node reports alone with one zone: see Fleet.scout)
CHANGED_FRACTION = 0.047  # nodes whose content changes per window
CHANGED_POD_FRACTION = 0.3  # of a changed node's pods
INTERVAL_S = 1.0
STALE_AFTER_S = 15.0  # the shipped default
DT_S = 5.0

# -- tolerances --------------------------------------------------------------
# Ratio nodes: the repo's own packed-f16 budget (benchmarks/accuracy.py),
# 0.5 % of ground truth over entries above 1 mW — imported in check_window.
FLOOR_W = 1e-3
#
# Model nodes: the repo states no budget that applies. On a TPU the packed
# program runs every estimator matmul with bf16 operands and f32
# accumulators (models/nn.acc_matmul; the f32 "skip" matmul runs at
# DEFAULT precision, one bf16 pass on the MXU as well), so the tolerance is
# a first-order forward bound on bf16 OPERAND rounding, evaluated per entry
# next to the f64 reference:
#
#   bf16 keeps 8 significant bits: unit roundoff u = 2^-8. A product of two
#   rounded operands is a·b·(1+δ), |δ| ≤ 2u + u² =: γ; accumulation is f32
#   (error ≤ K·2^-24, three orders below γ for K ≤ 128). So each matmul
#   z = a @ b perturbs its own output by at most γ·(|a| @ |b|), whatever
#   the signs of the roundings. The network is
#       z0 = x@w0+b0, h0 = gelu(z0), z1 = h0@w1+b1, h1 = gelu(z1),
#       out = h1@w2 + x@w_skip + b2
#   and to first order a perturbation of z1 reaches out_z through
#   w2[:, z]·gelu'(z1), one of z0 through the Jacobian
#   M_z = ((w2[:, z]·gelu'(z1)) @ w1ᵀ)·gelu'(z0). Hence, per entry,
#       |δout_z| ≤ γ·(|h1| @ |w2|)_z + γ·(|x| @ |w_skip|)_z
#                 + (γ·(|h0| @ |w1|)·|gelu'(z1)|) @ |w2[:, z]|
#                 + Σ_j |M_z[j]|·γ·(|x| @ |w0|)_j
#   (the Jacobians are the reference's own, with their cancellations; a
#   product of |w| norms instead would allow a third of the value). Bias
#   adds and the 0 W clamp are 1-Lipschitz and add nothing. The published
#   value is then quantized to f16 watts: relative 2^-11, absolute 2^-25 W
#   below the normal range.
#
# The bound is evaluated in f64 and widened by SLACK for what first order
# leaves out (γ² terms, the shift of gelu' itself, f32 transcendentals).
# Off-TPU the engine serves f32 compute (parallel/packed.py), far inside
# the same bound.
BF16_U = 2.0 ** -8
BF16_GAMMA = 2 * BF16_U + BF16_U ** 2
F16_REL = 2.0 ** -11
F16_ABS = 2.0 ** -25
SLACK = 1.5


class SmokeFailure(Exception):
    """One-line reason the smoke fails with."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# seeded inputs
# ---------------------------------------------------------------------------


def make_params(seed: int, n_zones: int, hidden: int = 128,
                n_features: int = 7) -> dict:
    """A seeded MLP in ``models.estimator.save_params``' flat layout.

    Untrained ``init_mlp`` has a zero output layer, which would make the
    model rows vacuous. Here the f32 skip path carries a positive,
    watt-scaled linear signal and the GELU trunk a correction of about a
    tenth of it, so a model row is a few watts, every layer matters, and
    the bf16 bound above stays a few percent of the value."""
    rng = np.random.default_rng(seed)
    f32 = np.float32

    def glorot(shape):
        return (rng.standard_normal(shape)
                * np.sqrt(2.0 / sum(shape))).astype(f32)

    w_skip = np.zeros((n_features, n_zones), f32)
    # features: 0 cpu delta, 1 share, 2 usage ratio, 3 dt, 4 rate, 5 bias,
    # 6 log1p(node cpu) — watts mostly from cpu time and rate
    w_skip[0] = rng.uniform(0.4, 1.2, n_zones)
    w_skip[4] = rng.uniform(1.0, 3.0, n_zones)
    w_skip[5] = rng.uniform(0.2, 0.6, n_zones)
    return {
        "w0": glorot((n_features, hidden)),
        "b0": (0.05 * rng.standard_normal(hidden)).astype(f32),
        "w1": glorot((hidden, hidden)),
        "b1": (0.05 * rng.standard_normal(hidden)).astype(f32),
        "w2": (0.05 * rng.standard_normal((hidden, n_zones))).astype(f32),
        "b2": rng.uniform(0.05, 0.2, n_zones).astype(f32),
        "w_skip": w_skip,
    }


class Fleet:
    """The seeded fleet and its window-to-window changes.

    Every node has a BASE state, sent once as a wire-v2 keyframe. In
    window k the next few percent of nodes send a delta against their
    keyframe (some pods' cpu, the zone energies and the node scalars
    change) and revert in window k+1, which the wire expresses as a
    FLAG_SAME delta whose content identity is the keyframe's again — so
    every window stages the newly changed rows AND the reverted ones, and
    everybody else stages nothing. Node ``leaver`` stops reporting after
    window 1; node ``joiner`` first reports in window 2.
    """

    def __init__(self, n_nodes: int, pods: tuple[int, int], model: bool,
                 seed: int = SEED) -> None:
        from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO

        self.n = n_nodes
        self.rng = np.random.default_rng(seed)
        rng = self.rng
        self.names = [f"node-{i:04d}" for i in range(n_nodes)]
        self.run = [f"smoke-{seed}-{i}" for i in range(n_nodes)]
        self.n_pods = rng.integers(pods[0], pods[1] + 1, n_nodes)
        self.w = int(self.n_pods.max())
        self.mode = np.where(model & (np.arange(n_nodes) % 2 == 1),
                             MODE_MODEL, MODE_RATIO).astype(np.int32)
        self.valid = np.arange(self.w)[None, :] < self.n_pods[:, None]
        self.base_cpu = np.where(
            self.valid, rng.uniform(0.01, 5.0, (n_nodes, self.w)),
            0.0).astype(np.float32)
        self.base_zone = rng.uniform(
            1e7, 5e8, (n_nodes, len(ZONES))).astype(np.float32)
        self.zone_valid = rng.random((n_nodes, len(ZONES))) > 0.02
        self.base_ratio = rng.uniform(0.2, 0.9, n_nodes).astype(np.float32)
        self.ids = [[f"n{i}-p{j}" for j in range(self.n_pods[i])]
                    for i in range(n_nodes)]
        self.leaver = min(7, n_nodes - 2)
        self.joiner = n_nodes - 1
        self.n_changed = max(1, round(CHANGED_FRACTION * n_nodes))
        self.keyframes: dict[int, bytes] = {}
        self.seq = [0] * n_nodes
        self._leaver_last: tuple | None = None  # what it last reported
        self._base_model_ref: tuple | None = None
        # current (this window's) state
        self.cpu = self.base_cpu.copy()
        self.zone = self.base_zone.copy()
        self.ratio = self.base_ratio.copy()
        self.changed: set[int] = set()

    def reporting(self, k: int) -> list[int]:
        """Nodes that send a report in window ``k``."""
        return [i for i in range(self.n)
                if not (i == self.joiner and k < 2)
                and not (i == self.leaver and k >= 2)]

    def advance(self, k: int) -> None:
        """Set the current state for window ``k`` (k ≥ 1 changes rows)."""
        rng = self.rng
        self.cpu = self.base_cpu.copy()
        self.zone = self.base_zone.copy()
        self.ratio = self.base_ratio.copy()
        self.changed = set()
        if k >= 2 and self._leaver_last is not None:
            # the aggregator keeps serving a silent node's last report
            # until it goes stale
            i = self.leaver
            self.cpu[i], self.zone[i], self.ratio[i] = self._leaver_last
        if k == 0:
            return
        # window k changes the k-th run of consecutive nodes: disjoint from
        # window k-1's, and — the sharded engine deals sorted names round
        # robin — the same count on every shard, so the per-shard delta
        # buckets settle in warm-up on any number of chips
        pool = [i for i in range(self.n)
                if i not in (self.leaver, self.joiner)]
        first = (k - 1) * self.n_changed
        self.changed = {pool[(first + j) % len(pool)]
                        for j in range(self.n_changed)}
        for i in sorted(self.changed):
            n_p = int(self.n_pods[i])
            m = max(1, round(CHANGED_POD_FRACTION * n_p))
            cols = rng.choice(n_p, m, replace=False)
            self.cpu[i, cols] = rng.uniform(0.01, 5.0, m).astype(np.float32)
            self.zone[i] = rng.uniform(1e7, 5e8, len(ZONES)).astype(
                np.float32)
            self.ratio[i] = np.float32(rng.uniform(0.2, 0.9))

    def model_reference(self, params: dict, live: Sequence[int]):
        """``model_reference`` of the CURRENT state of the model nodes in
        ``live``. Most nodes sit at their base state in any one window, so
        that is computed once and only the rows that differ are redone."""
        if self._base_model_ref is None:
            cpu_sum = np.where(self.valid, self.base_cpu, 0.0).sum(
                axis=1, dtype=np.float32)
            self._base_model_ref = model_reference(
                params, self.base_cpu, self.valid, cpu_sum, self.base_ratio,
                DT_S)
        idx = np.asarray(live, np.intp)
        want = self._base_model_ref[0][idx].copy()
        bound = self._base_model_ref[1][idx].copy()
        redo = [r for r, i in enumerate(live)
                if i in self.changed or i == self.leaver]
        if redo:
            sub = idx[redo]
            cpu_sum = np.asarray([self.node_cpu(i) for i in sub], np.float32)
            want[redo], bound[redo] = model_reference(
                params, self.cpu[sub], self.valid[sub], cpu_sum,
                self.ratio[sub], DT_S)
        return want, bound

    def node_cpu(self, i: int) -> float:
        """Σ pod cpu as the f32 the agent would report (so ratio nodes
        conserve exactly in the inputs)."""
        return float(self.cpu[i, :self.n_pods[i]].sum(dtype=np.float32))

    def payload(self, i: int, now: float) -> tuple[bytes, bool]:
        """→ (wire-v2 bytes for node i's current report, is_keyframe)."""
        from kepler_tpu.fleet.wire import encode_delta_v2, encode_report_v2
        from kepler_tpu.parallel.fleet import NodeReport

        self.seq[i] += 1
        n_p = int(self.n_pods[i])
        if i == self.leaver:
            self._leaver_last = (self.cpu[i].copy(), self.zone[i].copy(),
                                 self.ratio[i].copy())
        report = NodeReport(
            node_name=self.names[i],
            zone_deltas_uj=self.zone[i],
            zone_valid=self.zone_valid[i],
            usage_ratio=float(self.ratio[i]),
            cpu_deltas=self.cpu[i, :n_p],
            workload_ids=self.ids[i],
            node_cpu_delta=self.node_cpu(i),
            dt_s=DT_S,
            mode=int(self.mode[i]),
        )
        full = encode_report_v2(report, list(ZONES), seq=self.seq[i],
                                run=self.run[i], sent_at=now)
        base = self.keyframes.get(i)
        if base is not None:
            delta = encode_delta_v2(full, base)
            if delta is not None:
                return delta, False
        self.keyframes[i] = full
        return full, True

    def scout(self, now: float) -> bytes:
        """Node 0's report with one zone only, sent alone before window
        0. Its publication tells the smoke when the aggregator ticks, so
        that every later batch lands between two ticks; and because the
        zone axis then changes, window 0 is one full pack of the whole
        fleet, not a thousand joins squeezed through the delta path."""
        from kepler_tpu.fleet.wire import encode_report_v2
        from kepler_tpu.parallel.fleet import NodeReport

        self.seq[0] += 1
        report = NodeReport(
            node_name=self.names[0], zone_deltas_uj=self.base_zone[0, :1],
            zone_valid=self.zone_valid[0, :1],
            usage_ratio=float(self.base_ratio[0]),
            cpu_deltas=self.base_cpu[0, :self.n_pods[0]],
            workload_ids=self.ids[0], node_cpu_delta=self.node_cpu(0),
            dt_s=DT_S, mode=int(self.mode[0]))
        return encode_report_v2(report, list(ZONES[:1]), seq=self.seq[0],
                                run=self.run[0], sent_at=now)

    def keyframe(self, i: int, now: float) -> bytes:
        """Node i's current report again, as a keyframe (the answer to a
        409 needs-keyframe)."""
        self.keyframes.pop(i, None)
        return self.payload(i, now)[0]


# ---------------------------------------------------------------------------
# the NumPy reference
# ---------------------------------------------------------------------------


def _gelu_tanh(x):
    """jax.nn.gelu's default (tanh-approximate) form → (value, slope)."""
    c = np.sqrt(2.0 / np.pi)
    t = np.tanh(c * (x + 0.044715 * x ** 3))
    slope = 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * c * (
        1.0 + 3 * 0.044715 * x ** 2)
    return 0.5 * x * (1.0 + t), slope


def model_reference(params: dict, cpu, valid, node_cpu, ratio, dt):
    """f64 forward of the seeded MLP on ``models.features`` → (watts
    [n, W, Z], bf16 operand-rounding bound [n, W, Z]); see the derivation
    at BF16_GAMMA."""
    f64 = np.float64
    cpu = np.where(valid, cpu, 0.0).astype(f64)
    d = node_cpu.astype(f64)[:, None]
    share = np.where(d > 0.0, cpu / np.maximum(d, 1e-30), 0.0)
    feats = np.stack([
        cpu, share, np.broadcast_to(ratio.astype(f64)[:, None], cpu.shape),
        np.full_like(cpu, dt), cpu / dt, np.ones_like(cpu),
        np.broadcast_to(np.log1p(np.maximum(d, 0.0)), cpu.shape),
    ], axis=-1)
    x = feats[valid]  # [R, F]: the valid workload rows only
    p = {k: np.asarray(v, f64) for k, v in params.items()}
    g = BF16_GAMMA
    h0, s0 = _gelu_tanh(x @ p["w0"] + p["b0"])
    h1, s1 = _gelu_tanh(h0 @ p["w1"] + p["b1"])
    rows = np.maximum(h1 @ p["w2"] + x @ p["w_skip"] + p["b2"], 0.0)
    a0 = g * (np.abs(x) @ np.abs(p["w0"]))  # bounds on δz0, δz1 [R, H]
    a1 = g * (np.abs(h0) @ np.abs(p["w1"]))
    rows_bound = (g * (np.abs(h1) @ np.abs(p["w2"]))
                  + g * (np.abs(x) @ np.abs(p["w_skip"]))
                  + (a1 * np.abs(s1)) @ np.abs(p["w2"]))
    for z in range(rows.shape[1]):
        jac = ((p["w2"][:, z] * s1) @ p["w1"].T) * s0  # ∂out_z/∂z0 [R, H]
        rows_bound[:, z] += (np.abs(jac) * a0).sum(axis=1)
    watts = np.zeros(valid.shape + (rows.shape[1],))
    bound = np.zeros_like(watts)
    watts[valid] = rows
    bound[valid] = rows_bound
    return watts, bound


class WindowCheck:
    """Errors observed in one published window (all relative, over entries
    above FLOOR_W; ``model_bound_use`` is |error| / tolerance)."""

    def __init__(self) -> None:
        self.ratio_workload = 0.0
        self.ratio_node = 0.0
        self.conservation = 0.0
        self.model_workload = 0.0
        self.model_node = 0.0
        self.model_bound_use = 0.0
        self.model_bound_rel = 0.0

    def merge(self, other: "WindowCheck") -> None:
        for k, v in vars(other).items():
            setattr(self, k, max(getattr(self, k), v))


def check_window(fleet: Fleet, live: Sequence[int], nodes: dict,
                 params: dict | None) -> WindowCheck:
    """Compare one ``/v1/results`` payload with the reference of the
    fleet's CURRENT state. Raises SmokeFailure on the first violation."""
    from benchmarks.accuracy import (RATIO_TOL, max_rel_err,
                                     reference_attribution_f64)
    from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO

    idx = np.asarray(live)
    n, w, z = len(idx), fleet.w, len(ZONES)
    pub = np.zeros((n, w, z))
    pub_node = np.zeros((n, z))
    for r, i in enumerate(live):
        entry = nodes[fleet.names[i]]
        if entry["zones"] != list(ZONES):
            raise SmokeFailure(f"{fleet.names[i]}: zones {entry['zones']}")
        if entry["mode"] != int(fleet.mode[i]):
            raise SmokeFailure(f"{fleet.names[i]}: mode {entry['mode']}")
        wls = entry["workloads"]
        if [x["id"] for x in wls] != fleet.ids[i]:
            raise SmokeFailure(f"{fleet.names[i]}: workload ids differ")
        if wls:
            pub[r, :len(wls)] = [x["power_uw"] for x in wls]
        pub_node[r] = entry["node_power_uw"]
    pub *= 1e-6  # µW → W
    pub_node *= 1e-6
    if not (np.isfinite(pub).all() and np.isfinite(pub_node).all()):
        raise SmokeFailure("non-finite watts published")

    cpu, valid = fleet.cpu[idx], fleet.valid[idx]
    node_cpu = np.asarray([fleet.node_cpu(i) for i in live], np.float32)
    ratio, mode = fleet.ratio[idx], fleet.mode[idx]
    ref = reference_attribution_f64(
        zone_deltas_uj=fleet.zone[idx], zone_valid=fleet.zone_valid[idx],
        usage_ratio=ratio, cpu_deltas=cpu, workload_valid=valid,
        node_cpu_delta=node_cpu, dt_s=np.full(n, DT_S, np.float32))
    out = WindowCheck()
    rn = mode == MODE_RATIO
    if rn.any():
        out.ratio_workload = max_rel_err(
            pub[rn], ref.workload_power_uw[rn] * 1e-6, floor=FLOOR_W)
        out.ratio_node = max_rel_err(
            pub_node[rn], ref.node_power_uw[rn] * 1e-6, floor=FLOOR_W)
        out.conservation = max_rel_err(
            pub[rn].sum(axis=1), ref.node_active_power_uw[rn] * 1e-6,
            floor=FLOOR_W)
        worst = max(out.ratio_workload, out.ratio_node, out.conservation)
        if worst > RATIO_TOL:
            raise SmokeFailure(
                f"ratio nodes off the f64 reference by {worst:.3g} "
                f"(workload {out.ratio_workload:.3g}, node "
                f"{out.ratio_node:.3g}, conservation "
                f"{out.conservation:.3g}; budget {RATIO_TOL})")
    mn = mode == MODE_MODEL
    if mn.any():
        if params is None:
            raise SmokeFailure("model-mode nodes in a ratio-only leg")
        want, bound = fleet.model_reference(
            params, [i for i in live if fleet.mode[i] == MODE_MODEL])
        tol = SLACK * bound + F16_REL * want + F16_ABS
        err = np.abs(pub[mn] - want)
        # a model node's power is the sum of its workloads' watts
        want_node = want.sum(axis=1)
        tol_node = tol.sum(axis=1) + F16_REL * want_node + F16_ABS
        err_node = np.abs(pub_node[mn] - want_node)
        out.model_workload = max_rel_err(pub[mn], want, floor=FLOOR_W)
        out.model_node = max_rel_err(pub_node[mn], want_node, floor=FLOOR_W)
        out.model_bound_use = float(max((err / tol).max(),
                                        (err_node / tol_node).max()))
        big = want > FLOOR_W
        out.model_bound_rel = float((tol[big] / want[big]).max())
        if out.model_bound_use > 1.0:
            raise SmokeFailure(
                f"model nodes off the f64 forward by {out.model_bound_use:.3g}"
                f"x the bf16 operand-rounding bound (max relative error "
                f"{out.model_workload:.3g} per workload, "
                f"{out.model_node:.3g} per node)")
    return out


# ---------------------------------------------------------------------------
# the aggregator child
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class AggregatorChild:
    """``python -m kepler_tpu.cmd.aggregator`` as a child, with its output
    in a log file and a guaranteed stop."""

    def __init__(self, config: dict, workdir: str, name: str,
                 env: dict | None = None) -> None:
        self.port = int(config["aggregator"]["listenAddress"].rsplit(":", 1)[1])
        self.log_path = os.path.join(workdir, f"{name}.log")
        cfg_path = os.path.join(workdir, f"{name}.yaml")
        with open(cfg_path, "w", encoding="utf-8") as f:
            json.dump(config, f, indent=1)  # JSON is YAML
        with open(self.log_path, "w", encoding="utf-8") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "kepler_tpu.cmd.aggregator",
                 "--config.file", cfg_path],
                cwd=REPO, env=env if env is not None else dict(os.environ),
                stdout=log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()

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
            raise SmokeFailure(f"GET {path} → {status}")
        return json.loads(body)

    def wait_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                tail = " | ".join(self.log_text().strip().splitlines()[-3:])
                raise SmokeFailure(
                    f"aggregator exited {rc} at start: {tail[-400:]}")
            try:
                status, _ = self.request("GET", "/readyz", timeout=2.0)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeFailure(f"aggregator not ready after {timeout:.0f}s")

    def stop(self) -> int | None:
        """SIGTERM and wait → the exit code (None: it had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)


def post_window(child: AggregatorChild, fleet: Fleet,
                senders: Sequence[int]) -> tuple[float, float, int]:
    """POST every sender's report (batched, as an agent's drain does) →
    (wall time before, after, keyframes sent). A 409 needs-keyframe is
    answered with the keyframe, as an agent would."""
    from kepler_tpu.fleet.wire import encode_report_batch

    t_start = time.time()
    keyframes = 0
    pending = list(senders)
    resend: list[int] = []
    payloads = {}
    for i in pending:
        payloads[i], is_kf = fleet.payload(i, t_start)
        keyframes += is_kf
    for attempt in range(3):
        for lo in range(0, len(pending), 256):
            chunk = pending[lo:lo + 256]
            status, body = child.request(
                "POST", "/v1/reports",
                encode_report_batch([payloads[i] for i in chunk]))
            if status != 200:
                raise SmokeFailure(f"POST /v1/reports → {status} "
                                   f"{body[:120]!r}")
            for i, row in zip(chunk, json.loads(body)["results"]):
                if row["status"] == 409 and row.get("needs_keyframe"):
                    resend.append(i)
                elif row["status"] != 204:
                    raise SmokeFailure(
                        f"report for {fleet.names[i]} → {row}")
        if not resend:
            return t_start, time.time(), keyframes
        pending, resend = resend, []
        keyframes += len(pending)
        payloads = {i: fleet.keyframe(i, time.time()) for i in pending}
    raise SmokeFailure("aggregator kept asking for keyframes")


def run_leg(name: str, *, workdir: str, backend: str = "einsum",
            model: bool = True, fused_k: int = 1,
            expect_platform: str = "tpu", n_nodes: int = N_NODES,
            pods: tuple[int, int] = PODS, windows: int = WINDOWS,
            interval: float = INTERVAL_S, stale_after: float = STALE_AFTER_S,
            node_bucket: int = N_NODES, workload_bucket: int = WORKLOAD_BUCKET,
            extra_config: dict | None = None,
            env: dict | None = None, start_timeout: float = 240.0) -> dict:
    """One leg: start the aggregator, drive ``windows`` windows, check
    every one, stop it. → a summary dict. Raises SmokeFailure."""
    t_leg = time.monotonic()
    params = make_params(SEED, len(ZONES)) if model else None
    params_path = ""
    if params is not None:
        params_path = os.path.join(workdir, f"{name}-mlp.npz")
        np.savez(params_path, **params)
    config: dict = {
        "log": {"level": "info"},
        "tpu": {"platform": expect_platform, "fleetBackend": backend,
                "nodeBucket": node_bucket,
                "workloadBucket": workload_bucket},
        "aggregator": {
            "listenAddress": f"127.0.0.1:{_free_port()}",
            "interval": interval, "staleAfter": stale_after,
            "model": "mlp" if model else "", "paramsPath": params_path,
            "fallbackEnabled": False, "fusedWindowK": fused_k,
        },
    }
    for section, values in (extra_config or {}).items():
        config.setdefault(section, {}).update(values)
    fleet = Fleet(n_nodes, pods, model)
    fused = fused_k > 1
    # every ring buffer of the pipelined engines (depth 2, three slots)
    # must have served the window's content before the next one arrives,
    # or a buffer would stage the union of two windows' changes: a
    # different delta bucket and a compile after warm-up that no defect
    # caused. A tick dispatches before it publishes the tick before, so
    # two fresh publications mean three dispatches.
    fresh_needed = 1 if fused else 2
    child = AggregatorChild(config, workdir, name, env)
    if params is not None:
        # the f64 forward of the whole fleet takes seconds: do it while
        # the aggregator starts, not between two windows (every node must
        # report again before it goes stale)
        fleet.advance(0)
        fleet.model_reference(params, [])
    total = WindowCheck()
    compiles: list[int] = []
    h2d_seen: list[int] = []
    device: dict = {}
    try:
        child.wait_ready(start_timeout)
        probe = f"/v1/results?node={fleet.names[0]}"
        status, body = child.request("POST", "/v1/report",
                                     fleet.scout(time.time()))
        if status != 204:
            raise SmokeFailure(f"POST /v1/report → {status} {body[:120]!r}")
        _await_stamp(child, probe, 0.0, 1, interval)
        leave_done = 0.0
        for k in range(windows):
            fleet.advance(k)
            senders = fleet.reporting(k)
            if k == windows - 1 and leave_done:
                # the last window is judged after the leaver went stale
                wait = leave_done + stale_after + 2 * interval - time.time()
                if wait > 0:
                    time.sleep(wait)
            # tick-aligned, as agents on a shared interval are: post right
            # after a window published, so the whole batch lands between
            # two ticks and no window sees half of it
            _await_stamp(child, probe, _stamp(child, probe), 1, interval)
            t_start, t_done, keyframes = post_window(child, fleet, senders)
            if k == 1:
                leave_done = t_done
            stamp = _await_stamp(child, probe, t_done, fresh_needed,
                                 interval, h2d_seen=h2d_seen)
            results = child.get_json("/v1/results")
            live = _published_nodes(
                fleet, k, senders, set(results["nodes"]),
                must_be_gone=(k == windows - 1
                              or stamp - leave_done > stale_after + interval))
            t_check = time.monotonic()
            check = check_window(fleet, live, results["nodes"], params)
            t_check = time.monotonic() - t_check
            total.merge(check)
            dbg = child.get_json("/debug/window")
            device = _check_debug(dbg, expect_platform, fused, k)
            compiles.append(int(dbg["stats"]["window_compiles_total"]))
            say(f"  [{name}] window {k}: {len(live)} nodes "
                f"{sum(int(fleet.n_pods[i]) for i in live)} pods, "
                f"{len(fleet.changed)} changed, {keyframes} keyframes, "
                f"posted in {t_done - t_start:.2f}s, checked in "
                f"{t_check:.1f}s, compiles {compiles[-1]}")
        warm = min(WARMUP_WINDOWS, windows - 1)
        if compiles[-1] != compiles[warm - 1]:
            raise SmokeFailure(
                f"window_compiles_total kept growing after warm-up: "
                f"{compiles} (warm-up = first {warm} windows)")
        _check_metrics(child, compiles[-1])
        say(f"  [{name}] compiled: {_compiled_keys(dbg)}")
        if device["devices"] > 1 and not fused:
            _check_shards(dbg, device["devices"], h2d_seen)
        rc = child.stop()
        log = child.log_text()
        for needle in ("Traceback", "fleet aggregation failed",
                       "donated buffers were not usable"):
            if needle in log:
                line = next(x for x in log.splitlines() if needle in x)
                raise SmokeFailure(f"aggregator log has {needle!r}: "
                                   f"{line[:200]}")
        if rc != 0 or "Graceful shutdown completed" not in log:
            raise SmokeFailure(f"aggregator did not shut down cleanly "
                               f"(exit {rc})")
    except (OSError, http.client.HTTPException, ValueError, KeyError) as err:
        tail = " | ".join(child.log_text().strip().splitlines()[-2:])
        raise SmokeFailure(f"{type(err).__name__}: {err}; aggregator log "
                           f"tail: {tail[-300:]}") from err
    finally:
        child.kill()
    # the aggregator's own clock around the legs of the last window it
    # published: ONE observation for orientation, not a benchmark
    legs_ms = {key[len("last_"):-len("_ms")]: round(dbg["stats"][key], 2)
               for key in ("last_assembly_ms", "last_dispatch_ms",
                           "last_wait_ms", "last_fetch_ms",
                           "last_scatter_ms")}
    summary = {"leg": name, "backend": backend, "model": model,
               "fused_k": fused_k, **device, "windows": windows,
               "compiles": compiles[-1],
               "seconds": round(time.monotonic() - t_leg, 1),
               **{k: float(f"{v:.3g}") for k, v in vars(total).items()},
               "last_window_ms": legs_ms,
               "last_window_h2d_rows": dbg["stats"]["last_h2d_rows"]}
    say(f"leg {name}: ok — {json.dumps(summary)}")
    return summary


def _published_nodes(fleet: Fleet, k: int, senders: Sequence[int],
                     got: set[str], must_be_gone: bool) -> list[int]:
    """The nodes window ``k`` must publish: every node that reported in it
    — plus the node that left, which lingers with its last report until
    it is stale and after that must be gone. Raises on any other set."""
    live = list(senders)
    leaver = fleet.names[fleet.leaver]
    if k >= 2 and leaver in got:
        if must_be_gone:
            raise SmokeFailure(f"window {k}: {leaver} is still published "
                               "after it went stale")
        live.append(fleet.leaver)
    if got != {fleet.names[i] for i in live}:
        raise SmokeFailure(f"window {k}: published {len(got)} nodes, "
                           f"expected {len(live)}")
    return live


def _stamp(child: AggregatorChild, probe: str) -> float:
    """The probe node's published window timestamp (0.0 before any)."""
    status, body = child.request("GET", probe)
    return float(json.loads(body)["timestamp"]) if status == 200 else 0.0


def _await_stamp(child: AggregatorChild, probe: str, after: float,
                 count: int, interval: float,
                 h2d_seen: list[int] | None = None) -> float:
    """Poll one node's published window until ``count`` distinct window
    timestamps later than ``after`` were seen → the newest. The window
    timestamp is the aggregator's clock when it snapshotted its reports,
    so a stamp later than the end of a POST covers all of it."""
    seen: set[float] = set()
    deadline = time.monotonic() + 60.0 + 20 * interval * count
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(f"aggregator exited {child.proc.returncode} "
                               "mid-run")
        stamp = _stamp(child, probe)
        if stamp > after and stamp not in seen:
            seen.add(stamp)
            if h2d_seen is not None:
                shards = child.get_json("/debug/window")["stats"][
                    "last_h2d_shards"]
                h2d_seen[:] = [max(a, b) for a, b in zip(
                    h2d_seen + [0] * len(shards), shards)]
            if len(seen) >= count:
                return max(seen)
        time.sleep(min(0.1, interval / 5))
    raise SmokeFailure(f"no window published within the wait "
                       f"({len(seen)}/{count} fresh windows seen)")


def _check_debug(dbg: dict, expect_platform: str, fused: bool,
                 k: int) -> dict:
    """The assertion surface: the window came from rung 0 on the expected
    platform, with nothing demoted."""
    from kepler_tpu.fleet.scheduler import (RUNG_NAME_FUSED,
                                            RUNG_NAME_SHARDED, RUNG_NAMES)

    device = {key: dbg.get(key) for key in
              ("platform", "device_kind", "devices")}
    if device["platform"] != expect_platform:
        raise SmokeFailure(f"/debug/window platform is "
                           f"{device['platform']!r}, not {expect_platform!r}")
    # rung 0 under the name the device count implies
    want = (RUNG_NAME_FUSED if fused else RUNG_NAME_SHARDED
            if device["devices"] > 1 else RUNG_NAMES[0])
    if dbg["rung"] != 0 or dbg["rung_name"] != want:
        raise SmokeFailure(f"window {k}: rung {dbg['rung']} "
                           f"{dbg['rung_name']!r}, expected 0 {want!r}")
    if dbg["demotions_by_reason"] or "last_failure" in dbg:
        raise SmokeFailure(
            f"window {k}: demoted: {dbg['demotions_by_reason']} "
            f"{dbg.get('last_failure', '')}")
    return device


def _compiled_keys(dbg: dict) -> list[str]:
    """Every program the engines compiled, by its cache-key label."""
    keys = []
    for engine in dbg["engines"].values():
        for group in (engine["programs"], engine["updates"],
                      engine.get("fused", {}).get("programs", [])):
            keys += [entry["key"] for entry in group]
    return keys


def _check_metrics(child: AggregatorChild, compiles: int) -> None:
    status, body = child.request("GET", "/metrics")
    if status != 200:
        raise SmokeFailure(f"GET /metrics → {status}")
    from prometheus_client.parser import text_string_to_metric_families

    totals = dict.fromkeys(("kepler_fleet_window_demotions_total",
                            "kepler_fleet_window_degraded",
                            "kepler_fleet_window_compiles_total"), 0.0)
    for family in text_string_to_metric_families(body.decode()):
        for sample in family.samples:
            if sample.name in totals:
                totals[sample.name] += sample.value
    demoted, degraded, seen = totals.values()
    if demoted or degraded:
        raise SmokeFailure(f"/metrics: demotions {demoted}, degraded "
                           f"{degraded}")
    if seen != compiles:
        raise SmokeFailure(f"/metrics compiles {seen} != /debug/window "
                           f"{compiles}")


def _check_shards(dbg: dict, devices: int, h2d_seen: list[int]) -> None:
    """On several chips the default path is the sharded engine: its rows
    and its uploads must be spread over all the shards, not one."""
    engine = dbg["engines"].get("pipelined") or {}
    rows = [s["rows"] for s in engine.get("shards", [])]
    if len(rows) != devices or min(rows, default=0) == 0:
        raise SmokeFailure(f"resident rows per shard {rows} on {devices} "
                           "devices")
    if len(h2d_seen) != devices or min(h2d_seen) == 0:
        raise SmokeFailure(f"H2D rows per shard {h2d_seen} on {devices} "
                           "devices")
    say(f"  shards: rows {rows}, max h2d rows per shard {h2d_seen}")


# ---------------------------------------------------------------------------
# device checks (a child of their own: this is the part that touches JAX)
# ---------------------------------------------------------------------------


def device_checks() -> dict:
    """Runs IN A CHILD (``--device-checks``): the attention kernel under
    Mosaic at the temporal model's shapes, and the donation of the
    resident batch as the window engine really performs it."""
    import warnings

    import jax
    import jax.numpy as jnp

    from kepler_tpu.fleet.window import PackedWindowEngine, RowInput
    from kepler_tpu.models.temporal import N_HEADS
    from kepler_tpu.ops.attention import block_attn
    from kepler_tpu.ops.pallas_attention import flash_block_pallas
    from kepler_tpu.parallel import MODE_MODEL, NodeReport, make_mesh

    dev = jax.devices()[0]
    out: dict = {"platform": dev.platform, "device_kind": dev.device_kind,
                 "devices": len(jax.devices())}
    # T=16 ticks of history, d_model 128 over N_HEADS heads
    b, t, h, d = 256, 16, N_HEADS, 128 // N_HEADS
    q, k, v = (jax.random.normal(key, (b, t, h, d), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(SEED), 3))
    tv = jnp.arange(t)[None, :] < (1 + jnp.arange(b) % t)[:, None]
    mask = (jnp.broadcast_to(tv[:, None, None, :], (b, 1, t, t))
            & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]))
    want = block_attn(q, k, v, mask, 1.0 / d ** 0.5, jnp.bfloat16)
    got = flash_block_pallas(q, k, v, tv, 0, 0, causal=True,
                             compute_dtype=jnp.bfloat16)
    out["flash_max_abs_err"] = max(
        float(jnp.max(jnp.abs(a - w))) for a, w in zip(got, want))

    mesh = make_mesh(devices=jax.devices()[:1])
    engine = PackedWindowEngine(mesh, model_mode="mlp", node_bucket=64,
                                workload_bucket=16, staging_slots=3)
    zones = ("package", "dram")
    params = {k2: jnp.asarray(v2) for k2, v2 in make_params(SEED, 2).items()}

    def rows_at(seq: int) -> list:
        rng = np.random.default_rng(seq)
        rows = []
        for i in range(40):
            cpu = rng.uniform(0.1, 5.0, 8).astype(np.float32)
            rep = NodeReport(
                node_name=f"node-{i:02d}",
                zone_deltas_uj=rng.uniform(1e7, 1e8, 2).astype(np.float32),
                zone_valid=np.ones(2, bool), usage_ratio=0.6,
                cpu_deltas=cpu,
                workload_ids=[f"n{i}-w{j}" for j in range(8)],
                node_cpu_delta=float(cpu.sum()), dt_s=DT_S,
                mode=MODE_MODEL if i % 2 else 0)
            rows.append(RowInput(name=rep.node_name, report=rep,
                                 zone_names=zones, ident=("smoke", seq)))
        return rows

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        plan = engine.plan_window(rows_at(1), zones, params)
        np.asarray(plan.program(*plan.args))
        donated = engine._buffers[(engine._buf_i + 1) % len(engine._buffers)]
        plan = engine.plan_window(rows_at(2), zones, params)
        np.asarray(plan.program(*plan.args))
    out["donated_deleted"] = bool(donated.is_deleted())
    out["donation_warnings"] = [str(w.message)[:160] for w in caught
                                if "donated" in str(w.message)]
    return out


def run_device_checks(workdir: str, expect_platform: str,
                      env: dict | None = None) -> dict:
    """Start the device-checks child, judge what it reports."""
    log_path = os.path.join(workdir, "k.log")
    with open(log_path, "w", encoding="utf-8") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--device-checks"],
            cwd=REPO, env=env if env is not None else dict(os.environ),
            stdout=subprocess.PIPE, stderr=log, text=True)
        try:
            stdout, _ = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise SmokeFailure("device checks hung past 300s")
    with open(log_path, encoding="utf-8", errors="replace") as f:
        err_tail = " | ".join(f.read().strip().splitlines()[-2:])[-300:]
    if proc.returncode != 0:
        raise SmokeFailure(f"device checks exited {proc.returncode}: "
                           f"{err_tail}")
    out = json.loads(stdout.strip().splitlines()[-1])
    if out["platform"] != expect_platform:
        raise SmokeFailure(f"device checks ran on {out['platform']!r}")
    if not out["flash_max_abs_err"] <= 1e-2:
        raise SmokeFailure("flash_block_pallas differs from block_attn by "
                           f"{out['flash_max_abs_err']:.3g}")
    if not out["donated_deleted"] or out["donation_warnings"]:
        raise SmokeFailure(f"the resident batch's donation is not realized: "
                           f"{out}")
    say(f"leg k: ok — {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _cache_entries(path: str) -> set[str]:
    try:
        return {f for f in os.listdir(path) if f.endswith("-cache")}
    except OSError:
        return set()


def _versions() -> str:
    from importlib.metadata import PackageNotFoundError, version

    out = []
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out.append(f"{pkg} {version(pkg)}")
        except PackageNotFoundError:
            out.append(f"{pkg} absent")
    return ", ".join(out)


def smoke(workdir: str) -> dict:
    from kepler_tpu.utils.jaxenv import compile_cache_dir

    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and "tpu" not in pinned.split(","):
        raise SmokeFailure(
            f"JAX_PLATFORMS={pinned} keeps JAX off the TPU: this smoke "
            "runs on the chip only and never falls back")
    cache = compile_cache_dir()
    say(f"chip_smoke: {_versions()}; compile cache {cache}")
    a = run_leg("a", workdir=workdir)
    say(f"device: platform={a['platform']} device_kind={a['device_kind']} "
        f"count={a['devices']}")
    after_a = _cache_entries(cache)
    if not after_a:
        raise SmokeFailure(f"leg a left no entry in the compile cache "
                           f"{cache}")
    run_leg("b", workdir=workdir, backend="pallas")
    run_leg("c", workdir=workdir, backend="pallas", model=False, fused_k=4)
    before_d = _cache_entries(cache)
    run_leg("d", workdir=workdir)
    added = _cache_entries(cache) - before_d
    if added:
        raise SmokeFailure(f"the second start of leg a added {len(added)} "
                           f"compile-cache entries: {sorted(added)[:3]}")
    say(f"leg d: no new compile-cache entry ({len(before_d)} in {cache})")
    run_device_checks(workdir, "tpu")
    return {"platform": a["platform"], "kind": a["device_kind"],
            "count": a["devices"]}


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--device-checks", action="store_true",
                    help="internal: run the in-process device checks (the "
                         "smoke starts this as a child)")
    args = ap.parse_args(argv)
    if args.device_checks:
        print(json.dumps(device_checks()))
        return 0
    t0 = time.monotonic()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
            device = smoke(workdir)
    except SmokeFailure as err:
        print(f"chip_smoke: FAIL: {err}", file=sys.stderr)
        return 1
    except ImportError as err:
        print(f"chip_smoke: FAIL: run it from a checkout of the repo "
              f"({err})", file=sys.stderr)
        return 1
    say(f"chip_smoke: all legs ok in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
