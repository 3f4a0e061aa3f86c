"""The plain reference: what the aggregator must publish, in NumPy.

The fleet's side of it — the feature rows, each pod's history, ratio
attribution (written from ``docs``' description of it) — and a
``Reference`` that asks the configuration's estimator
(``estimators/<name>.py``: ``watts``, ``block_rows``) for the model nodes'
watts. Independent of the program: it imports nothing of ``kepler_tpu`` and
takes no weights, tables or features the program has made. It reads the
same seeded inputs as the generator (``fleetgen.Fleet.state``) and the
estimator's seeded parameters (its ``make_params``).

``quantize`` names a lower precision for the estimator's operands
(``precision.QUANTIZERS``); where it is the estimator's ``CONTROL``, the
ratio path's float32 product is lowered too, to bf16.
"""

from __future__ import annotations

import numpy as np

from chipbench.estimators.temporal import (  # noqa: F401 (kept for tests)
    make_params, temporal_watts)
from chipbench.fleetgen import Fleet
from chipbench.precision import QUANTIZERS
from chipbench.spec import estimator_of

F32 = np.float32
N_FEATURES = 7


def features(cpu, node_cpu, ratio, dt: float) -> np.ndarray:
    """One tick's feature rows: cpu [..., w], node_cpu and ratio [...] →
    f32 [..., w, 7]: cpu seconds, share of the node's, the node's usage
    ratio, dt, cores in use, 1, log1p(node cpu)."""
    cpu = np.asarray(cpu, F32)
    d = np.asarray(node_cpu, F32)[..., None]
    share = np.divide(cpu, d, out=np.zeros_like(cpu), where=d > 0)
    ones = np.ones_like(cpu)
    return np.stack([
        cpu, share, ones * np.asarray(ratio, F32)[..., None],
        ones * F32(dt), cpu / F32(dt), ones,
        ones * np.log1p(np.maximum(d, F32(0.0))),
    ], axis=-1).astype(F32)


class Reference:
    """Expected publications of one fleet, round by round."""

    def __init__(self, fleet: Fleet, params: dict, history: int,
                 quantize: str | None = None) -> None:
        self.fleet, self.params, self.t = fleet, params, int(history)
        self.quantize = quantize
        # the configuration as this reference runs it: ``history`` ticks
        self.config = dict(fleet.config, history_window=self.t)
        self.estimator = estimator_of(self.config)
        # the attribution product is float32 in the program; its control is
        # the step below float32, bf16, whatever the estimator's control is
        self.ratio_quantize = (
            "bf16" if quantize == self.estimator.CONTROL else None)
        self._states: dict[int, tuple] = {}

    def state(self, r: int):
        """(round ``r``'s state, its feature rows [n, w, 7])."""
        hit = self._states.get(r)
        if hit is None:
            st = self.fleet.state(r)
            feats = features(st.cpu, self.fleet.node_cpu(st), st.ratio,
                             self.fleet.dt)
            hit = self._states[r] = (st, feats)
            if len(self._states) > 2 * self.t + 8:
                self._states.pop(next(iter(self._states)))
        return hit

    def history(self, nodes: np.ndarray, r: int) -> tuple[
            np.ndarray, np.ndarray]:
        """(feature history [len(nodes), w, t, 7], its valid ticks
        [len(nodes), w, t]) after round ``r``: the rounds, oldest first, in
        which the pod that holds the slot in round ``r`` reported, at most
        ``t`` of them, at the front."""
        nodes = np.asarray(nodes, np.intp)
        if r < 0:
            raise ValueError("no round yet")
        born = self.state(r)[0].born[nodes]  # [m, w]
        first = np.maximum(born, r - self.t + 1)
        count = (r - first + 1) * self.fleet.valid[nodes]  # ticks held
        m, w = born.shape
        hist = np.zeros((m, w, self.t, N_FEATURES), F32)
        tick = np.arange(self.t)
        for rr in range(max(0, r - self.t + 1), r + 1):
            feats = self.state(rr)[1][nodes]  # [m, w, 7]
            pos = rr - first  # where this round sits in each pod's history
            here = (pos >= 0) & (count > 0)
            hist[here, pos[here]] = feats[here]
        return hist, tick[None, None, :] < count[:, :, None]

    def model_nodes(self, nodes, r: int) -> np.ndarray:
        """Watts [len(nodes), w, z] the model nodes publish after round r
        (0 where a node has no pod), as the estimator's reference gives
        them, in blocks of nodes of as many rows as it says it can hold."""
        nodes = np.asarray(nodes, np.intp)
        w = self.fleet.w
        block = max(1, self.estimator.block_rows(self.config) // w)
        z = len(self.fleet.zones)
        out = np.zeros((len(nodes), w, z), F32)
        for lo in range(0, len(nodes), block):
            part = nodes[lo:lo + block]
            hist, t_valid = self.history(part, r)
            watts = self.estimator.watts(
                self.params, hist.reshape(-1, self.t, N_FEATURES),
                t_valid.reshape(-1, self.t), self.config,
                self.quantize).reshape(len(part), w, z)
            out[lo:lo + block] = np.where(
                self.fleet.valid[part][:, :, None], watts, F32(0.0))
        return out

    def ratio_nodes(self, nodes, r: int):
        """(pod watts [len(nodes), w, z], node watts [len(nodes), z]) of
        ratio nodes after round r: a zone's energy over dt is the node's
        power; its active part, the usage ratio's share, is divided over
        the pods by their share of the node's cpu time."""
        nodes = np.asarray(nodes, np.intp)
        st = self.state(r)[0]
        f64 = np.float64
        energy = np.where(self.fleet.zone_valid[nodes], st.zone[nodes],
                          0.0).astype(f64)
        quant = QUANTIZERS[self.ratio_quantize]
        if quant is not None:  # the control of this path: bf16 operands
            energy = quant(energy.astype(F32)).astype(f64)
        node_w = energy / self.fleet.dt * 1e-6
        active_w = node_w * np.clip(st.ratio[nodes].astype(f64), 0.0,
                                    1.0)[:, None]
        cpu = np.where(self.fleet.valid[nodes], st.cpu[nodes],
                       0.0).astype(f64)
        total = self.fleet.node_cpu(st)[nodes].astype(f64)[:, None]
        share = np.divide(cpu, total, out=np.zeros_like(cpu),
                          where=total > 0)
        if quant is not None:
            share = quant(share.astype(F32)).astype(f64)
            active_w = quant(active_w.astype(F32)).astype(f64)
        return share[:, :, None] * active_w[:, None, :], node_w
