"""The seeded fleet: who reports what in which round.

One general generator for every traffic mix. A configuration file gives the
fleet's shape (nodes, pods per node, zones, share of model nodes, ``dt``),
a traffic file gives how the reports move from round to round and the loop;
the seed gives everything else. Every seed has the same SIZES: pods per
node are a permutation of one fixed multiset, so each seed posts the same
number of pods in another order.

A round is one report from every node, as Kepler's agent sends one every
``monitor.interval``: the CPU time each pod used since the last report and
the energy each RAPL zone counted. Neither is ever bitwise what it was a
tick before, so every report of every round differs from the node's last
one in every pod's cpu, every zone's energy and the usage ratio, and goes
over the wire as a real delta (never the wire's "nothing changed" frame):

- a pod's cpu is its own level (drawn once, when the pod appears) times a
  log-normal factor drawn anew each round (``cpu_sigma``); a node's zone
  energies and usage ratio move the same way around the node's levels
  (``zone_sigma``, ``ratio_sigma``);
- pods come and go: in round ``r`` a run of ``churn_node_share`` of the
  nodes (the ``r``-th run, so every node has its turn) replace
  ``churn_pod_share`` of their pods by new ones. A new pod has a new id (so
  the node's report is a keyframe, and the old id's history ages out of
  the aggregator) and a level of its own, and its history starts with that
  round.

Round ``r``'s content is a pure function of ``(seed, r)``, the same whoever
asks and in any order. The plain reference (``reference.py``) reads the
same states; nothing here imports the model.

Copied from ``chip_smoke.py``'s ``Fleet`` (PR 21) and cut to what a
benchmark needs: no leaver, no joiner, no scout. The wire encoding is the
agent's own (``kepler_tpu.fleet.wire``): it is the client half of the
protocol under test, and what it encodes is checked against the reference
after it went through the aggregator.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

MODE_RATIO = 0
MODE_MODEL = 1
BATCH = 256  # reports per POST /v1/reports, as an agent's drain sends them
F32 = np.float32


@dataclass
class RoundState:
    """What every node reports in one round."""

    cpu: np.ndarray  # f32 [n, w] seconds of CPU per pod (0 where no pod)
    zone: np.ndarray  # f32 [n, z] energy per zone, uJ
    ratio: np.ndarray  # f32 [n] node usage ratio
    gen: np.ndarray  # i32 [n, w] how often the slot's pod has been replaced
    born: np.ndarray  # i32 [n, w] the round of the slot's pod's first report


class Fleet:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.seed = int(seed)
        self.config = config  # the reference and the check read it too
        self.n = int(config["nodes"])
        self.zones = tuple(sorted(config["zones"]))
        self.dt = float(config["dt_s"])
        lo, hi = config["pods_per_node"]
        rng = np.random.default_rng([self.seed, 0])
        # the same multiset of sizes for every seed, in the seed's order
        sizes = lo + (np.arange(self.n) * (hi - lo + 1) // self.n)
        sizes[-1] = hi
        self.w = int(hi)
        every = int(round(1.0 / float(config["model_node_share"])))
        self.mode = np.where(np.arange(self.n) % every == every - 1,
                             MODE_MODEL, MODE_RATIO).astype(np.int32)
        # model nodes and ratio nodes each get the same sizes for every
        # seed too (every ``every``-th of the sorted sizes goes to a model
        # node), so the estimator's work does not move with the seed
        self.n_pods = np.zeros(self.n, np.int64)
        takes_model = np.arange(self.n) % every == every - 1
        for is_model in (True, False):
            where = np.flatnonzero((self.mode == MODE_MODEL) == is_model)
            self.n_pods[where] = rng.permutation(
                sizes[takes_model == is_model])
        self.valid = np.arange(self.w)[None, :] < self.n_pods[:, None]
        self.base_cpu = np.where(
            self.valid, rng.uniform(0.01, 5.0, (self.n, self.w)),
            0.0).astype(F32)
        z = len(self.zones)
        self.base_zone = rng.uniform(1e7, 5e8, (self.n, z)).astype(F32)
        self.zone_valid = rng.random((self.n, z)) > 0.02
        self.base_ratio = rng.uniform(0.2, 0.9, self.n).astype(F32)
        self.names = [f"node-{i:05d}" for i in range(self.n)]
        self.runs = [f"bench-{self.seed}-{i}" for i in range(self.n)]
        self.n_churned = round(float(traffic["churn_node_share"]) * self.n)
        self.churn_pod_share = float(traffic["churn_pod_share"])
        self.cpu_sigma = float(traffic["cpu_sigma"])
        self.zone_sigma = float(traffic["zone_sigma"])
        self.ratio_sigma = float(traffic["ratio_sigma"])
        self.total_pods = int(self.n_pods.sum())
        self.model_pods = int(self.n_pods[self.mode == MODE_MODEL].sum())
        # who lives in which slot, folded forward round by round
        self._at = 0
        self._level = self.base_cpu.copy()
        self._gen = np.zeros((self.n, self.w), np.int32)
        self._born = np.zeros((self.n, self.w), np.int32)
        self._ids: dict[int, tuple[bytes, list[str]]] = {}
        self._keyframes: dict[int, bytes] = {}
        self._seq = [0] * self.n

    # -- content ------------------------------------------------------------

    def churned_nodes(self, r: int) -> np.ndarray:
        """The nodes that replace pods in round ``r`` (none in round 0)."""
        if r < 1 or not self.n_churned:
            return np.zeros(0, np.intp)
        first = (r * self.n_churned) % self.n
        return (first + np.arange(self.n_churned)) % self.n

    def _replacements(self, r: int):
        """Round ``r``'s newcomers → [(node, slots, their levels)]."""
        rng = np.random.default_rng([self.seed, 1, int(r)])
        out = []
        for i in self.churned_nodes(r):
            n_p = int(self.n_pods[i])
            m = max(1, round(self.churn_pod_share * n_p))
            cols = rng.choice(n_p, m, replace=False)
            out.append((int(i), cols, rng.uniform(0.01, 5.0, m).astype(F32)))
        return out

    def _fold_to(self, r: int) -> None:
        if r < self._at:
            self._at = 0
            self._level = self.base_cpu.copy()
            self._gen[:] = 0
            self._born[:] = 0
        while self._at < r:
            self._at += 1
            for i, cols, levels in self._replacements(self._at):
                self._level[i, cols] = levels
                self._gen[i, cols] += 1
                self._born[i, cols] = self._at

    def state(self, r: int) -> RoundState:
        """Round ``r``'s content, the same whoever asks and in any order."""
        self._fold_to(int(r))
        rng = np.random.default_rng([self.seed, 2, int(r)])
        z = self.base_zone.shape[1]
        cpu = self._level * np.exp(self.cpu_sigma * rng.standard_normal(
            (self.n, self.w), F32))
        zone = self.base_zone * np.exp(self.zone_sigma * rng.standard_normal(
            (self.n, z), F32))
        ratio = np.clip(self.base_ratio + self.ratio_sigma
                        * rng.standard_normal(self.n, F32), 0.05, 0.95)
        return RoundState(cpu.astype(F32), zone.astype(F32),
                          ratio.astype(F32), self._gen.copy(),
                          self._born.copy())

    def node_cpu(self, state: RoundState) -> np.ndarray:
        """Sum of pod cpu as the f32 an agent reports, [n]."""
        return np.where(self.valid, state.cpu, 0.0).sum(axis=1, dtype=F32)

    def ids(self, i: int, gen: np.ndarray) -> list[str]:
        """The pod ids of node ``i`` where its slots are at ``gen`` [w]."""
        n_p = int(self.n_pods[i])
        key = gen[:n_p].tobytes()
        hit = self._ids.get(i)
        if hit is None or hit[0] != key:
            hit = self._ids[i] = (key, [
                f"n{i}-p{j}" if g == 0 else f"n{i}-p{j}-{g}"
                for j, g in enumerate(gen[:n_p].tolist())])
        return hit[1]

    # -- the wire -----------------------------------------------------------

    def payload(self, i: int, state: RoundState, node_cpu: np.ndarray,
                now: float, keyframe: bool = False) -> bytes:
        """Wire-v2 bytes of node ``i``'s report: a delta against its last
        keyframe where the wire can express one (the same pods), else a
        keyframe."""
        from kepler_tpu.fleet.wire import encode_delta_v2, encode_report_v2
        from kepler_tpu.parallel.fleet import NodeReport

        self._seq[i] += 1
        n_p = int(self.n_pods[i])
        report = NodeReport(
            node_name=self.names[i], zone_deltas_uj=state.zone[i],
            zone_valid=self.zone_valid[i],
            usage_ratio=float(state.ratio[i]),
            cpu_deltas=state.cpu[i, :n_p],
            workload_ids=self.ids(i, state.gen[i]),
            node_cpu_delta=float(node_cpu[i]), dt_s=self.dt,
            mode=int(self.mode[i]))
        full = encode_report_v2(report, list(self.zones), seq=self._seq[i],
                                run=self.runs[i], sent_at=now)
        base = None if keyframe else self._keyframes.get(i)
        if base is not None:
            delta = encode_delta_v2(full, base)
            if delta is not None:
                return delta
        self._keyframes[i] = full
        return full

    def batches(self, state: RoundState, now: float) -> list[tuple[
            list[int], bytes]]:
        """The round as POST bodies of ``BATCH`` reports each →
        [(nodes in the body, body)]."""
        from kepler_tpu.fleet.wire import encode_report_batch

        node_cpu = self.node_cpu(state)
        out = []
        for lo in range(0, self.n, BATCH):
            chunk = list(range(lo, min(lo + BATCH, self.n)))
            out.append((chunk, encode_report_batch(
                [self.payload(i, state, node_cpu, now) for i in chunk])))
        return out

    def keyframe_batch(self, nodes: list[int], state: RoundState,
                       now: float) -> bytes:
        """The answer to 409 needs-keyframe, as an agent gives it."""
        from kepler_tpu.fleet.wire import encode_report_batch

        node_cpu = self.node_cpu(state)
        return encode_report_batch(
            [self.payload(i, state, node_cpu, now, keyframe=True)
             for i in nodes])
