"""The comparison that decides ``correct``.

What is compared is what the timed path published, at the timed sizes:

- from every window first seen inside the measured window, the answers of a
  few nodes drawn from the seed (half of them model nodes). Where a POST
  that carried the node's next report overlapped the window's assembly, the
  client cannot know which of the two reports the window read: either is
  the answer that is due, so the answer is held against the nearer of the
  two references, and counted (``answers_round_in_doubt``). Only where the
  node's pods differ between the two, so that a window may have read one
  round's ids and the other's history, is the answer skipped, and counted;
- after the close, the whole fleet's answers from the window that the
  pipeline published from the state after the last POST. Every node has to
  be there and well-formed, and every ratio node is compared; the model
  nodes' watts all go through the reference unless the configuration says
  how many do (``check.final_model_nodes``: that many, drawn from the
  seed): an estimator whose reference costs hours for a whole fleet is
  compared on a sample, at the timed sizes still.

Each number has a limit of its own, kept in the configuration's file under
``limits`` with the readings it was set from (``PERF.md`` section 2 has the
table). An exact comparison has the limit 0.
"""

from __future__ import annotations

import numpy as np

from chipbench.drive import Drive
from chipbench.reference import Reference

FLOOR_W = 1e-3  # relative errors are taken over entries above 1 mW
TORN_MARGIN_S = 0.005


class Errors:
    """Pools of |published - reference| over the answers compared."""

    def __init__(self) -> None:
        self.model_sq = 0.0
        self.model_ref_sq = 0.0
        self.numbers = {
            "model_pod_rms_rel": 0.0, "model_pod_max_rel": 0.0,
            "model_node_max_rel": 0.0, "ratio_pod_max_rel": 0.0,
            "ratio_node_max_rel": 0.0, "answers_malformed": 0,
            "rounds_uncovered": 0, "final_window_missing": 0,
            "compiles_in_window": 0, "windows_off_rung0": 0,
        }
        self.counts = {"answers_compared": 0, "pods_compared": 0,
                       "answers_round_in_doubt": 0,
                       "answers_skipped_torn": 0,
                       "final_model_nodes_compared": 0}

    def _max(self, key: str, value: float) -> None:
        self.numbers[key] = max(self.numbers[key], float(value))

    def add_model(self, pub, pub_node, want) -> None:
        """pub, want [n, w, z] watts; pub_node [n, z]."""
        err = np.abs(pub.astype(np.float64) - want)
        self.model_sq += float((err ** 2).sum())
        self.model_ref_sq += float((want.astype(np.float64) ** 2).sum())
        self._max("model_pod_max_rel",
                  (err / np.maximum(np.abs(want), 1.0)).max())
        want_node = want.astype(np.float64).sum(axis=1)
        self._max("model_node_max_rel", _max_rel(pub_node, want_node))
        if self.model_ref_sq > 0:
            self.numbers["model_pod_rms_rel"] = float(
                np.sqrt(self.model_sq / self.model_ref_sq))

    def add_ratio(self, pub, pub_node, want, want_node) -> None:
        self._max("ratio_pod_max_rel", _max_rel(pub, want))
        self._max("ratio_node_max_rel", _max_rel(pub_node, want_node))


def _max_rel(got, want) -> float:
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    big = np.abs(want) > FLOOR_W
    if not big.any():
        return float(np.abs(got - want).max(initial=0.0))
    return float((np.abs(got[big] - want[big]) / np.abs(want[big])).max())


def _unpack(fleet, i: int, entry: dict, gen):
    """One node's /v1/results payload → (pod watts [w, z], node watts [z])
    or None where it is not the answer that node is due (``gen`` [w]: who
    lives in the node's slots in the round the answer is held against)."""
    if entry.get("zones") != list(fleet.zones) \
            or entry.get("mode") != int(fleet.mode[i]):
        return None
    pods = entry.get("workloads", [])
    if [p["id"] for p in pods] != fleet.ids(i, gen):
        return None
    pub = np.zeros((fleet.w, len(fleet.zones)))
    if pods:
        pub[:len(pods)] = [p["power_uw"] for p in pods]
    node = np.asarray(entry["node_power_uw"], np.float64)
    if not (np.isfinite(pub).all() and np.isfinite(node).all()):
        return None
    return pub * 1e-6, node * 1e-6


def candidate_rounds(rounds: list, batch_of: int, stamp: float,
                     assembly_s: float) -> list[int]:
    """The rounds whose report a node may have held when the window
    stamped ``stamp`` was assembled: the last whose POST ended before the
    assembly began, and every one whose POST overlapped it (``rounds`` in
    posting order; ``batch_of`` the node's batch in every round)."""
    lo, hi = stamp - TORN_MARGIN_S, stamp + assembly_s + TORN_MARGIN_S
    held: list[int] = []
    doubt: list[int] = []
    for rnd in rounds:
        _nodes, start, end = rnd.batches[batch_of]
        if end < lo:
            held = [rnd.r]
        elif start <= hi:
            doubt.append(rnd.r)
        else:
            break
    return held + doubt


def _wants(ref: Reference, pairs: set) -> dict:
    """The reference's answers for (node, round) pairs, a round at a time
    → {(node, round): (pod watts [w, z], node watts [z] or None)}."""
    fleet = ref.fleet
    out = {}
    for r in sorted({r for _i, r in pairs}):
        nodes = np.asarray(sorted(i for i, rr in pairs if rr == r), np.intp)
        model = nodes[fleet.mode[nodes] == 1]
        ratio = nodes[fleet.mode[nodes] == 0]
        if len(model):
            for i, want in zip(model, ref.model_nodes(model, r)):
                out[int(i), r] = (want, None)
        if len(ratio):
            for i, want, node in zip(ratio, *ref.ratio_nodes(ratio, r)):
                out[int(i), r] = (want, node)
    return out


def _nearest(ref: Reference, i: int, entry: dict, rounds: list[int],
             wants: dict) -> int:
    """Of ``rounds``, the one whose reference lies nearest the answer."""
    got = _unpack(ref.fleet, i, entry, ref.state(rounds[0])[0].gen[i])
    if got is None:
        return rounds[0]
    gaps = [float(np.abs(got[0] - wants[i, r][0]).max()) for r in rounds]
    return rounds[int(np.argmin(gaps))]


def final_model_nodes(fleet) -> set[int] | None:
    """The model nodes of the final window whose watts go through the
    reference: None (all of them) unless the configuration's
    ``check.final_model_nodes`` is a number, then that many, drawn from
    the seed as ``drive.sample_nodes`` draws."""
    how = fleet.config.get("check", {}).get("final_model_nodes", "all")
    if how == "all":
        return None
    rng = np.random.default_rng([fleet.seed, 4])
    model = np.flatnonzero(fleet.mode == 1)
    return {int(i) for i in rng.choice(model, min(int(how), len(model)),
                                       replace=False)}


def compare(drive: Drive, ref: Reference, launch: dict) -> Errors:
    from chipbench.fleetgen import BATCH

    fleet = drive.fleet
    out = Errors()
    sampled = []
    for win in drive.windows:
        if not drive.t_open <= win.seen <= drive.t_close:
            continue
        for i, entry in win.answers.items():
            rounds = candidate_rounds(
                drive.all_rounds, i // BATCH, win.stamp,
                win.gauges["last_assembly_ms"] / 1e3)
            gens = [ref.state(r)[0].gen[i] for r in rounds]
            if not rounds or any(
                    not np.array_equal(g, gens[0]) for g in gens[1:]):
                out.counts["answers_skipped_torn"] += 1
                continue
            sampled.append((i, entry, rounds))
    wants = _wants(ref, {(i, r) for i, _e, rounds in sampled
                         for r in rounds})
    for i, entry, rounds in sampled:
        if len(rounds) > 1:
            out.counts["answers_round_in_doubt"] += 1
        _compare_nodes(out, ref, [i], [entry],
                       _nearest(ref, i, entry, rounds, wants), wants)
    # every measured round is due a window that covers it
    stamps = np.asarray([w.stamp for w in drive.windows])
    for rnd in drive.rounds:
        if not (stamps > rnd.end).any():
            out.numbers["rounds_uncovered"] += 1
    nodes = drive.final.get("nodes") if drive.final else None
    if not nodes or drive.final_round < 0:
        out.numbers["final_window_missing"] = 1
    else:
        missing = [i for i in range(fleet.n) if fleet.names[i] not in nodes]
        out.numbers["answers_malformed"] += len(missing) + max(
            0, len(nodes) - fleet.n)
        have = [i for i in range(fleet.n) if fleet.names[i] in nodes]
        out.counts["final_model_nodes_compared"] = _compare_nodes(
            out, ref, have, [nodes[fleet.names[i]] for i in have],
            drive.final_round, only_model=final_model_nodes(fleet))
    out.numbers["compiles_in_window"] = sum(
        1 for at, dur in launch.get("compiles", [])
        if at > drive.t_open and at - dur < drive.t_close)
    for dbg in (drive.debug.get("first", {}), drive.debug.get("last", {})):
        if dbg.get("rung") != 0 or dbg.get("demotions_by_reason") \
                or "last_failure" in dbg:
            out.numbers["windows_off_rung0"] += 1
    return out


def _compare_nodes(out: Errors, ref: Reference, idx: list[int],
                   entries: list[dict], r: int, wants: dict | None = None,
                   only_model: set | None = None) -> int:
    """Pool the errors of ``entries`` (the answers of nodes ``idx``) against
    the reference after round ``r``: ``wants`` where it was worked out
    beforehand, else asked of ``ref`` for all the nodes at once. Where
    ``only_model`` is given, a model node outside it is held to its form
    alone (ids, zones, mode, finite numbers) and its watts are not
    compared. → the model nodes compared."""
    fleet = ref.fleet
    gen = ref.state(r)[0].gen
    good, pubs, pub_nodes = [], [], []
    for i, entry in zip(idx, entries):
        got = _unpack(fleet, i, entry, gen[i])
        if got is None:
            out.numbers["answers_malformed"] += 1
            continue
        if only_model is not None and fleet.mode[i] == 1 \
                and i not in only_model:
            continue
        good.append(i)
        pubs.append(got[0])
        pub_nodes.append(got[1])
    if not good:
        return 0
    good_a = np.asarray(good)
    pubs_a, nodes_a = np.stack(pubs), np.stack(pub_nodes)
    is_model = fleet.mode[good_a] == 1
    out.counts["answers_compared"] += len(good)
    out.counts["pods_compared"] += int(fleet.n_pods[good_a].sum())
    if wants is None:
        wants = _wants(ref, {(i, r) for i in good})
    if is_model.any():
        want = np.stack([wants[i, r][0] for i in good_a[is_model]])
        out.add_model(pubs_a[is_model], nodes_a[is_model], want)
    if (~is_model).any():
        want = np.stack([wants[i, r][0] for i in good_a[~is_model]])
        want_node = np.stack([wants[i, r][1] for i in good_a[~is_model]])
        out.add_ratio(pubs_a[~is_model], nodes_a[~is_model], want,
                      want_node)
    return int(is_model.sum())


def verdict(errors: Errors, limits: dict) -> tuple[bool, dict]:
    """→ (correct, {name: {"value", "limit"}}): every number compared
    beside its limit. A number without a limit in the configuration's file
    is refused: nothing is compared against a guess."""
    table = {}
    ok = True
    for name, value in errors.numbers.items():
        if name not in limits:
            raise KeyError(f"the configuration states no limit for {name}")
        limit = float(limits[name]["limit"])
        table[name] = {"value": value, "limit": limit}
        if not (np.isfinite(value) and value <= limit):
            ok = False
    if errors.counts["answers_compared"] == 0:
        ok = False
    return ok, table
