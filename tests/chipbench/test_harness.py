"""chipbench's own arithmetic and data files, on the CPU, in seconds.

Nothing here measures: a time or a rate comes only from a chip run. These
tests hold the yardstick still — the files every cell is found by, the
operation count, the trace reduction, the percentile and rate arithmetic,
the plain reference against the estimator it describes, and the control
that the comparison has to fail.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import check, control, spec, stats, trace, work  # noqa: E402
from chipbench.fleetgen import Fleet  # noqa: E402
from chipbench.reference import (Reference, make_params,  # noqa: E402
                                 temporal_watts)

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


BENCH = bench()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


# -- the data files -------------------------------------------------------------


def test_benchmark_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    size = os.path.getsize(os.path.join(REPO, "BENCHMARK.json"))
    assert size <= 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines_keep_to_the_contract(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry and key != "source" or (
                key == "source" and "file" in entry):
            text = entry[key]
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text
    for cell in entry.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_parses_and_states_every_limit(config):
    path = os.path.join(REPO, config["file"])
    assert config["file"].startswith("chipbench/configs/")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    assert cfg["name"] == config["name"]
    assert cfg["source"] == config["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    # the widths are the estimator's own, as published: none is cut
    for key, value in spec.estimator_of(cfg).WIDTHS.items():
        assert cfg.get(key) == value, key
    for name in check.Errors().numbers:
        row = cfg["limits"][name]
        assert row["limit"] >= 0
        if row["limit"] > 0:  # set between two readings, both written down
            assert row["lower"] < row["limit"] < row["upper"]
            assert row["upper"] >= 3 * row["lower"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_found_by_name_and_reports_what_it_must(cell):
    found = spec.load_cell(REPO, cell)
    assert found.traffic["loop"] in ("open", "closed")
    assert found.traffic["interval_s"] > 0
    e2e = {m["name"] for m in found.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = found.metrics("per_layer")
    assert per
    for m in per:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    for m in found.metrics("end_to_end") + per:
        read, args = found.reader(m["name"])
        assert callable(read) and isinstance(args, dict)
    if found.workload["chips"] != 1:
        assert found.workload["chips"] == 4


@pytest.mark.parametrize("cell", CELLS)
def test_the_configurations_runtime_env_reaches_the_aggregator(cell):
    from chipbench import run

    found = spec.load_cell(REPO, cell)
    given = {"JAX_PLATFORMS": "cpu", "TPU_PREMAPPED_BUFFER_SIZE": "1"}
    wanted = found.config.get("runtime_env", {})
    assert all(isinstance(v, str) for v in wanted.values())
    assert run.child_env(found, given) == {**given, **wanted}
    assert given == {"JAX_PLATFORMS": "cpu",
                     "TPU_PREMAPPED_BUFFER_SIZE": "1"}  # not changed


def test_every_file_under_traffic_and_metrics_parses():
    for sub in ("traffic", "metrics", "configs"):
        folder = os.path.join(REPO, "chipbench", sub)
        for name in os.listdir(folder):
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name)
            with open(os.path.join(folder, name), encoding="utf-8") as f:
                assert isinstance(json.load(f), dict)
    named = {m["name"] for m in METRICS}
    on_disk = {n[:-len(".json")] for n in os.listdir(
        os.path.join(REPO, "chipbench", "metrics"))}
    assert named == on_disk


def test_layers_of_one_name_are_spelled_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers)
    # every roofline has one whole-step share beside it: a metric with
    # ``mfu`` in its name, of the same mix, moving the same metric in the
    # same cells, so that a kernel taken off the path leaves a bound
    roofs = [m for m in BENCH["per_layer"] if "roofline" in m["name"]]
    for m in roofs:
        assert m["unit"] == "%"
        mix = m["name"].rsplit(".", 1)[-1]
        twins = [x for x in BENCH["per_layer"]
                 if "mfu" in x["name"] and x["name"].endswith("." + mix)
                 and x["moves"] == m["moves"]
                 and sorted(x.get("workloads", CELLS))
                 == sorted(m.get("workloads", CELLS))]
        assert len(twins) == 1, (m["name"], [x["name"] for x in twins])


# -- operations and bytes -------------------------------------------------------


def test_window_work_against_a_hand_count():
    # 3 pods, 2 ticks, 2 features, width 4, MLP 8, 1 zone, by hand:
    # per tick: in-proj 2*2*4=16, K and V 2*(2*4*4)=64 -> 80; two ticks 160
    # query 32; scores 2*2*4=16; values 16; out-proj 32; MLP 2*(2*4*8)=128
    # head 2*4*1=8; skip 2*2*1=4  -> per pod 396
    flops, nbytes = work.window_work(3, t=2, f=2, d=4, d_mlp=8, z=1)
    assert flops == 3 * 396
    # bytes: per pod 2*2*4 features + 2 mask + 4 out = 22; parameters:
    # in 8, pos 8, qkvo 64, mlp 64, b0 8, b1 4, 3 LNs 24, head 4, bias 1,
    # skip 2 = 187 floats
    assert nbytes == 3 * 22 + 187 * 4


def test_peaks_come_from_the_table_or_not_at_all():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.peaks("source")
    least, bound = work.least_seconds(197e12, 1.0, peak)
    assert (least, bound) == (1.0, "compute")
    least, bound = work.least_seconds(1.0, 819e9 * 2, peak)
    assert (least, bound) == (2.0, "bandwidth")


# -- the trace reduction --------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """Two windows of a paced cell (256 nodes, T 128) cut from a run on one
    v5e."""
    with open(os.path.join(HERE, "trace_small.json"), encoding="utf-8") as f:
        return json.load(f)


def test_union_counts_overlap_once():
    events = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 31, 1],
              ["zero", 50, 0]]
    assert trace.union(events) == [(0, 15), (30, 35)]
    plane = {"plane": "/device:TPU:0",
             "lines": [{"line": "XLA Ops", "events": events}]}
    assert trace.busy_seconds([plane]) == pytest.approx(20e-9)
    assert trace.idle_share([plane], 100e-9) == pytest.approx(0.8)
    assert trace.idle_share([plane], 0.0) is None
    assert trace.idle_gaps([plane]) == [(15, 30)]


def test_a_trace_with_no_device_op_reads_nothing_not_zero():
    plane = {"plane": "/device:TPU:0", "lines": []}
    assert trace.busy_seconds([plane]) == 0.0
    assert trace.idle_share([plane], 1.0) is None
    assert trace.program_ms([plane]) is None


def test_reduction_of_the_recorded_trace(recorded):
    planes = recorded["planes"]
    expect = recorded["expect"]
    assert trace.busy_seconds(planes) == pytest.approx(expect["busy_s"])
    # two whole runs of the program, whose ops sum to the busy time
    assert trace.program_ms(planes) == pytest.approx(
        expect["op_s"] * 1e3 / expect["program_runs"])
    idle = trace.idle_share(planes, expect["window_s"])
    assert idle == pytest.approx(1 - expect["busy_s"] / expect["window_s"])
    top = trace.top_ops(planes)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert top[0][0] == expect["top_op"]


# -- percentiles and rates ------------------------------------------------------


def test_percentile_interpolates_and_carries_infinity():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 11)), 90) == pytest.approx(9.1)
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([1, 2, 3, math.inf], 50) == 2.5
    assert stats.percentile([1, 2, 3, math.inf], 90) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_window_never_seen_counts_as_failed_and_as_infinity():
    wins = [SimpleNamespace(stamp=10.0 + k, seen=10.6 + k) for k in range(5)]
    lat, attempted, failed = stats.window_latencies(
        wins, 10.5, 15.0, published=5, count_from=10.4, count_to=15.1)
    assert (attempted, failed) == (5, 0)
    assert lat == pytest.approx([600.0] * 5)
    # the aggregator says it published 7: two were never seen
    lat, attempted, failed = stats.window_latencies(
        wins, 10.5, 15.0, published=7, count_from=10.4, count_to=15.1)
    assert (attempted, failed) == (7, 2)
    assert stats.percentile(lat, 50) == pytest.approx(600.0)
    assert stats.percentile(lat, 90) == math.inf
    # a window seen outside the measured window is no sample
    lat, attempted, failed = stats.window_latencies(
        wins, 12.0, 13.0, published=1, count_from=11.9, count_to=13.1)
    assert (len(lat), attempted, failed) == (1, 1, 0)


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(1000.0, 50.0) == 20.0
    with pytest.raises(ValueError):
        stats.rate(1.0, 0.0)


# -- the generator --------------------------------------------------------------

SMALL = {"nodes": 16, "pods_per_node": [70, 110], "zones": [
    "package", "core", "dram", "uncore"], "dt_s": 5.0,
    "model_node_share": 0.5, "d_model": 128, "mlp_dim": 512, "t_max": 128,
    "n_features": 7, "estimator": "temporal"}
CHURN = {"churn_node_share": 0.25, "churn_pod_share": 0.1, "cpu_sigma": 0.25,
         "zone_sigma": 0.1, "ratio_sigma": 0.05}


def test_every_seed_posts_the_same_sizes_in_another_order():
    a, b = Fleet(SMALL, CHURN, 1), Fleet(SMALL, CHURN, 2 ** 31 + 7)
    assert sorted(a.n_pods) == sorted(b.n_pods)
    assert a.total_pods == b.total_pods and a.w == b.w == 110
    # the estimator's share of the work is the same for every seed too
    assert sorted(a.n_pods[a.mode == 1]) == sorted(b.n_pods[b.mode == 1])
    assert a.model_pods == b.model_pods
    assert list(a.n_pods) != list(b.n_pods)
    assert a.zones == ("core", "dram", "package", "uncore")
    assert int((a.mode == 1).sum()) == 8


def test_a_round_is_a_function_of_seed_and_round():
    a, b = Fleet(SMALL, CHURN, 5), Fleet(SMALL, CHURN, 5)
    b.state(9)  # asking in another order changes nothing
    s1, s2 = a.state(3), b.state(3)
    for key in ("cpu", "zone", "ratio", "gen", "born"):
        assert np.array_equal(getattr(s1, key), getattr(s2, key)), key
    assert a.ids(2, s1.gen[2]) == b.ids(2, s2.gen[2])


def test_every_report_of_every_round_is_a_real_delta():
    """No report is bitwise its node's last one, so none goes over the wire
    as the empty "nothing changed" frame; a node whose pods changed sends a
    keyframe."""
    from kepler_tpu.fleet.wire import parse_header

    fleet = Fleet(SMALL, CHURN, 7)
    s3, s4 = fleet.state(3), fleet.state(4)
    pods = fleet.valid
    assert (s3.cpu[pods] != s4.cpu[pods]).all()
    assert (s3.zone != s4.zone).all() and (s3.ratio != s4.ratio).all()
    assert (s4.cpu[pods] > 0).all() and (s4.cpu[~pods] == 0).all()
    for r in range(3):
        st = fleet.state(r)
        node_cpu = fleet.node_cpu(st)
        churned = set(fleet.churned_nodes(r).tolist())
        for i in range(fleet.n):
            head = parse_header(fleet.payload(i, st, node_cpu, 0.0))
            assert not head.same
            # round 0 and a node with new pods: a keyframe; else a delta
            assert head.is_delta == (r > 0 and i not in churned), (r, i)


def test_pods_come_and_go_and_a_newcomers_history_starts_with_it():
    fleet = Fleet(SMALL, CHURN, 9)
    assert len(fleet.churned_nodes(0)) == 0
    assert len(fleet.churned_nodes(1)) == 4  # a quarter of 16 nodes
    # every node has its turn
    assert {int(i) for r in range(1, 5) for i in fleet.churned_nodes(r)} \
        == set(range(16))
    s0, s5 = fleet.state(0), fleet.state(5)
    assert (s0.gen == 0).all() and (s0.born == 0).all()
    new = s5.gen > 0
    assert new.any() and (s5.born[new] >= 1).all() and not new[~fleet.valid].any()
    i = int(np.flatnonzero(new.any(axis=1))[0])
    assert fleet.ids(i, s5.gen[i]) != fleet.ids(i, s0.gen[i])
    assert len(set(fleet.ids(i, s5.gen[i]))) == fleet.n_pods[i]
    ref = Reference(fleet, make_params(9, SMALL), 4)
    hist, t_valid = ref.history(np.asarray([i]), 5)
    j = int(np.flatnonzero(new[i])[0])
    held = 5 - int(s5.born[i, j]) + 1
    assert held < 4 or s5.born[i, j] <= 2
    assert t_valid[0, j].tolist() == [k < min(held, 4) for k in range(4)]
    assert (hist[0, j][~t_valid[0, j]] == 0).all()
    old = int(np.flatnonzero(~new[i] & fleet.valid[i])[0])
    assert t_valid[0, old].all()


# -- the reference, its program and its control ---------------------------------


def small_cell(config: dict, nodes: int = 16) -> spec.Cell:
    """The configuration at a size a test run can hold (its estimator's
    ``small``, at ``nodes`` nodes), under its first cell's mix with churn a
    node of 16 can show."""
    with open(os.path.join(REPO, config["file"]), encoding="utf-8") as f:
        cfg = json.load(f)
    found = spec.load_cell(REPO, next(
        w["name"] for w in BENCH["workloads"] if w["config"] == cfg["name"]))
    traffic = dict(found.traffic, churn_node_share=0.25)
    cut = found.estimator().small(cfg)
    return spec.Cell(REPO, BENCH, found.workload, dict(cut, nodes=nodes),
                     traffic)


# the precision a configuration states, by its estimator's control, the
# step below it
STATED = {"fp8": "bf16", "bf16": "f32"}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_control_in_the_programs_place_is_not_correct(config):
    """The control: the reference in the estimator's ``CONTROL`` (fp8 e4m3
    for temporal), the step below the precision the configuration states
    (bf16), put in the program's place and taken through the run's own
    comparison. It has to come out not correct; the same at the stated
    precision, which is what the chip computes, has to pass. Here on the
    estimator's ``small`` configuration at 16 nodes, never at published
    widths; ``chipbench/control.py`` does it at the cell's own size, and
    PERF.md has those readings."""
    cell = small_cell(config)
    lower = cell.estimator().CONTROL
    correct, low = control.control_run(cell, 11, lower)
    assert correct is False
    over = [k for k, v in low.items() if v["value"] > v["limit"]]
    assert any(k.startswith("model_") for k in over), low
    assert any(k.startswith("ratio_") for k in over), low
    correct, stated = control.control_run(cell, 11, STATED[lower])
    assert correct is True, stated
    assert low["model_pod_rms_rel"]["value"] \
        > 3 * stated["model_pod_rms_rel"]["value"]


def test_an_answer_whose_round_is_in_doubt_is_held_against_the_nearer():
    rounds = [SimpleNamespace(r=r, batches=[(None, r + 0.0, r + 0.1),
                                            (None, r + 0.1, r + 0.2)])
              for r in range(4)]
    # assembled 1.5-1.7: round 1 ended, round 2 not begun
    assert check.candidate_rounds(rounds, 0, 1.5, 0.2) == [1]
    # assembled 1.9-2.3: round 2's POST of batch 1 fell inside
    assert check.candidate_rounds(rounds, 1, 1.9, 0.4) == [1, 2]
    assert check.candidate_rounds(rounds, 0, 1.9, 0.05) == [1]
    # a window so long that two rounds were posted under it
    assert check.candidate_rounds(rounds, 0, 0.5, 2.0) == [0, 1, 2]
    assert check.candidate_rounds(rounds, 0, -1.0, 0.1) == []


def test_reference_agrees_with_the_estimator_it_describes():
    """The reference imports nothing of the program; this test is where the
    two meet: ``predict_temporal`` at float32 on the CPU and the reference
    give the same watts for the same history, young pods' short histories
    among them."""
    import jax.numpy as jnp

    from kepler_tpu.models.temporal import predict_temporal

    cfg = dict(SMALL, nodes=4, pods_per_node=[5, 9])
    fleet = Fleet(cfg, dict(CHURN, churn_pod_share=0.3), 3)
    params = make_params(3, cfg)
    ref = Reference(fleet, params, 6)
    nodes = np.flatnonzero(fleet.mode == 1)
    hist, t_valid = ref.history(nodes, 20)  # [n, w, t, 7], [n, w, t]
    assert not t_valid[fleet.valid[nodes]].all()  # some pods are young
    want = ref.model_nodes(nodes, 20)
    got = predict_temporal(
        {k: jnp.asarray(v) for k, v in params.items()}, jnp.asarray(hist),
        jnp.asarray(fleet.valid[nodes]), t_valid=jnp.asarray(t_valid),
        compute_dtype=jnp.float32)
    assert want.max() > 1.0  # watts, not zeros
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # and the history is what the aggregator's own buffer holds after the
    # same reports, oldest tick first, a newcomer's ticks at the front
    from kepler_tpu.monitor.history import HistoryBuffer
    from kepler_tpu.resource.informer import FeatureBatch
    i = int(nodes[0])
    n_p = int(fleet.n_pods[i])
    buf = HistoryBuffer(window=6)
    for r in range(21):
        st = fleet.state(r)
        buf.push(FeatureBatch(
            kinds=np.zeros(n_p, np.int8), ids=fleet.ids(i, st.gen[i]),
            cpu_deltas=st.cpu[i, :n_p],
            node_cpu_delta=float(fleet.node_cpu(st)[i]),
            usage_ratio=float(st.ratio[i])), fleet.dt)
    feats, valid = buf.window_arrays(fleet.ids(i, st.gen[i]))
    assert np.array_equal(valid, t_valid[0, :n_p])
    np.testing.assert_allclose(hist[0, :n_p], feats, rtol=1e-6)


def test_quantizers_round_as_their_formats_do():
    from chipbench.reference import QUANTIZERS
    x = np.asarray([1.0, 1.00390625, 1.01171875, 3.3, -0.3, 500.0],
                   np.float32)
    bf16 = QUANTIZERS["bf16"](x)
    assert list(bf16[:3]) == [1.0, 1.0, 1.015625]  # ties to even, 8 bits
    fp8 = QUANTIZERS["fp8"](x)
    assert list(fp8) == [1.0, 1.0, 1.0, 3.25, -0.3125, 448.0]
    hist = np.ones((2, 3, 7), np.float32)
    p = make_params(1, SMALL)
    assert temporal_watts(p, hist, np.ones((2, 3), bool)).shape == (2, 4)
