"""The four-chip cell (ISSUE 31), on the CPU: its files, its readers'
arithmetic on two hand-made device planes, and an untraced rehearsal of a
tiny copy of it on a child with four virtual devices (a traced one is left
to ``test_rehearsal.py``: two CPU children under the profiler at once have
hung it, PERF.md section 7; what the counters of a served body read through
the benchmark's reader is in ``tests/test_sharded_put.py``).

``temporal-k8s-limit.flood`` is the cluster at Kubernetes' documented
limit, whole, node-sharded over the four chips of one host. Nothing here
measures: the rehearsal shows that the harness drives the served path over
a mesh of four devices, that what it publishes is held against the plain
reference (which knows nothing of shards) under the configuration's own
limits, and what the program counts. ``JAX_PLATFORMS=cpu`` on purpose.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import run, spec, trace, work  # noqa: E402
from chipbench.readers import (collective_share, count_ratio,  # noqa: E402
                               plane_skew, program_time_slowest,
                               roofline_chips, step_mfu_chips)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "temporal-k8s-limit.flood"
SEED = 2 ** 31 + 31  # more than 32 signed bits hold
NEW = ("program_ms_slowest.flood4", "shard_skew_pct.flood4",
       "collective_pct.flood4", "temporal_roofline.flood4",
       "window_mfu.flood4", "h2d_max_device_mb.flood4")


# -- the cell's files -----------------------------------------------------------


def test_the_cell_is_the_deployment_the_issue_states():
    cell = spec.load_cell(REPO, CELL)
    cfg = cell.config
    assert cell.workload["chips"] == 4
    assert cell.traffic["loop"] == "closed"
    assert (cfg["nodes"], cfg["pods_per_node"], cfg["history_window"]) == (
        5000, [10, 50], 16)
    assert cfg["reduced"] == {}  # the source's cluster, whole
    settings = cfg["aggregator_config"]
    assert settings["tpu"]["nodeBucket"] == cfg["nodes"]
    assert settings["tpu"]["nodeBucket"] % 4 == 0  # 1250 nodes a chip
    assert settings["aggregator"]["baseRowCache"] >= cfg["nodes"]
    # no node is ever silent in this traffic; the profiler's stop is, for
    # as long as the harness waits for it (child.trace: 120 s)
    assert settings["aggregator"]["staleAfter"] == 120.0
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) <= names
    # the whole fleet's work over ONE chip's peak, and the first plane's
    # program alone: the one-chip cells' readers stay with them
    assert not {"program_ms.flood", "temporal_roofline.flood",
                "window_mfu.flood"} & names
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "pods_per_s", "setup_s"}


def test_the_fleet_is_at_the_clusters_pod_limit():
    from chipbench.fleetgen import BATCH, Fleet

    cell = spec.load_cell(REPO, CELL)
    fleet = Fleet(cell.config, cell.traffic, SEED)
    assert fleet.n == 5000 and -(-fleet.n // BATCH) == 20  # POSTs a round
    assert fleet.total_pods == 149_980  # of the 150,000 a cluster may hold
    assert fleet.model_pods == 75_000
    assert (fleet.n_pods.min(), fleet.n_pods.max()) == (10, 50)
    assert len(fleet.churned_nodes(1)) == 50  # 1 % of the nodes a round


# -- the readers' arithmetic ------------------------------------------------------


@pytest.fixture(scope="module")
def two_planes():
    """Two chips' device planes made by hand (the file says how), and the
    profiler's own plane, which holds nothing."""
    with open(os.path.join(HERE, "trace_two_planes.json"),
              encoding="utf-8") as f:
        return json.load(f)


def a_run(planes: list, chips: int | None = 2, windows: int = 7,
          counts: tuple[dict, dict] | None = None) -> SimpleNamespace:
    cell = spec.load_cell(REPO, CELL)
    first, last = counts or ({}, {})
    return SimpleNamespace(
        planes=planes, peak=work.peaks("TPU v5 lite"),
        work=work.of_config(cell.config, 75_000),
        launch={} if chips is None else {"count": chips},
        windows_in=[None] * windows,
        drive=SimpleNamespace(seconds=50.0, debug={
            "first": {"counts": first}, "last": {"counts": last}}))


def test_the_slowest_plane_sets_the_programs_time(two_planes):
    planes, expect = two_planes["planes"], two_planes["expect"]
    assert program_time_slowest.per_plane_ms(planes) == pytest.approx(
        expect["program_ms"])
    # trace.program_ms reads the first plane that ran and stops there
    assert trace.program_ms(planes) == pytest.approx(expect["program_ms"][0])
    this = a_run(planes)
    slow, fast = max(expect["program_ms"]), min(expect["program_ms"])
    assert program_time_slowest.read(this) == pytest.approx(slow)
    assert plane_skew.read(this) == pytest.approx(
        100.0 * (slow - fast) / slow)
    # whichever plane comes first
    assert program_time_slowest.read(a_run(planes[::-1])) == \
        pytest.approx(slow)


def test_collective_time_is_counted_by_the_ops_own_name(two_planes):
    planes, expect = two_planes["planes"], two_planes["expect"]
    assert collective_share.read(a_run(planes)) == pytest.approx(
        100.0 * expect["collective_us"] / sum(expect["busy_us"]))
    # the second plane alone ran none: 0 is printed, not left out
    assert collective_share.read(a_run(planes[1:])) == 0.0
    yes = ["%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)",
           "%all-gather-start.2 = (f32[2], f32[8]) all-gather-start(%x)",
           "%collective-permute-done = f32[4]{0} collective-permute-done()",
           "%reduce-scatter.7 = f32[1] reduce-scatter(%x)",
           "all-to-all.3"]
    # an op that only reads a collective's result is none
    no = ["%fusion.3 = f32[4]{0} fusion(f32[4]{0} %all-reduce.1)",
          "%copy.42 = f32[262144,16,128] copy(%fusion.5)", "%reduce.4"]
    assert all(collective_share.is_collective(n) for n in yes)
    assert not any(collective_share.is_collective(n) for n in no)


def test_the_shares_divide_by_every_chip_the_run_held(two_planes):
    planes, expect = two_planes["planes"], two_planes["expect"]
    this = a_run(planes, chips=2)
    flops, nbytes = this.work
    peak = this.peak
    least = max(flops / (2 * peak["flops_per_s"]),
                nbytes / (2 * peak["bytes_per_s"]))
    slow = max(expect["program_ms"])
    assert roofline_chips.read(this) == pytest.approx(
        100.0 * least / (slow / 1e3))
    assert step_mfu_chips.read(this) == pytest.approx(
        100.0 * flops * 7 / (50.0 * 2 * 197e12))
    # twice the chips, half the share: what the one-chip readers, which
    # divide by one chip's peak, would overstate fourfold on four
    assert roofline_chips.read(a_run(planes, chips=4)) == pytest.approx(
        roofline_chips.read(this) / 2)
    assert step_mfu_chips.read(a_run(planes, chips=4)) == pytest.approx(
        step_mfu_chips.read(this) / 2)
    assert 0 < roofline_chips.read(this) < 100
    assert 0 < step_mfu_chips.read(this) < 100


def test_each_reader_returns_nothing_where_the_run_lacks_what_it_reads(
        two_planes):
    planes = two_planes["planes"]
    untraced = a_run([])
    for reader in (program_time_slowest, plane_skew, collective_share,
                   roofline_chips):
        assert reader.read(untraced) is None
    # planes that ran nothing (the profiler's own): nothing, never 0
    idle = a_run(planes[2:])
    for reader in (program_time_slowest, plane_skew, collective_share,
                   roofline_chips):
        assert reader.read(idle) is None
    assert plane_skew.read(a_run(planes[:1])) is None  # one chip: no skew
    # a launcher that names no device count, a run with no window, a CPU
    for reader in (roofline_chips, step_mfu_chips):
        assert reader.read(a_run(planes, chips=None)) is None
        no_peak = a_run(planes)
        no_peak.peak = None
        assert reader.read(no_peak) is None
    assert step_mfu_chips.read(a_run(planes, windows=0)) is None
    # the parent's /debug/window has no h2d_bytes_max_device: the metric
    # is left out there, and read where the program counts it
    cell = spec.load_cell(REPO, CELL)
    read, args = cell.reader("h2d_max_device_mb.flood4")
    assert read is count_ratio.read
    parent = ({"windows": 3, "h2d_bytes": 3 * 600},
              {"windows": 10, "h2d_bytes": 10 * 600})
    assert read(a_run(planes, counts=parent), **args) is None
    change = ({"windows": 3, "h2d_bytes_max_device": 3 * 150_000_000},
              {"windows": 10, "h2d_bytes_max_device": 10 * 150_000_000})
    assert read(a_run(planes, counts=change), **args) == \
        pytest.approx(150.0)


# -- a tiny copy of the cell on four virtual devices -------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark's data as it stands, with the cell's configuration at
    16 nodes (4 a device) under another name; no file that is there is
    edited."""
    root = str(tmp_path_factory.mktemp("bench4"))
    shutil.copytree(os.path.join(REPO, "chipbench", "configs"),
                    os.path.join(root, "chipbench", "configs"))
    for sub in ("traffic", "metrics"):
        os.symlink(os.path.join(REPO, "chipbench", sub),
                   os.path.join(root, "chipbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cfg = dict(spec.load_cell(REPO, CELL).config)
    cfg.update(name="tiny4", nodes=16, history_window=4, aggregator_config={
        "tpu": {"workloadBucket": 64, "nodeBucket": 16},
        "aggregator": {"baseRowCache": 16, "staleAfter": 120.0}})
    with open(os.path.join(root, "chipbench", "configs", "tiny4.json"), "w",
              encoding="utf-8") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "tiny4", "source": "a test",
                             "file": "chipbench/configs/tiny4.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny4.flood", "config": "tiny4",
                               "traffic": "flood", "chips": 4,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny4.flood")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def child_env(tmp_path_factory) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.getbasetemp() / "jax_cache4")
    return env


def test_untraced_rehearsal_on_four_devices_is_correct(root,
                                                       tmp_path_factory):
    rc, line = run.run_cell("tiny4.flood", SEED, 2.0, False, root=root,
                            platform="cpu", env=child_env(tmp_path_factory))
    assert rc == 0 and line["correct"] is True, line and line["compared"]
    dev = line["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", 4)
    assert set(line["metrics"]) == {"pods_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["compared"]["answers_malformed"]["value"] == 0
    assert line["notes"]["answers_compared"] >= 16 + 6  # fleet and samples
