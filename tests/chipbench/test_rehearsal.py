"""The whole of a run, at a tiny size, against a CPU child.

``JAX_PLATFORMS=cpu`` is set on purpose: these runs show that the harness
drives ``cmd.aggregator.main`` over HTTP, prints the contract's line and
names the device it ran on — a CPU. No time, rate or share they print is a
device metric, and none is asserted on.

The cell they run is one no file of the repo knows: a configuration, a
traffic mix and a metric dropped into a copy of the benchmark's
directories, found by name with no edit to a file that was there — which is
what later PRs' additions rely on.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import run, spec  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 11  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with a second configuration, a new
    traffic mix and a new metric added beside what is there."""
    root = str(tmp_path_factory.mktemp("bench"))
    os.makedirs(os.path.join(root, "chipbench"))
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(root, "chipbench", sub))
    before = {sub: sorted(os.listdir(os.path.join(root, "chipbench", sub)))
              for sub in ("configs", "traffic", "metrics")}
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "chipbench", "configs",
                           "temporal-shipped.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(
        name="tiny", nodes=8, pods_per_node=[4, 4], history_window=4,
        aggregator_config={
            "tpu": {"workloadBucket": 8, "nodeBucket": 8},
            "aggregator": {"staleAfter": 5.0}})

    def put(sub: str, name: str, obj: dict) -> None:
        with open(os.path.join(root, "chipbench", sub, name), "w",
                  encoding="utf-8") as f:
            json.dump(obj, f)

    put("configs", "tiny.json", cfg)
    with open(os.path.join(REPO, "chipbench", "traffic", "paced.json"),
              encoding="utf-8") as f:
        paced = json.load(f)
    # a quarter of the nodes replace a pod each round: keyframes, and
    # young pods' short histories, all through the run
    put("traffic", "trickle.json", dict(
        paced, interval_s=0.25, churn_node_share=0.25, churn_pod_share=0.25))
    put("metrics", "scatter_ms.trickle.json",
        {"name": "scatter_ms.trickle", "reader": "gauge_median",
         "args": {"gauges": ["last_scatter_ms"]}})
    bench["configs"].append({"name": "tiny", "source": "a test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "a test"})
    cells = {"tiny.trickle": "trickle", "tiny.flood": "flood"}
    for name, traffic in cells.items():
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = m["workloads"][0].rsplit(".", 1)[1]
            m["workloads"].append(
                "tiny.trickle" if kind == "paced" else "tiny.flood")
    bench["per_layer"].append({
        "name": "scatter_ms.trickle", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "dispatch, wait, fetch, publish",
        "moves": "window_latency_p50_ms", "workloads": ["tiny.trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    for sub, names in before.items():  # nothing that was there was edited
        for name in names:
            with open(os.path.join(root, "chipbench", sub, name), "rb") as a, \
                    open(os.path.join(REPO, "chipbench", sub, name),
                         "rb") as b:
                assert a.read() == b.read()
    return root


def child_env(tmp_path_factory) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.getbasetemp() / "jax_cache")
    return env


def test_new_cell_is_found_by_name(root):
    cell = spec.load_cell(root, "tiny.trickle")
    assert cell.config["nodes"] == 8
    assert cell.traffic["interval_s"] == 0.25
    assert cell.traffic["loop"] == "open"
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "scatter_ms.trickle" in names
    assert "ingest_reports_per_s.flood" not in names
    read, args = cell.reader("scatter_ms.trickle")
    assert args == {"gauges": ["last_scatter_ms"]}
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "tiny.nowhere")


@pytest.fixture(scope="module")
def traced(root, tmp_path_factory):
    """One traced run of the open-loop cell, shared by the tests below."""
    return run.run_cell("tiny.trickle", SEED, 2.0, True, root=root,
                        platform="cpu", env=child_env(tmp_path_factory))


def test_traced_run_prints_the_contracts_line_and_names_the_cpu(traced):
    rc, line = traced
    assert rc == 0
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert set(keys) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "notes", "compared"}
    assert keys[-1] == "compared"  # each number beside its limit, last
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    dev = line["device"]
    assert (dev["platform"], dev["kind"], dev["count"]) == ("cpu", "cpu", 1)
    # never a device metric from a CPU: the readers found no device plane
    # and no published peak, and left their metrics out of the line
    assert dev["busy_s"] is None and dev["window_s"] is None
    got = set(line["metrics"])
    assert {"assembly_ms.paced", "publish_ms.paced",
            "generator_late_ms.paced", "scatter_ms.trickle"} <= got
    assert not {"program_ms.paced", "temporal_roofline.paced",
                "window_mfu.paced", "device_idle_pct.paced"} & got
    for row in line["metrics"].values():
        assert set(row) == {"value", "unit"}
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    notes = line["notes"]
    assert notes["answers_compared"] >= 8 + 4  # the fleet, and samples
    # every window's own latency travels with an open-loop run's line
    assert len(notes["latencies_ms"]) == line["attempted"] == notes["windows"]
    assert all(0.0 < ms < 60e3 for ms in notes["latencies_ms"])
    assert "latency_p90_ms.paced" in got
    assert (notes["reports_throttled"], notes["throttle_wait_s"]) == (0, 0.0)
    # a CPU trace has no device plane: the zero has nothing to be held
    # against, and says so
    assert notes["trace_zero"] is None
    assert json.loads(json.dumps(line)) == line


def test_untraced_closed_loop_reports_the_end_to_end_metrics(
        root, tmp_path_factory):
    rc, line = run.run_cell("tiny.flood", SEED + 1, 2.0, False, root=root,
                            platform="cpu", env=child_env(tmp_path_factory))
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"pods_per_s", "setup_s"}
    assert "breakdown" not in line
    assert line["metrics"]["pods_per_s"]["value"] > 0
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert "latencies_ms" not in line["notes"]  # a closed loop has rounds
    assert "trace_zero" not in line["notes"]


def test_a_throttle_is_waited_out_and_the_run_ends_correct(
        root, tmp_path_factory):
    """Admission sheds once in the warm-up (a machine freeze does that to a
    run on the chip): the report and the rest of its POST are sent again,
    nothing is lost, and the run says how much it was throttled."""
    env = child_env(tmp_path_factory)
    env["CHIPBENCH_TEST_FAULT"] = "shed_once"
    env["CHIPBENCH_TEST_FAULT_AFTER"] = str(4 * 8 + 3)  # round 5, report 3
    rc, line = run.run_cell(
        "tiny.flood", SEED + 3, 1.0, False, root=root, platform="cpu",
        env=env, launcher=os.path.join(HERE, "broken_launch.py"))
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["notes"]["reports_throttled"] == 8 - 2
    assert line["notes"]["throttle_wait_s"] == pytest.approx(0.2)


@pytest.mark.parametrize("fault, after", [
    ("answer_altered", 0), ("history_stalled", 4 * 5), ("half_left_out", 0)])
def test_a_broken_timed_path_comes_out_not_correct(
        root, tmp_path_factory, fault, after):
    """The rest of a run, with the timed path broken underneath: the
    comparison has to say so, by a number over its limit."""
    env = child_env(tmp_path_factory)
    env["CHIPBENCH_TEST_FAULT"] = fault
    env["CHIPBENCH_TEST_FAULT_AFTER"] = str(after)
    rc, line = run.run_cell(
        "tiny.flood", SEED + 2, 1.0, False, root=root, platform="cpu",
        env=env, launcher=os.path.join(HERE, "broken_launch.py"))
    assert rc == 0
    assert line["correct"] is False
    over = [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    assert any(k.startswith("model_") for k in over), line["compared"]


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", BENCH_CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "chipbench: FAIL: in ready:" in proc.stderr


with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH_CELL = json.load(_f)["workloads"][0]["name"]
