"""The seam an estimator is found by: ``chipbench/estimators/<name>.py``.

Three things, on the CPU, no aggregator but in the last three tests:

1. golden pins: every configuration's seeded parameters (of its
   ``small``) and operation count are its goldens file's
   (``goldens/<config>.json``), and the temporal reference's watts and
   control readings are what they were before the seam was cut (at
   ``e1a6e31``), so the move rewrote nothing;
2. a second estimator, put into ``sys.modules`` with a configuration that
   names it: every part of the harness goes through it with no edit to a
   file that is there; and one at published widths, added as files alone
   to a copy of the tree, passes every per-configuration case;
3. ``check.final_model_nodes``: how many model nodes of the final window go
   through the reference is the configuration's, and what is not sampled is
   still held to its form.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import check, control, records, run, spec, trace, work  # noqa: E402
from chipbench.estimators import temporal  # noqa: E402
from chipbench.fleetgen import Fleet  # noqa: E402
from chipbench.reference import Reference  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)


def shipped(name: str) -> dict:
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"]), encoding="utf-8") as f:
        return json.load(f)


CONFIGS = [c["name"] for c in BENCH["configs"]]

# -- 1. what the parent gave ---------------------------------------------------

GOLDENS = os.path.join(HERE, "goldens")
WATTS = {
    None: [[8.01245403289795, 6.964231491088867, 6.62423038482666,
            9.611072540283203],
           [9.962381362915039, 7.449270725250244, 7.117508411407471,
            10.00908088684082],
           [10.615826606750488, 10.54859733581543, 12.224452018737793,
            13.451379776000977],
           [8.313720703125, 7.085164546966553, 5.7670488357543945,
            8.105062484741211],
           [11.565681457519531, 10.07779598236084, 12.280975341796875,
            15.464784622192383],
           [6.55615234375, 6.01870059967041, 5.650598526000977,
            9.061225891113281]],
    "bf16": [[8.024055480957031, 6.96695613861084, 6.619384288787842,
              9.625297546386719],
             [9.969926834106445, 7.449524879455566, 7.108773231506348,
              10.011279106140137],
             [10.6043119430542, 10.539093017578125, 12.193988800048828,
              13.454767227172852],
             [8.306955337524414, 7.081109523773193, 5.758184432983398,
              8.100395202636719],
             [11.577183723449707, 10.083024978637695, 12.272640228271484,
              15.500612258911133],
             [6.562633037567139, 6.0155930519104, 5.646208763122559,
              9.068601608276367]],
    "fp8": [[7.7707695960998535, 6.9065260887146, 6.5759477615356445,
             9.626001358032227],
            [9.92432975769043, 7.460893154144287, 7.200016975402832,
             9.932504653930664],
            [10.615392684936523, 10.534196853637695, 12.367131233215332,
             13.319269180297852],
            [8.146474838256836, 7.070596218109131, 5.6698198318481445,
             7.942137241363525],
            [11.462751388549805, 9.893449783325195, 12.085415840148926,
             15.154458999633789],
            [6.46831750869751, 6.023545742034912, 5.6917619705200195,
             9.086091995239258]],
}
CONTROL_AT_16_NODES = {  # temporal-shipped under flood, seed 11, fp8
    "model_pod_rms_rel": 0.017607594478635816,
    "model_pod_max_rel": 0.05984068188746495,
    "model_node_max_rel": 0.019485671458506347,
    "ratio_pod_max_rel": 0.008984203427933633,
    "ratio_node_max_rel": 0.0036852646570614852,
}


def digest(params: dict) -> str:
    h = hashlib.sha256()
    for key in sorted(params):
        a = np.ascontiguousarray(params[key])
        for part in (key, str(a.dtype), repr(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def golden_cases(key: str) -> list:
    """(configuration, seed or pods, the golden) for every entry under
    ``key`` of each configuration's goldens (``goldens/<config>.json``:
    ``params_sha256`` by seed, of the estimator's ``small(config)``;
    ``work`` by model pods, of the configuration itself, arithmetic and
    cheap at any width). A configuration without the file gets one case,
    which fails and names it."""
    cases = []
    for config in CONFIGS:
        path = os.path.join(GOLDENS, f"{config}.json")
        if not os.path.exists(path):
            cases.append(pytest.param(config, None, None, id=config))
            continue
        with open(path, encoding="utf-8") as f:
            found = json.load(f)[key]
        cases += [pytest.param(config, int(k), found[k], id=f"{config}-{k}")
                  for k in sorted(found, key=int)]
    return cases


def brings_goldens(config: str, want) -> None:
    assert want is not None, (f"the configuration {config!r} brings no "
                              f"tests/chipbench/goldens/{config}.json")


@pytest.mark.parametrize("config, seed, want", golden_cases("params_sha256"))
def test_seeded_parameters_are_byte_for_byte_what_they_were(config, seed,
                                                            want):
    brings_goldens(config, want)
    cfg = shipped(config)
    estimator = spec.estimator_of(cfg)
    assert digest(estimator.make_params(seed, estimator.small(cfg))) == want


def test_the_temporal_parameters_hold_the_layers_its_reference_reads():
    params = temporal.make_params(7, shipped("temporal-shipped"))
    assert set(params) >= {"in_proj", "pos_emb", "w_head", "w_skip"}


@pytest.mark.parametrize("config, pods, want", golden_cases("work"))
def test_operation_count_is_what_it_was(config, pods, want):
    brings_goldens(config, want)
    assert work.of_config(shipped(config), pods) == tuple(want)


@pytest.mark.parametrize("quantize", [None, "bf16", "fp8"], ids=str)
def test_reference_watts_are_what_they_were(quantize):
    cfg = shipped("temporal-shipped")
    params = temporal.make_params(5, cfg)
    rng = np.random.default_rng([5, 99])
    hist = rng.uniform(0.0, 3.0, (6, 16, 7)).astype(np.float32)
    held = np.asarray([16, 16, 9, 1, 16, 4])
    t_valid = np.arange(16)[None, :] < held[:, None]
    hist = np.where(t_valid[:, :, None], hist, np.float32(0))
    got = temporal.watts(params, hist, t_valid, cfg, quantize)
    assert got.dtype == np.float32
    # float32 through another BLAS rounds the last bits otherwise
    np.testing.assert_allclose(got, WATTS[quantize], rtol=2e-6)
    assert np.array_equal(
        got, temporal.temporal_watts(params, hist, t_valid, quantize))


def small_cell(config: dict, nodes: int = 16, **more) -> spec.Cell:
    """``config`` at a size a test can hold, under the flood mix with churn
    that 16 nodes can show (as ``test_harness.small_cell``)."""
    found = spec.load_cell(REPO, "temporal-shipped.flood")
    traffic = dict(found.traffic, churn_node_share=0.25)
    return spec.Cell(REPO, BENCH, found.workload,
                     dict(config, nodes=nodes, **more), traffic)


def test_control_reads_what_it_read_and_is_not_correct():
    cell = small_cell(shipped("temporal-shipped"))
    correct, table = control.control_run(cell, 11)  # the estimator's own
    assert correct is False
    for name, value in CONTROL_AT_16_NODES.items():
        assert table[name]["value"] == pytest.approx(value, rel=1e-3), name
        assert table[name]["value"] > table[name]["limit"], name
    for name in ("answers_malformed", "rounds_uncovered",
                 "final_window_missing", "compiles_in_window",
                 "windows_off_rung0"):
        assert table[name]["value"] == 0


def test_the_names_from_before_the_seam_are_the_modules_own():
    from chipbench import reference

    assert reference.make_params is temporal.make_params
    assert reference.temporal_watts is temporal.temporal_watts
    assert work.window_work is temporal.window_work
    assert not hasattr(records, "PROGRAM")  # its callers name the program
    assert temporal.CONTROL == "fp8"
    found = spec.estimator_of({"estimator": "temporal"})
    assert found is temporal
    assert all(hasattr(found, name) for name in spec.ESTIMATOR_NAMES)


# -- 2. a second estimator, with no edit to a file that is there ---------------


def fake_estimator() -> types.ModuleType:
    """watts = the newest valid tick's features through one [7, z] matrix,
    plus 2 W: nothing like the temporal trunk, and cheap. It notes what it
    was asked."""
    mod = types.ModuleType("chipbench.estimators.fake")
    mod.calls = {"make_params": 0, "watts": 0, "work": 0, "rows_max": 0,
                 "quantize": set()}
    mod.PROGRAM = "jit_fake_fleet_window"
    mod.CONTROL = "bf16"  # the fake configuration states float32
    mod.WIDTHS = {"n_features": 7}

    def make_params(seed, config):
        mod.calls["make_params"] += 1
        rng = np.random.default_rng([int(seed), 8])
        z = len(config["zones"])
        return {"w": rng.uniform(0.5, 1.5, (7, z)).astype(np.float32)}

    def watts(params, hist, t_valid, config, quantize=None):
        from chipbench.precision import QUANTIZERS

        mod.calls["watts"] += 1
        mod.calls["rows_max"] = max(mod.calls["rows_max"], len(hist))
        mod.calls["quantize"].add(quantize)
        assert hist.shape[1] == config["history_window"]
        last = np.maximum(t_valid.sum(axis=1) - 1, 0)
        x, w = hist[np.arange(len(hist)), last], params["w"]
        q = QUANTIZERS[quantize]
        if q is not None:
            x, w = q(x), q(w)
        return np.matmul(x, w, dtype=np.float32) + np.float32(2.0)

    def work_(config, model_pods):
        mod.calls["work"] += 1
        z = len(config["zones"])
        return float(model_pods) * 2 * 7 * z, float(model_pods) * (28 + 4 * z)

    mod.make_params, mod.watts, mod.work = make_params, watts, work_
    mod.block_rows = lambda config: 250
    mod.small = lambda config: config  # a test's size already
    return mod


@pytest.fixture()
def fake(monkeypatch):
    mod = fake_estimator()
    monkeypatch.setitem(sys.modules, "chipbench.estimators.fake", mod)
    return mod


def fake_config(**more) -> dict:
    cfg = shipped("temporal-shipped")
    for key in ("d_model", "n_heads", "mlp_dim", "t_max", "compute_dtype"):
        del cfg[key]
    limits = copy.deepcopy(cfg["limits"])
    for name in ("model_pod_rms_rel", "model_pod_max_rel",
                 "model_node_max_rel"):
        limits[name] = {"limit": 1e-4}  # float32 is stated: bf16 is over it
    cfg.update(name="fake-small", estimator="fake", nodes=16,
               history_window=5, limits=limits, **more)
    return cfg


def bench_copy(root: str, configs: list[dict]) -> str:
    """The benchmark's data files copied to ``root``, and beside them
    ``configs``, each with a flood cell ``<name>.flood`` of its own that
    reports what the shipped one-chip flood cell reports."""
    os.makedirs(os.path.join(root, "chipbench"))
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(root, "chipbench", sub))
    bench = copy.deepcopy(BENCH)
    for cfg in configs:
        name = cfg["name"]
        with open(os.path.join(root, "chipbench", "configs", f"{name}.json"),
                  "w", encoding="utf-8") as f:
            json.dump(cfg, f)
        bench["configs"].append({
            "name": name, "source": "a test", "reduced": [], "why": "a test",
            "file": f"chipbench/configs/{name}.json"})
        bench["workloads"].append({
            "name": f"{name}.flood", "config": name, "traffic": "flood",
            "chips": 1, "why": "a test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "temporal-shipped.flood" in m.get("workloads", []):
                m["workloads"].append(f"{name}.flood")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


@pytest.fixture()
def fake_root(tmp_path):
    """A configuration that names the estimator ``fake``, and one that
    names an estimator with no module, each with a cell."""
    return bench_copy(str(tmp_path / "bench"), [
        fake_config(),
        dict(fake_config(), name="lost", estimator="nowhere")])


def test_a_cell_finds_its_estimator_by_the_configurations_name(
        fake, fake_root):
    cell = spec.load_cell(fake_root, "fake-small.flood")
    assert cell.estimator() is fake
    assert work.of_config(cell.config, 100) == (100 * 2 * 7 * 4.0,
                                                100 * 44.0)
    assert fake.calls["work"] == 1
    # the shipped cells still find theirs
    assert spec.load_cell(fake_root,
                          "temporal-shipped.flood").estimator() is temporal


def test_an_estimator_without_a_module_fails_where_the_cell_is_loaded(
        fake, fake_root):
    with pytest.raises(spec.SpecError,
                       match=r"chipbench/estimators/nowhere\.py"):
        spec.load_cell(fake_root, "lost.flood")
    with pytest.raises(spec.SpecError, match="'nowhere'"):
        work.of_config({"estimator": "nowhere"}, 1)
    # before anything is started: no child, no parameters, no port
    with pytest.raises(spec.SpecError, match="nowhere"):
        run.run_cell("lost.flood", 1, 1.0, False, root=fake_root,
                     platform="cpu")
    # a module that states less than the seam asks for says what it lacks
    del fake.CONTROL, fake.block_rows
    with pytest.raises(spec.SpecError, match="block_rows, CONTROL"):
        spec.load_cell(fake_root, "fake-small.flood")


def test_the_reference_asks_the_cells_estimator_in_its_own_blocks(
        fake, fake_root):
    cell = spec.load_cell(fake_root, "fake-small.flood")
    fleet = Fleet(cell.config, cell.traffic, 5)
    params = fake.make_params(5, cell.config)
    ref = Reference(fleet, params, cell.config["history_window"])
    nodes = np.flatnonzero(fleet.mode == 1)
    got = ref.model_nodes(nodes, 9)
    # 8 model nodes of 110 slots in blocks of 250 rows: two nodes a call
    assert fake.calls["watts"] == 4 and fake.calls["rows_max"] == 220
    hist, t_valid = ref.history(nodes, 9)
    last = np.maximum(t_valid.sum(axis=2) - 1, 0)
    newest = np.take_along_axis(
        hist, last[:, :, None, None], axis=2)[:, :, 0]  # [n, w, 7]
    want = np.where(fleet.valid[nodes][:, :, None],
                    newest @ params["w"] + 2.0, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[fleet.valid[nodes]].min() >= 2.0  # watts, not zeros


def test_the_control_is_the_estimators_own_step_below(
        fake, fake_root, monkeypatch, capsys):
    cell = spec.load_cell(fake_root, "fake-small.flood")
    correct, table = control.control_run(cell, 3)
    assert fake.calls["quantize"] == {None, "bf16"}
    assert correct is False
    over = {k for k, v in table.items() if v["value"] > v["limit"]}
    # bf16 in the fake's one product, and in the ratio path's beside it
    assert {"model_pod_rms_rel", "ratio_pod_max_rel"} <= over
    # the precision the fake configuration states passes, exactly
    correct, table = control.control_run(cell, 3, "f32")
    assert correct is True
    assert table["model_pod_max_rel"]["value"] < 1e-12  # uW and back
    # and the command, from the cell's name alone
    monkeypatch.setattr(control, "ROOT", fake_root)
    assert control.main(["--workload", "fake-small.flood", "--seed", "3",
                         "4"]) == 0
    out = capsys.readouterr().out
    assert out.count("bf16: correct = False") == 2


def renamed(planes: list, program: str) -> list:
    planes = copy.deepcopy(planes)
    for event in trace.module_events(planes[0]):
        event[0] = program + event[0][event[0].index("("):]
    return planes


def test_the_windows_program_is_found_by_the_estimators_prefix(fake):
    import test_window_records as twr  # the recorded trace's hand-made body

    with open(os.path.join(HERE, "trace_small.json"), encoding="utf-8") as f:
        raw = json.load(f)["planes"]
    old = renamed(raw, temporal.PROGRAM)
    new = renamed(raw, fake.PROGRAM)
    body = twr.planted(old)
    assert records.program_runs(new, temporal.PROGRAM) == []  # not by that
    assert records.program_runs(new, fake.PROGRAM) == \
        records.program_runs(old, temporal.PROGRAM)
    assert records.align(body, new, twr.ZERO, temporal.PROGRAM) is None
    fit = records.align(body, new, twr.ZERO, fake.PROGRAM)
    assert fit == records.align(body, old, twr.ZERO, temporal.PROGRAM)
    assert (fit["checked"], fit["contradicted"]) == (2, 0)
    found = records.idle_by_leg(body, new, twr.ZERO, {"tick": twr.TICK},
                                fake.PROGRAM)
    assert found == records.idle_by_leg(body, old, twr.ZERO,
                                        {"tick": twr.TICK}, temporal.PROGRAM)
    # the reader takes the prefix from the run, which has it from the cell
    from chipbench.readers import idle_by_leg

    def a_run(program):
        return types.SimpleNamespace(
            drive=types.SimpleNamespace(debug={"first": {}, "last": body}),
            planes=new, launch=twr.ZERO, program=program)

    assert idle_by_leg.read(a_run(fake.PROGRAM), legs=twr.TICK) is not None
    assert idle_by_leg.read(a_run(temporal.PROGRAM), legs=twr.TICK) is None


# an estimator at published widths, as the files a later change would add
WIDE_MODULE = '''"""An estimator at published widths whose parameters no test may draw:
``make_params`` refuses more than 64 MB. Its ``small`` is the temporal trunk
32 wide, and its arithmetic is temporal's."""

from chipbench.estimators import temporal

PROGRAM = "jit_wide_fleet_window"
CONTROL = "fp8"
WIDTHS = {"d_model": 6144, "n_heads": 48, "mlp_dim": 24576, "n_features": 7}
MAX_BYTES = 64 * 2 ** 20
READINGS = ("CPU, 16 nodes under paced with churn 0.25, seeds 11-22 and "
            "2147483653: lower = largest at bf16 (the stated precision), "
            "upper = smallest at fp8 (the control)")
LIMITS = {
    "model_pod_rms_rel": {"limit": 3e-3, "lower": 8.92e-4, "upper": 9.02e-3},
    "model_pod_max_rel": {"limit": 1e-2, "lower": 3.26e-3, "upper": 2.95e-2},
    "model_node_max_rel": {"limit": 2.5e-3, "lower": 1.08e-3,
                           "upper": 6.39e-3},
    "ratio_pod_max_rel": {"limit": 1e-5, "lower": 2.12e-16, "upper": 7.66e-3},
    "ratio_node_max_rel": {"limit": 1e-5, "lower": 2.12e-16,
                           "upper": 2.90e-3},
}


def make_params(seed, config):
    d, d_mlp = int(config["d_model"]), int(config["mlp_dim"])
    floats = 4 * d * d + 2 * d * d_mlp + (int(config["t_max"]) + 16) * d
    if 4 * floats > MAX_BYTES:
        raise MemoryError(f"{4 * floats} bytes of parameters asked for")
    return temporal.make_params(seed, config)


def small(config):
    limits = dict(config["limits"])
    limits.update({k: dict(v, readings=READINGS) for k, v in LIMITS.items()})
    return dict(config, d_model=32, n_heads=4, mlp_dim=128, limits=limits)


watts, block_rows, work = temporal.watts, temporal.block_rows, temporal.work
'''
WIDE_GOLDENS = {  # of the module above, taken once by hand
    "params_sha256": {
        "7": "0a1dbeac8096c5bed2ba17911bff1e0fe683ae5ec80007125044b345b7fc7315",
        "2147483659":
            "a614aab02b1360f5943f01816db9f7d4772b920bcac549fe3162adcd5aff2134"},
    "work": {"46080": [146198592184320.0, 1834991744.0],
             "75000": [237953437800000.0, 1848873344.0]}}
PER_CONFIGURATION = {  # every case a configuration and its one cell have
    "test_names_units_and_lines_keep_to_the_contract": ["", ".paced"],
    "test_configuration_file_parses_and_states_every_limit": [""],
    "test_cell_is_found_by_name_and_reports_what_it_must": [".paced"],
    "test_control_in_the_programs_place_is_not_correct": [""],
    "test_seeded_parameters_are_byte_for_byte_what_they_were":
        ["-7", "-2147483659"],
    "test_operation_count_is_what_it_was": ["-46080", "-75000"],
}


def tree_hashes(root: str) -> dict:
    out = {}
    for folder, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_an_estimator_at_published_widths_is_added_as_files_alone(tmp_path):
    """A copy of the yardstick with only files added (and entries in
    ``BENCHMARK.json``): an estimator whose parameters at its published
    widths no test may draw, its configuration, one cell on ``paced``, the
    configuration's goldens, and its roofline and whole-step share. Every
    per-configuration case of the copy passes for it, none is skipped, and
    no file that was copied changed."""
    import subprocess
    import xml.etree.ElementTree as ET

    root = str(tmp_path / "tree")
    skip = shutil.ignore_patterns("__pycache__", "*.pyc")
    for sub in ("chipbench", os.path.join("tests", "chipbench")):
        shutil.copytree(os.path.join(REPO, sub), os.path.join(root, sub),
                        ignore=skip)
    copied = tree_hashes(root)

    name, cell = "wide-published", "wide-published.paced"
    cfg = dict(shipped("temporal-shipped"), name=name, estimator="wide",
               source="a test", reduced={}, d_model=6144, n_heads=48,
               mlp_dim=24576)
    added = {
        "chipbench/estimators/wide.py": WIDE_MODULE,
        f"chipbench/configs/{name}.json": json.dumps(cfg),
        f"tests/chipbench/goldens/{name}.json": json.dumps(WIDE_GOLDENS),
        "chipbench/metrics/wide_roofline.paced.json": json.dumps(
            {"name": "wide_roofline.paced", "reader": "roofline"}),
        "chipbench/metrics/wide_mfu.paced.json": json.dumps(
            {"name": "wide_mfu.paced", "reader": "step_mfu"}),
    }
    for rel, text in added.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), rel  # added, never overwritten
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": name, "source": "a test",
                             "file": f"chipbench/configs/{name}.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "paced", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if "temporal-shipped.paced" in m.get("workloads", []):
            m["workloads"].append(cell)
    for metric, layer, source in (
            ("wide_roofline.paced", "kernels", "device_trace"),
            ("wide_mfu.paced", "whole window path", "program_counter")):
        bench["per_layer"].append({
            "name": metric, "unit": "%", "better": "higher",
            "source": source, "layer": layer,
            "moves": "window_latency_p50_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)

    xml = str(tmp_path / "cases.xml")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=REPO)  # the program itself, beside the copy
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:xdist",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-q",
         "-k", f"{name} or wide_ or test_layers_of_one_name"
               " or test_every_file_under_traffic",
         "--junitxml", xml, "tests/chipbench/test_harness.py",
         "tests/chipbench/test_estimator_seam.py"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    tail = proc.stdout[-4000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail

    outcome = {case.get("name"): [child.tag for child in case] or ["passed"]
               for case in ET.parse(xml).getroot().iter("testcase")}
    want = [f"{test}[{name}{tail}]"
            for test, tails in PER_CONFIGURATION.items() for tail in tails]
    assert {t: outcome.get(t) for t in want} == {t: ["passed"] for t in want}
    assert all(v == ["passed"] for v in outcome.values()), outcome
    assert "test_layers_of_one_name_are_spelled_alike" in outcome
    assert tree_hashes(root).items() >= copied.items()


# -- 3. how much of the final window is compared -------------------------------


def sound_drive(cell: spec.Cell, seed: int = 21):
    """A run's observations with the reference at the configuration's own
    precision in the program's place → (drive, float32 reference)."""
    t = int(cell.config["history_window"])
    fleet = Fleet(cell.config, cell.traffic, seed)
    params = temporal.make_params(seed, cell.config)
    drive = control.stand_in(cell, fleet, Reference(fleet, params, t, "bf16"))
    return drive, Reference(fleet, params, t)


def judged(cell, drive, ref):
    errors = check.compare(drive, ref, {})
    correct, table = check.verdict(errors, cell.config["limits"])
    return correct, table, errors.counts


def scaled(drive, node: int, factor: float = 1.2):
    """``drive`` with every pod of ``node``'s final answer times ``factor``
    where it was published."""
    out = copy.copy(drive)
    out.final = copy.deepcopy(drive.final)
    entry = out.final["nodes"][drive.fleet.names[node]]
    for pod in entry["workloads"]:
        pod["power_uw"] = [x * factor for x in pod["power_uw"]]
    return out


def test_by_default_every_model_node_of_the_final_window_is_compared():
    cell = small_cell(shipped("temporal-shipped"))
    drive, ref = sound_drive(cell)
    fleet = drive.fleet
    assert check.final_model_nodes(fleet) is None
    correct, _table, counts = judged(cell, drive, ref)
    assert correct is True
    assert counts["final_model_nodes_compared"] == 8
    # 3 windows of 6 sampled answers, and the 16 of the final one
    assert counts["answers_compared"] == 3 * 6 + 16
    for node in np.flatnonzero(fleet.mode == 1)[[0, -1]]:
        correct, table, _ = judged(cell, scaled(drive, int(node)), ref)
        assert correct is False
        assert table["model_pod_max_rel"]["value"] > 0.1


def test_a_number_compares_that_many_model_nodes_and_every_ratio_node():
    cell = small_cell(shipped("temporal-shipped"),
                      check={"final_model_nodes": 2, "sampled_nodes": 4})
    drive, ref = sound_drive(cell)
    fleet = drive.fleet
    picked = check.final_model_nodes(fleet)
    assert len(picked) == 2 and all(fleet.mode[i] == 1 for i in picked)
    assert picked == check.final_model_nodes(
        Fleet(cell.config, cell.traffic, 21))  # drawn from the seed
    assert picked != check.final_model_nodes(
        Fleet(cell.config, cell.traffic, 22))
    correct, _table, counts = judged(cell, drive, ref)
    assert correct is True
    assert counts["final_model_nodes_compared"] == 2
    # 3 windows of 4 answers (``sampled_nodes``); 8 ratio nodes and 2
    # model nodes of the final window
    assert counts["answers_compared"] == 3 * 4 + 8 + 2
    # a wrong watt where it is sampled is not correct
    for node in sorted(picked):
        correct, table, _ = judged(cell, scaled(drive, node), ref)
        assert correct is False
        assert table["model_pod_max_rel"]["value"] > 0.1
    # every ratio node is compared still
    for node in np.flatnonzero(fleet.mode == 0):
        correct, table, _ = judged(cell, scaled(drive, int(node)), ref)
        assert correct is False, node
        assert table["ratio_pod_max_rel"]["value"] > 0.1
    # what is not sampled is held to its form alone: the price of a sample
    rest = [int(i) for i in np.flatnonzero(fleet.mode == 1)
            if int(i) not in picked]
    correct, _table, _ = judged(cell, scaled(drive, rest[0]), ref)
    assert correct is True
    # ... and its form is: a pod under another's id, a number that is none
    broken = scaled(drive, rest[0], 1.0)
    pods = broken.final["nodes"][fleet.names[rest[0]]]["workloads"]
    pods[0]["id"], pods[1]["id"] = pods[1]["id"], pods[0]["id"]
    correct, table, _ = judged(cell, broken, ref)
    assert correct is False and table["answers_malformed"]["value"] == 1
    broken = scaled(drive, rest[0], float("nan"))
    correct, table, _ = judged(cell, broken, ref)
    assert correct is False and table["answers_malformed"]["value"] == 1


def test_a_node_missing_from_the_final_window_is_malformed_sampled_or_not():
    cell = small_cell(shipped("temporal-shipped"),
                      check={"final_model_nodes": 2})
    drive, ref = sound_drive(cell)
    fleet = drive.fleet
    picked = check.final_model_nodes(fleet)
    unpicked = next(int(i) for i in np.flatnonzero(fleet.mode == 1)
                    if int(i) not in picked)
    for node in (unpicked, sorted(picked)[0],
                 int(np.flatnonzero(fleet.mode == 0)[0])):
        gone = copy.copy(drive)
        gone.final = {"nodes": {k: v for k, v in drive.final["nodes"].items()
                                if k != fleet.names[node]}}
        correct, table, _ = judged(cell, gone, ref)
        assert correct is False
        assert table["answers_malformed"]["value"] == 1


# -- the whole of a run with a sampled final window, against a CPU child -------


@pytest.fixture(scope="module")
def sampled_root(tmp_path_factory):
    """``test_rehearsal``'s tiny cell with ``check.final_model_nodes`` 2 of
    its 4 model nodes."""
    cfg = shipped("temporal-shipped")
    cfg.update(
        name="tiny", nodes=8, pods_per_node=[4, 4], history_window=4,
        check={"final_model_nodes": 2},
        aggregator_config={
            "tpu": {"workloadBucket": 8, "nodeBucket": 8},
            "aggregator": {"staleAfter": 5.0}})
    return bench_copy(str(tmp_path_factory.mktemp("sampled") / "bench"),
                      [cfg])


def child_env(tmp_path_factory, **more) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **more)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.getbasetemp() / "jax_cache")
    return env


def test_a_run_with_a_sampled_final_window_says_how_many_it_compared(
        sampled_root, tmp_path_factory):
    rc, line = run.run_cell("tiny.flood", 2 ** 31 + 39, 1.0, False,
                            root=sampled_root, platform="cpu",
                            env=child_env(tmp_path_factory))
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["notes"]["final_model_nodes_compared"] == 2
    assert list(line)[-1] == "compared"


def test_half_of_the_batch_left_out_is_still_not_correct_under_a_sample(
        sampled_root, tmp_path_factory):
    """Half of the model nodes are estimated from nothing: of 4, the final
    window's 2 and the sampled windows' 3 cannot all be sound ones."""
    rc, line = run.run_cell(
        "tiny.flood", 2 ** 31 + 40, 1.0, False, root=sampled_root,
        platform="cpu", launcher=os.path.join(HERE, "broken_launch.py"),
        env=child_env(tmp_path_factory, CHIPBENCH_TEST_FAULT="half_left_out",
                      CHIPBENCH_TEST_FAULT_AFTER="0"))
    assert rc == 0 and line["correct"] is False
    assert line["notes"]["final_model_nodes_compared"] == 2
    over = [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    assert any(k.startswith("model_") for k in over), line["compared"]
