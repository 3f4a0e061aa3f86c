"""What PR 35 repaired in the harness, on the CPU and with no aggregator: a
429 is waited out and what was throttled is sent again (``drive.post_round``
against a child made by hand, the clock injected, no sleep), a failed run
names the phase it failed in, the launcher's report keeps the trace's own
start time, and the paced cell's tail metrics read the samples they are
given."""

from __future__ import annotations

import json
import math
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import drive, launch, run, spec, stats  # noqa: E402
from chipbench.child import BenchFailure  # noqa: E402
from chipbench.fleetgen import Fleet  # noqa: E402

SMALL = {"nodes": 16, "pods_per_node": [4, 8], "zones": [
    "package", "core", "dram", "uncore"], "dt_s": 5.0,
    "model_node_share": 0.5}
CALM = {"churn_node_share": 0.0, "churn_pod_share": 0.1, "cpu_sigma": 0.25,
        "zone_sigma": 0.1, "ratio_sigma": 0.05, "interval_s": 0.05,
        "loop": "closed", "warmup_rounds": 1}


class Clock:
    """``time()`` and ``sleep()`` of a clock that only sleeping moves."""

    def __init__(self) -> None:
        self.now, self.slept = 1000.0, []

    def time(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.slept.append(seconds)
        self.now += seconds


class ScriptedChild:
    """Answers ``POST /v1/reports`` from a script, one entry a POST: an
    int is a whole-POST status (429 carries ``retry_after`` 7), a dict
    maps a row's position to its row, every other row is 204; past the
    script's end everything is acknowledged. Keeps every body it got."""

    def __init__(self, script: list) -> None:
        self.script, self.got = list(script), []

    def request(self, method, path, body=None, timeout=0.0):
        from kepler_tpu.fleet.wire import decode_report_batch

        if (method, path) != ("POST", "/v1/reports"):
            return 404, b""
        records = decode_report_batch(body)
        self.got.append(records)
        entry = self.script.pop(0) if self.script else {}
        if isinstance(entry, int):
            return entry, json.dumps({"retry_after": 7}).encode()
        rows = [entry.get(k, {"status": 204}) for k in range(len(records))]
        return 200, json.dumps({"results": rows}).encode()

    def alive(self) -> None:
        pass

    def get_json(self, path):
        raise BenchFailure(f"GET {path} -> 404")


def one_round(script: list, clock: Clock | None = None):
    fleet = Fleet(SMALL, CALM, 3)
    state = fleet.state(0)
    bodies = fleet.batches(state, 0.0)
    assert [len(nodes) for nodes, _body in bodies] == [16]
    child, rnd = ScriptedChild(script), drive.Round(r=0, due=0.0)
    drive.post_round(child, fleet, rnd, bodies, state, clock or Clock())
    return child, rnd


def test_a_throttled_row_is_waited_out_and_the_same_record_sent_again():
    # admission sheds from the sixth record on: it and all after it are 429
    shed = {k: {"status": 429, "retry_after": 0.2} for k in range(5, 16)}
    clock = Clock()
    child, rnd = one_round([shed], clock)
    first, again = child.got
    assert len(first) == 16 and again == first[5:]  # the same bytes
    assert (rnd.acked, rnd.throttled, rnd.keyframes) == (16, 11, 0)
    assert clock.slept == [0.2] and rnd.throttle_wait_s == 0.2
    # the stall stays on the clock: the batch ends with its last resend
    assert rnd.end - rnd.start == pytest.approx(0.2)


def test_a_whole_throttled_post_is_sent_again_and_both_kinds_are_counted():
    # the POST whole, with a hint over the clamp; then two rows with a hint
    # under it and one that is no number; a keyframe asked for meanwhile
    rows = {3: {"status": 429, "retry_after": 0.001},
            4: {"status": 429, "retry_after": None},
            7: {"status": 409, "needs_keyframe": True}}
    clock = Clock()
    child, rnd = one_round([429, rows], clock)
    whole, again, last = child.got
    assert again == whole and len(last) == 3
    assert last[:2] == [whole[3], whole[4]] and last[2] != whole[7]
    assert (rnd.acked, rnd.throttled, rnd.keyframes) == (16, 16 + 2, 1)
    assert clock.slept == [5.0, 0.05]
    assert rnd.throttle_wait_s == pytest.approx(5.05)
    # a sound run counts none
    _child, calm = one_round([])
    assert (calm.throttled, calm.throttle_wait_s, calm.acked) == (0, 0.0, 16)


def test_a_batch_still_throttled_after_thirty_seconds_fails_the_run():
    clock = Clock()
    with pytest.raises(BenchFailure, match=r"still throttled \(429\) after"):
        one_round([429] * 50, clock)
    assert sum(clock.slept) == pytest.approx(35.0)  # 7 waits of 5 s, no more
    assert 30.0 < clock.now - 1000.0 <= 30.0 + drive.THROTTLE_WAIT_S[1]


@pytest.mark.parametrize("script, what", [
    ([503], "POST /v1/reports -> 503"),
    ([{2: {"status": 400, "error": "bad"}}], "report of node-00002"),
    ([{2: {"status": 409, "needs_keyframe": True}}]
     + [{0: {"status": 409, "needs_keyframe": True}}] * 2, "keyframes"),
])
def test_every_other_status_fails_the_run_as_before(script, what):
    with pytest.raises(BenchFailure, match=what):
        one_round(script)


@pytest.mark.parametrize("fail_at, phase", [(0, "fill"), (2, "warmup")])
def test_a_failed_run_names_the_phase_it_failed_in(fail_at, phase):
    fleet = Fleet(SMALL, CALM, 3)
    child = ScriptedChild([{}] * fail_at + [500])
    with pytest.raises(BenchFailure) as caught:
        drive.run_window(child, fleet, CALM, 2, 0.1, False, 0.0)
    assert caught.value.phase == phase
    assert "POST /v1/reports -> 500" in str(caught.value)


def test_the_fail_line_carries_the_phase(monkeypatch, capsys):
    """``run_cell`` prints it: a child that never comes up fails in
    ``ready``, one whose window fails in the phase the drive was in."""
    class Down:
        def __init__(self, *args) -> None:
            pass

        def wait_ready(self, timeout):
            raise BenchFailure("aggregator exited 1: no TPU")

        def kill(self) -> None:
            pass

    def failing(*args):
        err = BenchFailure("no window later than the POST within 60s")
        err.phase = "window"
        raise err

    monkeypatch.setattr(run, "AggregatorChild", Down)
    cell = "temporal-shipped.flood"
    assert run.run_cell(cell, 1, 1.0, False) == (1, None)
    assert "chipbench: FAIL: in ready: aggregator exited 1" in \
        capsys.readouterr().err
    monkeypatch.setattr(Down, "wait_ready", lambda self, timeout: None)
    monkeypatch.setattr(run, "run_window", failing)
    assert run.run_cell(cell, 1, 1.0, False) == (1, None)
    assert "chipbench: FAIL: in window: no window later" in \
        capsys.readouterr().err


# -- the trace's own zero ------------------------------------------------------


def hand_made_planes() -> list:
    def event(name, start, dur):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)

    def line(name, events):
        return SimpleNamespace(name=name, events=events)

    def plane(name, lines=(), stats=()):
        return SimpleNamespace(name=name, lines=list(lines), stats=stats)

    return [
        plane("/host:CPU", [line("python", [event("main", 0, 10)])]),
        plane("/device:TPU:0", [line("XLA Modules", [
            event("jit_temporal_fleet_window(1)", 5_000, 40_000)])]),
        plane("Task Environment", stats=[
            ("profile_start_time", 1_791_001_939_121_495_262),
            ("profile_stop_time", 1_791_001_989_121_495_262),
            ("host_name", "tpu-vm")]),
    ]


def test_the_launchers_report_carries_the_traces_start_and_stop_time():
    report = launch._reduce(hand_made_planes())
    assert report["profile_start_time"] == 1_791_001_939_121_495_262
    assert report["profile_stop_time"] == 1_791_001_989_121_495_262
    assert set(report) == {"planes", "profile_start_time",
                           "profile_stop_time"}
    assert report["planes"] == [{"plane": "/device:TPU:0", "lines": [{
        "line": "XLA Modules",
        "events": [["jit_temporal_fleet_window(1)", 5_000, 40_000]]}]}]
    assert json.loads(json.dumps(report)) == report  # whole ns survive JSON
    # an older JAX writes no such plane: the planes alone, and no zero
    assert launch._reduce(hand_made_planes()[:2]) == {
        "planes": report["planes"]}
    assert launch._device_planes(os.path.join(REPO, "no", "such", "dir")) \
        == {"planes": []}


# -- the paced cell's tail -----------------------------------------------------


def paced_metrics(group: str) -> dict:
    cell = spec.load_cell(REPO, "temporal-shipped.paced")
    return {m["name"]: m for m in cell.metrics(group)}


def test_the_paced_cells_gate_and_its_p90_read_the_hand_made_samples():
    """The samples of ``test_percentile_interpolates_and_carries_infinity``
    through the files the cell's metrics are found by."""
    cell = spec.load_cell(REPO, "temporal-shipped.paced")
    gates = paced_metrics("end_to_end")
    assert "window_latency_p90_ms" not in gates
    assert {"window_latency_p50_ms", "window_latency_p75_ms",
            "setup_s"} == set(gates)
    layer = paced_metrics("per_layer")["latency_p90_ms.paced"]
    assert (layer["layer"], layer["source"], layer["moves"]) == (
        "whole window path", "host_clock", "window_latency_p50_ms")
    assert layer["workloads"] == ["temporal-shipped.paced"]
    ten = SimpleNamespace(latencies_ms=[float(x) for x in range(1, 11)])
    lost = SimpleNamespace(latencies_ms=[1.0, 2.0, 3.0, math.inf])
    none = SimpleNamespace(latencies_ms=[])
    read, args = cell.reader("latency_p90_ms.paced")
    assert read(ten, **args) == pytest.approx(9.1)
    assert read(lost, **args) == math.inf and read(none, **args) is None
    for name in gates:
        if not name.startswith("window_latency_"):
            continue
        read, args = cell.reader(name)
        assert read(none, **args) is None
        assert read(lost, **args) in (2.5, math.inf)
        want = (stats.percentile(ten.latencies_ms, args["q"]) if "q" in args
                else sum(ten.latencies_ms) / 10)
        assert read(ten, **args) == pytest.approx(want)
