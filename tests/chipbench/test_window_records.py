"""The readers of the aggregator's window records, on hand-made bodies and
on the recorded trace with records planted at a known offset. No chip and
no aggregator: arithmetic only."""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import records, trace  # noqa: E402
from chipbench.readers import count_ratio, idle_by_leg, leg_median  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


FIELDS = ["seq", "stamp", "kind", "tick", "begin", "snapshot", "batch",
          "assembled", "h2d", "dispatched", "publish_begin", "fetched",
          "scattered", "published", "assembly_cpu_s", "rows_program",
          "rows_work", "h2d_bytes", "compiled"]
# span name → the two marks the leg lies on, as the body serves them
LEGS = {"window.tick_wait": ["tick", "begin"],
        "window.snapshot": ["begin", "snapshot"],
        "window.batch": ["snapshot", "batch"],
        "window.history": ["batch", "assembled"],
        "window.h2d": ["assembled", "h2d"],
        "window.dispatch": ["h2d", "dispatched"],
        "window.queued": ["dispatched", "publish_begin"],
        "window.pipeline_wait": ["publish_begin", "fetched"],
        "window.scatter": ["fetched", "scattered"],
        "window.publish": ["scattered", "published"]}
ASSEMBLY = ["window.snapshot", "window.batch", "window.history",
            "window.h2d"]
TICK = ["window.tick_wait"]


def row(seq: int, stamp: float, history_s: float, cpu_s: float) -> list:
    """A served record: boundaries as seconds after the stamp. Snapshot
    1 ms, batch 9 ms, then the history; h2d 20 ms, dispatch 2 ms; queued
    1 s; published 5 ms after its fetch began."""
    t = 0.010 + history_s
    marks = [-1.0, 0.0, 0.001, 0.010, t, t + 0.020, t + 0.022, t + 1.022,
             t + 1.023, t + 1.025, t + 1.027]
    return [seq, stamp, "legacy"] + marks + [
        cpu_s, 262144, 46080, 120_000_000, False]


def body(windows: int, reports: int, rows: list | None = None) -> dict:
    """A /debug/window body after ``windows`` windows."""
    return {
        "stats": {},
        "records": {"fields": FIELDS, "legs": LEGS, "rows": rows or []},
        "counts": {"windows": windows, "rows_program": windows * 262144,
                 "rows_work": windows * 46080,
                 "h2d_bytes": windows * 120_000_000},
        "ingest": {"reports": reports, "decode_s": reports * 50e-6,
                   "lock_wait_s": reports * 5e-6},
    }


def run_with(first: dict | None, last: dict | None,
             stamps: tuple = ()) -> SimpleNamespace:
    return SimpleNamespace(
        drive=SimpleNamespace(debug={"first": first, "last": last}),
        windows_in=[SimpleNamespace(stamp=s) for s in stamps],
        planes=[], launch={})


def test_leg_median_reads_the_measured_windows_own_records():
    # five records; the client saw 101-103 inside its window (100 is
    # warm-up's, 104 came after the close)
    rows = [row(k, 100.0 + k, history_s, cpu_s) for k, (history_s, cpu_s)
            in enumerate([(9.0, 9.0), (0.5, 0.3), (0.7, 0.4), (0.6, 0.45),
                          (9.0, 9.0)])]
    run = run_with(body(10, 1000), body(15, 6120, rows),
                   stamps=(101.0, 102.0, 103.0))
    assert leg_median.read(run, legs=["window.history"]) == \
        pytest.approx(600.0)
    assert leg_median.read(
        run, legs=["window.snapshot", "window.batch"]) == pytest.approx(10.0)
    assert leg_median.read(run, legs=["window.queued"]) == \
        pytest.approx(1000.0)
    assert leg_median.read(run, legs=["window.tick_wait"]) == \
        pytest.approx(1000.0)
    # wall over snapshot + batch + history less the thread's CPU time:
    # 210, 310 and 160 ms
    assert leg_median.read(run, cpu_gap=True) == pytest.approx(210.0)
    # a leg whose marks a record lacks (the packed path has no h2d mark)
    for r in rows:
        r[FIELDS.index("h2d")] = None
    assert leg_median.read(run, legs=["window.h2d"]) is None
    assert leg_median.read(run, legs=["window.history"]) == \
        pytest.approx(600.0)
    # a leg the program's table does not name
    assert leg_median.read(run, legs=["window.d2h"]) is None


def test_leg_median_says_nothing_when_a_measured_window_has_no_record():
    """The body keeps the last 256 records: a faster program's run may
    publish more, and a median over the tail alone would pass for the
    run's."""
    rows = [row(k, 100.0 + k, 0.5, 0.3) for k in range(1, 4)]
    last = body(15, 6120, rows)
    whole = run_with(body(10, 1000), last, stamps=(101.0, 102.0, 103.0))
    assert records.joined(whole) is not None
    assert leg_median.read(whole, legs=["window.history"]) == \
        pytest.approx(500.0)
    cut = run_with(body(10, 1000), last, stamps=(100.0, 101.0, 102.0, 103.0))
    assert records.joined(cut) is None
    assert leg_median.read(cut, legs=["window.history"]) is None
    assert leg_median.read(cut, cpu_gap=True) is None


def test_count_ratio_takes_last_minus_first():
    run = run_with(body(10, 1000), body(35, 26600))
    assert count_ratio.read(
        run, num=["counts", "rows_work"], den=["counts", "rows_program"],
        scale=100.0) == pytest.approx(100.0 * 46080 / 262144)
    assert count_ratio.read(
        run, num=["counts", "h2d_bytes"], den=["counts", "windows"],
        scale=1e-6) == pytest.approx(120.0)
    assert count_ratio.read(
        run, num=["ingest", "decode_s"], den=["ingest", "reports"],
        scale=1e6) == pytest.approx(50.0)
    # only what lies between the two reads counts
    last = body(35, 26600)
    last["ingest"]["decode_s"] = 1000 * 50e-6 + 25600 * 300e-6
    assert count_ratio.read(
        run_with(body(10, 1000), last), num=["ingest", "decode_s"],
        den=["ingest", "reports"], scale=1e6) == pytest.approx(300.0)


@pytest.mark.parametrize("first, last", [
    (None, None),  # the run kept no body
    ({"stats": {}}, {"stats": {}}),  # an older program: no such keys
    (body(5, 100), body(5, 100)),  # no window and no report in between
])
def test_readers_return_nothing_not_zero(first, last):
    run = run_with(first, last, stamps=(101.0,))
    assert leg_median.read(run, legs=["window.history"]) is None
    assert leg_median.read(run, cpu_gap=True) is None
    assert count_ratio.read(run, num=["counts", "rows_work"],
                            den=["counts", "rows_program"]) is None
    assert count_ratio.read(run, num=["ingest", "decode_s"],
                            den=["ingest", "reports"], scale=1e6) is None
    assert idle_by_leg.read(run, legs=ASSEMBLY) is None
    assert records.window_records(last) == []


# -- the aligner, on the recorded trace -------------------------------------

OFFSET = 1_790_000_000.25  # the wall time of the trace's zero
DELAYS = (0.0071, 0.0032)  # launch delay of the two recorded runs


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "trace_small.json"), encoding="utf-8") as f:
        return json.load(f)["planes"]


def planted(planes, fetched_early: float = 0.0) -> dict:
    """A body whose records 3 and 4 dispatched the two recorded runs,
    ``DELAYS`` before each started; the records before and after them are
    spaced otherwise, so only one shift fits. Served as the aggregator
    serves them: boundaries as seconds after the stamp."""
    runs = records.program_runs(planes)
    assert len(runs) == 2
    begins = {3: OFFSET + runs[0][0] - DELAYS[0] - 0.31,
              4: OFFSET + runs[1][0] - DELAYS[1] - 0.31}
    for seq, back in ((2, 0.9), (1, 1.7), (0, 2.9)):
        begins[seq] = begins[3] - back
    begins[5] = begins[4] + 0.64
    rows = []
    for seq in sorted(begins):
        # tick 0.05 s, assembly 0.30 s, h2d 0.01 s, dispatch 0.002 s; the
        # window is published with the next one's dispatch (depth 2)
        marks = {"tick": -0.05, "begin": 0.0, "snapshot": 0.001,
                 "batch": 0.05, "assembled": 0.30, "h2d": 0.31,
                 "dispatched": 0.312}
        nxt = begins.get(seq + 1, begins[seq] + 0.7) - begins[seq]
        marks.update(publish_begin=nxt + 0.312, fetched=nxt + 0.313,
                     scattered=nxt + 0.315, published=nxt + 0.316)
        if seq == 4:
            marks["fetched"] -= fetched_early
        rows.append([seq, begins[seq], "legacy"]
                    + [marks[m] for m in FIELDS[3:14]]
                    + [0.2, 262144, 46080, 120_000_000, False])
    return {"records": {"fields": FIELDS, "legs": LEGS, "rows": rows}}


def test_aligner_recovers_the_offset_to_within_the_least_launch_delay(
        planes):
    body = planted(planes)
    recs = records.window_records(body)
    assert [r["seq"] for r in recs] == [0, 1, 2, 3, 4, 5]
    assert recs[3]["h2d"] == pytest.approx(recs[3]["stamp"] + 0.31)
    fit = records.align(body, planes, {"start": OFFSET + 0.2})
    assert [rec["seq"] for rec, _run in fit["pairs"]] == [3, 4]
    assert 0.0 <= OFFSET - fit["offset_s"] <= min(DELAYS) + 1e-6
    assert fit["launch_delay_s"] == pytest.approx(
        [0.0, max(DELAYS) - min(DELAYS)], abs=1e-6)


def test_aligner_returns_nothing_when_a_record_contradicts_a_bound(planes):
    # the trace's zero after the launcher's mark
    body = planted(planes)
    assert records.align(body, planes, {"start": OFFSET - 0.5}) is None
    # a window fetched before its program's run ended
    early = planted(planes, fetched_early=0.9)
    assert records.align(early, planes, {"start": OFFSET + 0.2}) is None
    # no launcher mark, no record, no table of legs, no trace
    assert records.align(body, planes, {}) is None
    none = {"records": {**body["records"], "rows": []}}
    assert records.align(none, planes, {"start": OFFSET + 0.2}) is None
    bare = {"records": {**body["records"], "legs": {}}}
    assert records.align(bare, planes, {"start": OFFSET + 0.2}) is None
    assert records.align(body, [], {"start": OFFSET + 0.2}) is None


def test_idle_shares_and_the_rest_sum_to_the_whole_idle_time(planes):
    marks = {"start": OFFSET + 0.2}
    found = records.idle_by_leg(planted(planes), planes, marks,
                                {"assembly": ASSEMBLY, "tick": TICK})
    inside = found["in_s"]
    assert found["idle_s"] > 0
    assert inside["assembly"] + inside["tick"] + found["rest_s"] == \
        pytest.approx(found["idle_s"], rel=1e-9)
    # between the two runs the device idles 0.736 s; record 4's tick wait
    # (0.05 s) and its assembly and h2d (0.31 s, less the launch delay's
    # share that the estimate cannot see) lie inside that gap
    busy = trace.busy_seconds(planes)
    window = (records.program_runs(planes)[1][1]
              - records.program_runs(planes)[0][0])
    assert found["idle_s"] == pytest.approx(window - busy, rel=0.02)
    assert inside["tick"] == pytest.approx(0.05, abs=1e-3)
    assert inside["assembly"] == pytest.approx(0.31, abs=5e-3)
    run = SimpleNamespace(
        drive=SimpleNamespace(debug={"first": {}, "last": planted(planes)}),
        planes=planes, launch={"marks": marks})
    both = (idle_by_leg.read(run, legs=ASSEMBLY)
            + idle_by_leg.read(run, legs=TICK))
    assert both == pytest.approx(
        100.0 * (inside["assembly"] + inside["tick"]) / found["idle_s"])
    assert idle_by_leg.read(run, legs=["window.d2h"]) is None  # no such leg
    run.launch = {"marks": {"start": OFFSET - 0.5}}
    assert idle_by_leg.read(run, legs=TICK) is None


def test_the_readers_know_the_records_as_the_aggregator_serves_them():
    """The readers keep no copy of the table of legs: this file's is the
    one the hand-made bodies serve, and the metrics' legs are in it."""
    import glob

    from kepler_tpu.fleet import window_record

    assert LEGS == {k: list(v) for k, v in window_record.LEGS.items()}
    assert FIELDS == list(window_record.FIELDS)
    assert not hasattr(records, "LEGS")
    named = set()
    for path in glob.glob(os.path.join(REPO, "chipbench", "metrics",
                                       "*.json")):
        with open(path, encoding="utf-8") as f:
            legs = json.load(f).get("args", {}).get("legs") or []
        named.update(legs)
    assert named and named <= set(LEGS)
