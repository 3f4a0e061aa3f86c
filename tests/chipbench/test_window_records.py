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
from chipbench.estimators.temporal import PROGRAM  # noqa: E402
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


# -- the trace laid on the records, on the recorded trace --------------------

ZERO_NS = 1_790_000_000_250_000_000  # the trace's own ``profile_start_time``
OFFSET = ZERO_NS / 1e9  # the wall time of the trace's zero
ZERO = {"profile_start_time": ZERO_NS}
DELAYS = (0.0071, 0.0032)  # launch delay of the two recorded runs
FETCH = 0.002  # a window is fetched this long after its run ended


@pytest.fixture(scope="module")
def planes():
    """The recorded trace (PR 26: the program carried no name yet) with its
    two runs under the name the program has now, which ``program_runs``
    picks them by."""
    with open(os.path.join(HERE, "trace_small.json"), encoding="utf-8") as f:
        planes = json.load(f)["planes"]
    for event in trace.module_events(planes[0]):
        assert event[0].startswith("jit__unknown(")
        event[0] = event[0].replace("jit__unknown", PROGRAM)
    return planes


def planted(planes, fetched_early: float = 0.0) -> dict:
    """A body whose records 3 and 4 dispatched the two recorded runs,
    ``DELAYS`` before each started, and fetched them ``FETCH`` after each
    ended; the records before and after them ran nothing the trace holds.
    Served as the aggregator serves them: boundaries as seconds after the
    stamp."""
    runs = records.program_runs(planes, PROGRAM)
    assert len(runs) == 2
    ran = {3: runs[0], 4: runs[1]}
    begins = {3: OFFSET + runs[0][0] - DELAYS[0] - 0.31,
              4: OFFSET + runs[1][0] - DELAYS[1] - 0.31}
    for seq, back in ((2, 0.9), (1, 1.7), (0, 2.9)):
        begins[seq] = begins[3] - back
    begins[5] = begins[4] + 0.64
    rows = []
    for seq in sorted(begins):
        # tick 0.05 s, assembly 0.30 s, h2d 0.01 s, dispatch 0.002 s; the
        # publisher takes the window at once and fetches it as its run
        # ends (a window whose run the trace lacks: after 0.06 s)
        marks = {"tick": -0.05, "begin": 0.0, "snapshot": 0.001,
                 "batch": 0.05, "assembled": 0.30, "h2d": 0.31,
                 "dispatched": 0.312, "publish_begin": 0.313}
        fetched = 0.31 + 0.06
        if seq in ran:
            fetched = OFFSET + ran[seq][1] + FETCH - begins[seq]
        if seq == 4:
            fetched -= fetched_early
        marks.update(fetched=fetched, scattered=fetched + 0.002,
                     published=fetched + 0.003)
        rows.append([seq, begins[seq], "legacy"]
                    + [marks[m] for m in FIELDS[3:14]]
                    + [0.2, 262144, 46080, 120_000_000, False])
    return {"records": {"fields": FIELDS, "legs": LEGS, "rows": rows}}


def test_aligner_takes_the_offset_from_the_traces_own_zero(planes):
    body = planted(planes)
    recs = records.window_records(body)
    assert [r["seq"] for r in recs] == [0, 1, 2, 3, 4, 5]
    assert recs[3]["h2d"] == pytest.approx(recs[3]["stamp"] + 0.31)
    fit = records.align(body, planes, ZERO, PROGRAM)
    assert fit["offset_s"] == OFFSET  # exact: no estimate is made
    assert (fit["checked"], fit["contradicted"]) == (2, 0)
    assert fit["launch_delay_s"] == pytest.approx(min(DELAYS), abs=1e-6)
    assert fit["fetch_margin_s"] == pytest.approx(FETCH, abs=1e-6)
    # the runs are picked by the program's name, not by how long they took:
    # a helper as long as the program is no run of it
    helper = json.loads(json.dumps(planes))
    events = trace.module_events(helper[0])
    events.append(["jit_convert_element_type(7)", events[0][1] + 400_000_000,
                   events[0][2]])
    assert records.program_runs(helper, PROGRAM) == \
        records.program_runs(planes, PROGRAM)


def test_aligner_counts_what_contradicts_the_zero_and_then_nothing_prints(
        planes):
    def run_with_zero(body, launch):
        return SimpleNamespace(
            drive=SimpleNamespace(debug={"first": {}, "last": body}),
            planes=planes, launch=launch, program=PROGRAM)

    body = planted(planes)
    assert idle_by_leg.read(run_with_zero(body, ZERO), legs=TICK) is not None
    # a window fetched before its program's run ended
    early = planted(planes, fetched_early=0.03)
    fit = records.align(early, planes, ZERO, PROGRAM)
    assert (fit["checked"], fit["contradicted"]) == (2, 1)
    assert records.idle_by_leg(early, planes, ZERO, {"tick": TICK},
                               PROGRAM) is None
    assert idle_by_leg.read(run_with_zero(early, ZERO), legs=TICK) is None
    # a zero 50 ms late: both runs end after their windows were fetched
    late = {"profile_start_time": ZERO_NS + 50_000_000}
    assert records.align(body, planes, late, PROGRAM)["contradicted"] == 2
    assert idle_by_leg.read(run_with_zero(body, late), legs=TICK) is None
    # a zero 0.5 s early lays each run on a window that never ran it
    soon = {"profile_start_time": ZERO_NS - 500_000_000}
    assert records.align(body, planes, soon, PROGRAM)["contradicted"] == 2
    # a zero so early that no record had begun: nothing to hold it against
    never = {"profile_start_time": ZERO_NS - 10_000_000_000}
    assert records.align(body, planes, never, PROGRAM) is None
    # no start time (an older JAX), no record, no table of legs, no trace,
    # no run that carries the program's name
    assert records.align(body, planes, {}, PROGRAM) is None
    assert records.align(body, planes, {"marks": {"start": OFFSET}},
                         PROGRAM) is None
    assert idle_by_leg.read(run_with_zero(body, {}), legs=TICK) is None
    none = {"records": {**body["records"], "rows": []}}
    assert records.align(none, planes, ZERO, PROGRAM) is None
    bare = {"records": {**body["records"], "legs": {}}}
    assert records.align(bare, planes, ZERO, PROGRAM) is None
    assert records.align(body, [], ZERO, PROGRAM) is None
    with open(os.path.join(HERE, "trace_small.json"), encoding="utf-8") as f:
        unnamed = json.load(f)["planes"]
    assert records.program_runs(unnamed, PROGRAM) == []
    assert records.align(body, unnamed, ZERO, PROGRAM) is None


def windows_by_hand(landing: dict | None = None, stamp_off: dict | None = None,
                  windows: int = 40) -> tuple[dict, list]:
    """``windows`` windows 0.15 s apart, as flood's shortest cycle is: the
    dispatch begins 0.09 s after the stamp, the put lands 0.075 s later
    (``landing``: window → its own delay), the program runs 0.05 s and is
    fetched 2 ms after its end; ``stamp_off`` shifts one record's wall
    times as a stamp read late would. → (body, planes) on ``OFFSET``."""
    rows, events, free = [], [], 0.0
    for seq in range(windows):
        begin = 1.0 + 0.15 * seq
        start = max(begin + 0.09 + (landing or {}).get(seq, 0.075), free)
        free = start + 0.05
        marks = {"tick": -0.05, "begin": 0.0, "snapshot": 0.001,
                 "batch": 0.01, "assembled": 0.08, "h2d": 0.09,
                 "dispatched": 0.092, "publish_begin": 0.1,
                 "fetched": free + FETCH - begin}
        marks.update(scattered=marks["fetched"] + 0.002,
                     published=marks["fetched"] + 0.003)
        rows.append([seq, OFFSET + begin + (stamp_off or {}).get(seq, 0.0),
                     "legacy"] + [marks[m] for m in FIELDS[3:14]]
                    + [0.07, 262144, 46080, 120_000_000, False])
        events.append([f"{PROGRAM}({seq})", int(start * 1e9),
                       50_000_000])
    planes = [{"plane": "/device:TPU:0",
               "lines": [{"line": "XLA Modules", "events": events}]}]
    return {"records": {"fields": FIELDS, "legs": LEGS, "rows": rows}}, planes


def test_a_put_that_lands_late_is_its_own_windows_run_all_the_same():
    # PR 35's refusal: one put of a flood run's 208 landed after the NEXT
    # window's dispatch had begun; its run was laid on that window, which
    # then held two, and the idle metrics printed nothing
    body, planes = windows_by_hand()
    fit = records.align(body, planes, ZERO, PROGRAM)
    assert (fit["checked"], fit["contradicted"]) == (40, 0)
    assert fit["launch_delay_s"] == pytest.approx(0.075, abs=1e-6)
    body, planes = windows_by_hand(landing={17: 0.19, 30: 0.40})
    runs = records.program_runs(planes, PROGRAM)
    began = [r["h2d"] for r in records.window_records(body)]
    assert runs[17][0] + OFFSET > began[18]  # after the next one's dispatch
    assert runs[30][0] + OFFSET > began[32]  # and after the one after it
    fit = records.align(body, planes, ZERO, PROGRAM)
    assert (fit["checked"], fit["contradicted"]) == (40, 0)
    assert fit["fetch_margin_s"] == pytest.approx(FETCH, abs=1e-6)
    assert fit["against"] == []
    assert records.idle_by_leg(body, planes, ZERO, {"tick": TICK},
                               PROGRAM) is not None


def test_one_record_in_forty_is_no_case_against_the_zero_but_three_are():
    # a record whose stamp was read 0.2 s late: its dispatch seems to begin
    # after its run started, so the run fits no window; the zero is one
    # number for all forty runs, and the other thirty-nine agree with it
    body, planes = windows_by_hand(stamp_off={11: 0.2})
    fit = records.align(body, planes, ZERO, PROGRAM)
    assert (fit["checked"], fit["contradicted"]) == (40, 1)
    # the run is held against the next window it could be, 12, and started
    # before that one's dispatch began and ended before it was fetched
    window, after_dispatch, after_fetched = fit["against"][0]
    assert window == 12
    assert after_dispatch == pytest.approx(0.075 - 0.15, abs=1e-5)
    assert after_fetched == pytest.approx(-FETCH - 0.15, abs=1e-5)
    sound = records.idle_by_leg(*windows_by_hand(), ZERO, {"tick": TICK},
                                PROGRAM)
    found = records.idle_by_leg(body, planes, ZERO, {"tick": TICK}, PROGRAM)
    assert found["in_s"]["tick"] == pytest.approx(sound["in_s"]["tick"],
                                                  rel=0.05)
    body, planes = windows_by_hand(stamp_off={5: 0.2, 17: 0.2, 29: 0.2})
    assert records.align(body, planes, ZERO, PROGRAM)["contradicted"] == 3
    assert records.idle_by_leg(body, planes, ZERO, {"tick": TICK},
                               PROGRAM) is None
    # and a zero that is wrong is contradicted by every run
    late = {"profile_start_time": ZERO_NS + 50_000_000}
    body, planes = windows_by_hand()
    assert records.align(body, planes, late, PROGRAM)["contradicted"] == 40
    assert records.idle_by_leg(body, planes, late, {"tick": TICK},
                               PROGRAM) is None


def test_idle_shares_and_the_rest_sum_to_the_whole_idle_time(planes):
    found = records.idle_by_leg(planted(planes), planes, ZERO,
                                {"assembly": ASSEMBLY, "tick": TICK}, PROGRAM)
    inside = found["in_s"]
    assert found["idle_s"] > 0 and found["offset_s"] == OFFSET
    assert inside["assembly"] + inside["tick"] + found["rest_s"] == \
        pytest.approx(found["idle_s"], rel=1e-9)
    # between the two runs the device idles 0.736 s; record 4's tick wait
    # (0.05 s) and its assembly and h2d (0.31 s) lie inside that gap, whole
    busy = trace.busy_seconds(planes)
    window = (records.program_runs(planes, PROGRAM)[1][1]
              - records.program_runs(planes, PROGRAM)[0][0])
    assert found["idle_s"] == pytest.approx(window - busy, rel=0.02)
    assert inside["tick"] == pytest.approx(0.05, abs=1e-6)
    assert inside["assembly"] == pytest.approx(0.31, abs=1e-6)
    run = SimpleNamespace(
        drive=SimpleNamespace(debug={"first": {}, "last": planted(planes)}),
        planes=planes, launch=ZERO, program=PROGRAM)
    both = (idle_by_leg.read(run, legs=ASSEMBLY)
            + idle_by_leg.read(run, legs=TICK))
    assert both == pytest.approx(
        100.0 * (inside["assembly"] + inside["tick"]) / found["idle_s"])
    assert idle_by_leg.read(run, legs=["window.d2h"]) is None  # no such leg


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
