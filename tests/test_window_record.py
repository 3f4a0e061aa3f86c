"""One record per fleet window (ISSUE 27): the served temporal window path
at ``pipelineDepth`` 2 on the CPU — every published window has exactly one
record, its marks are ordered and are the one clock of the gauges and of
the legs' spans, the sums on ``/debug/window`` only grow and add up, and
the device side carries names.
Counts and order only: no time a CPU run yields is asserted as a speed."""

from __future__ import annotations

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from kepler_tpu import telemetry
from kepler_tpu.fleet.aggregator import Aggregator
from kepler_tpu.fleet.window_record import (COUNTS, FIELDS, LEGS, MARKS,
                                            RECORDS_KEPT, ROW_COUNTS,
                                            SUM_COUNTS, WindowLedger,
                                            WindowRecord, records_json)
from kepler_tpu.fleet.wire import encode_report
from kepler_tpu.parallel.fleet import MODE_MODEL, MODE_RATIO, NodeReport
from kepler_tpu.server.http import APIServer
from kepler_tpu.service.lifecycle import CancelContext
from kepler_tpu.telemetry.spans import SpanRecorder

ZONES = ["package", "dram"]
WINDOWS = 5
GAUGES = {"last_assembly_ms": ("begin", "assembled"),
          "last_dispatch_ms": ("assembled", "dispatched"),
          "last_wait_ms": ("publish_begin", "fetched"),
          "last_scatter_ms": ("fetched", "scattered")}


def report(name: str, seed: int, mode: int, w: int = 3) -> NodeReport:
    rng = np.random.default_rng(seed)
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    return NodeReport(
        node_name=name,
        zone_deltas_uj=rng.uniform(1e7, 5e8, 2).astype(np.float32),
        zone_valid=np.ones(2, bool), usage_ratio=0.6, cpu_deltas=cpu,
        workload_ids=[f"{name}-w{k}" for k in range(w)],
        node_cpu_delta=float(cpu.sum()), dt_s=5.0, mode=mode,
        workload_kinds=np.ones(w, np.int8))


class Served:
    """A temporal aggregator behind a real HTTP server, driven by hand:
    reports in over POST, one ``aggregate_once`` a window."""

    def __init__(self, **agg_kw) -> None:
        self.server = APIServer(listen_addresses=["127.0.0.1:0"])
        self.server.init()
        self.ctx = CancelContext()
        threading.Thread(target=self.server.run, args=(self.ctx,),
                         daemon=True).start()
        time.sleep(0.05)
        self.agg = Aggregator(self.server, model_mode="temporal",
                              node_bucket=8, workload_bucket=16,
                              history_window=4, pipeline_depth=2, **agg_kw)
        self.agg.init()
        self.seq = 0

    def url(self, path: str) -> str:
        host, port = self.server.addresses[0]
        return f"http://{host}:{port}{path}"

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(self.url(path), timeout=10) as r:
            return json.loads(r.read())

    def window(self):
        """Both nodes report, then one tick of the loop."""
        self.seq += 1
        for name, mode in (("node-r", MODE_RATIO), ("node-m", MODE_MODEL)):
            req = urllib.request.Request(
                self.url("/v1/report"), method="POST",
                data=encode_report(report(name, self.seq, mode), ZONES,
                                   seq=self.seq, run="r1"))
            assert urllib.request.urlopen(req, timeout=10).status == 204
        return self.agg.aggregate_once()

    def close(self) -> None:
        self.agg.shutdown()
        self.ctx.cancel()
        self.server.shutdown()


def rows_of(body: dict) -> list[dict]:
    table = body["records"]
    assert table["fields"] == list(FIELDS)
    assert table["legs"] == {k: list(v) for k, v in LEGS.items()}
    return [dict(zip(table["fields"], row)) for row in table["rows"]]


@pytest.fixture(scope="module", params=[True, False],
                ids=["telemetry_on", "telemetry_off"])
def served(request):
    """Five windows, the body read after each, with the span recorder
    enabled and disabled: the records and gauges do not depend on it."""
    rec = SpanRecorder(enabled=request.param)
    with telemetry.installed(rec):
        s = Served()
        bodies, published = [s.get("/debug/window")], []
        for _ in range(WINDOWS):
            published.append(s.window())
            bodies.append(s.get("/debug/window"))
        published.append(s.agg.windows.drain())
        bodies.append(s.get("/debug/window"))
        yield s, rec, bodies, published
        s.close()


def test_every_published_window_has_exactly_one_record(served):
    _s, _rec, bodies, published = served
    # depth 2: the first call publishes nothing, the drain the last window
    assert published[0] is None
    stamps = [r.timestamp for r in published[1:]]
    assert len(stamps) == WINDOWS and len(set(stamps)) == WINDOWS
    rows = rows_of(bodies[-1])
    assert [r["seq"] for r in rows] == list(range(WINDOWS))
    assert [r["stamp"] for r in rows] == stamps
    assert all(r["kind"] == "legacy" for r in rows)
    # a body holds the records of the windows published before it
    assert [len(rows_of(b)) for b in bodies] == [0, 0, 1, 2, 3, 4, 5]


def test_boundaries_are_ordered_and_legs_do_not_overlap(served):
    _s, _rec, bodies, _published = served
    for row in rows_of(bodies[-1]):
        marks = [row[m] for m in MARKS if m != "tick"]
        assert None not in marks
        assert marks == sorted(marks) and row["begin"] == 0.0
        assert row["tick"] is None  # aggregate_once by hand: no loop wait
        # the legs tile begin → published: each ends where the next starts
        ends = [b for _a, b in LEGS.values()]
        assert [a for a, _b in LEGS.values()][1:] == ends[:-1]
        assert 0.0 <= row["assembly_cpu_s"]


def test_a_window_is_published_after_the_next_one_is_dispatched(served):
    """The pipeline's shape: record k's publication ends after record
    k+1's dispatch does, on the wall clock both are laid on."""
    _s, _rec, bodies, _published = served
    rows = rows_of(bodies[-1])
    for a, b in zip(rows, rows[1:]):
        assert a["stamp"] + a["published"] > b["stamp"] + b["dispatched"]
        assert a["stamp"] + a["publish_begin"] >= \
            b["stamp"] + b["dispatched"]


def test_the_gauges_are_the_records_differences(served):
    _s, _rec, bodies, _published = served
    for body in bodies[2:]:
        row, stats = rows_of(body)[-1], body["stats"]
        total = 0.0
        for gauge, (a, b) in GAUGES.items():
            assert stats[gauge] == pytest.approx((row[b] - row[a]) * 1e3,
                                                 abs=1e-3)
            total += stats[gauge]
        assert stats["last_attribution_ms"] == pytest.approx(total)
        assert stats["last_fetch_ms"] == 0.0


def test_counts_and_ingest_only_grow_and_add_up_over_the_records(served):
    _s, rec, bodies, _published = served
    for first, last in zip(bodies, bodies[1:]):
        for key in ("counts", "ingest"):
            assert all(last[key][k] >= v for k, v in first[key].items())
    first, last = bodies[2], bodies[-1]  # one record before, all after
    between = rows_of(last)[len(rows_of(first)):]
    assert len(between) == WINDOWS - 1
    assert set(last["counts"]) == {"windows", *COUNTS}
    grew = {k: last["counts"][k] - first["counts"][k] for k in COUNTS}
    for key in ("windows", *ROW_COUNTS):
        assert last["counts"][key] - first["counts"][key] == sum(
            1 if key == "windows" else r[key] for r in between)
    # what the put over the mesh counts is in the sums alone: every window
    # ran over the mesh's devices, and the device that was sent most was
    # sent its share of the window's bytes (the node bucket divides evenly)
    import jax
    n_dev = len(jax.devices())
    assert SUM_COUNTS == ("devices", "h2d_bytes_max_device",
                          "published_early", "hist_rows_sent", "rows_fused")
    assert not set(SUM_COUNTS) & set(between[0])
    # called directly at depth 2, a window is published by the next call:
    # only the drained one was published before a later one was snapshotted
    assert grew["published_early"] == 1
    assert grew["devices"] == n_dev * len(between)
    assert grew["h2d_bytes_max_device"] * n_dev == grew["h2d_bytes"]
    # the history goes up whole here, where a device's 16 rows are under
    # the smallest bucket of rows (tests/test_sharded_put.py: compact)
    assert grew["hist_rows_sent"] == 8 * 16 * len(between)
    assert grew["rows_fused"] == 0  # the XLA trunk: the CPU's
    row = between[0]
    assert row["rows_program"] == 8 * 16  # node bucket × workload bucket
    assert row["rows_work"] == 3  # node-m's pods
    # feat_hist f32 [8,16,4,7], t_valid [8,16,4] and the batch's arrays
    assert row["h2d_bytes"] > 8 * 16 * 4 * 7 * 4
    # the ingest sums keep the decode and merge spans company: with the
    # recorder off no report is timed
    legs = ("decode_s", "lock_wait_s", "merge_s", "history_push_s")
    if rec.enabled:
        assert last["ingest"]["reports"] == 2 * WINDOWS
        assert all(last["ingest"][k] > 0.0 for k in legs)
    else:
        assert last["ingest"] == {"reports": 0, **dict.fromkeys(legs, 0.0)}


def test_the_first_temporal_window_counts_its_compile(served):
    _s, _rec, bodies, _published = served
    assert [r["compiled"] for r in rows_of(bodies[-1])] == \
        [True] + [False] * (WINDOWS - 1)
    assert bodies[1]["stats"]["window_compiles_total"] == 0  # unpublished
    assert all(b["stats"]["window_compiles_total"] == 1 for b in bodies[2:])


def test_spans_carry_the_window_id_only_when_telemetry_is_on(served):
    _s, rec, _bodies, _published = served
    events = [e for t in rec.recent_traces() for e in t.events
              if e.name.startswith(("window.", "aggregator.window"))]
    if not rec.enabled:
        assert events == []
        return
    by_window: dict[int, set] = {}
    for e in events:
        assert e.window is not None, e.name
        by_window.setdefault(e.window, set()).add(e.name)
    legs = set(LEGS) - {"window.tick_wait"}
    assert by_window[0] == legs | {"aggregator.window", "window.compile"}
    for seq in range(1, WINDOWS):
        assert by_window[seq] == legs | {"aggregator.window"}
    # window 1 is dispatched in the cycle that publishes window 0
    cycles = [t for t in rec.recent_traces() if t.name == "aggregator.window"]
    second = {(e.name, e.window) for e in cycles[1].events}
    assert {("window.dispatch", 1), ("window.publish", 0),
            ("window.queued", 0)} <= second
    # an aggregator.window cycle covers its snapshot: it opens on the
    # record's first mark
    for t in cycles:
        # (window.queued alone began earlier: in the cycle before)
        first = min((e for e in t.events if e.name != "window.queued"),
                    key=lambda e: (e.rel_start_s, e.depth))
        assert (first.name, first.rel_start_s) == ("aggregator.window", 0.0)
        snapshot = next(e for e in t.events if e.name == "window.snapshot")
        assert (snapshot.rel_start_s, snapshot.depth) == (0.0, 1)
    depth = {e.name: e.depth for e in cycles[0].events}
    assert depth["window.compile"] == depth["window.dispatch"] + 1


def test_a_legs_span_lies_on_the_records_two_marks(served):
    """One clock: the stage histograms and ``/debug/traces`` read for a leg
    what the served record reads (a row is rounded to a tenth of a µs)."""
    _s, rec, bodies, _published = served
    if not rec.enabled:
        return
    rows = {r["seq"]: r for r in rows_of(bodies[-1])}
    seen = 0
    for trace in rec.recent_traces():
        for e in trace.events:
            if e.name in LEGS:
                a, b = LEGS[e.name]
                row = rows[e.window]
                assert e.duration_s == pytest.approx(row[b] - row[a],
                                                     abs=2e-7), e.name
                seen += 1
    assert seen == WINDOWS * (len(LEGS) - 1)  # by hand: no tick_wait


def test_an_empty_fleet_opens_no_cycle_and_takes_no_sequence_number():
    rec = SpanRecorder(enabled=True)
    with telemetry.installed(rec):
        agg = Aggregator(APIServer(), model_mode="temporal")
        assert agg.aggregate_once() is None
        assert agg.aggregate_once() is None
    assert rec.recent_traces() == [] and agg.windows._window_seq == 0
    assert json.loads(agg._handle_window_debug(None)[2])["records"] == {
        "fields": list(FIELDS), "rows": [],
        "legs": {k: list(v) for k, v in LEGS.items()}}


def test_the_run_loop_times_its_wait_as_the_next_windows_tick_leg():
    s = Served()
    try:
        s.agg._interval = 0.05
        s.window()  # a report is stored; this window is seq 0
        ctx = CancelContext()
        loop = threading.Thread(target=s.agg.run, args=(ctx,), daemon=True)
        loop.start()
        deadline = time.monotonic() + 20
        while s.agg.windows._window_seq < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        ctx.cancel()
        loop.join(timeout=20)
        assert not loop.is_alive()
        rows = rows_of(s.get("/debug/window"))
        assert len(rows) >= 3 and rows[0]["tick"] is None
        for row in rows[1:]:
            assert -1.0 < row["tick"] <= -0.05  # it waited the interval
    finally:
        s.close()


def test_the_ledger_keeps_the_last_records_kept_and_every_count():
    ledger = WindowLedger()
    for seq in range(RECORDS_KEPT + 44):
        rec = WindowRecord(seq, 1000.0 + seq, begin=float(seq))
        rec.snapshot, rec.batch, rec.assembled = seq + 0.1, seq + 0.2, seq + 0.5
        rec.rows_program = rec.rows_fused = 128
        ledger.add(rec)
    kept, counts = ledger.snapshot()
    assert len(kept) == RECORDS_KEPT and kept[0].seq == 44
    assert counts == {"windows": RECORDS_KEPT + 44, "rows_work": 0,
                      "rows_program": 128 * (RECORDS_KEPT + 44),
                      "h2d_bytes": 0, "devices": 0,
                      "h2d_bytes_max_device": 0, "published_early": 0,
                      "hist_rows_sent": 0,
                      "rows_fused": 128 * (RECORDS_KEPT + 44)}
    table = json.loads(records_json(kept))
    assert len(table["rows"]) == RECORDS_KEPT and table["rows"][0][0] == 44
    assert kept[0].text is not None  # rendered once, then served as text
    rec.restart()  # a retried window keeps its snapshot, nothing after it
    assert (rec.begin, rec.snapshot, rec.batch) == (seq, seq + 0.1, None)


def test_a_served_mark_counts_from_the_stamp_whatever_moves_begin():
    """A fused drain lays ``begin`` on the flush (its window has no
    assembly leg); the served marks still count from the ``stamp``."""
    rec = WindowRecord(0, 1000.0, begin=50.0)
    rec.begin = rec.assembled = 53.0
    rec.dispatched = 53.5
    row = dict(zip(FIELDS, rec.row()))
    assert (row["begin"], row["assembled"], row["dispatched"]) == (
        3.0, 3.0, 3.5)
    assert rec.ms("begin", "assembled") == 0.0


def test_a_leg_that_raises_sets_no_mark_and_closes_its_span():
    spans = SpanRecorder(enabled=True)
    rec = WindowRecord(3, 1000.0, begin=time.monotonic())
    with telemetry.installed(spans):
        with telemetry.span("aggregator.window", window=3):
            with pytest.raises(RuntimeError):
                with rec.leg("window.snapshot"):
                    raise RuntimeError("the store is gone")
            with rec.leg("window.snapshot"):
                pass
    assert rec.snapshot is not None
    (cycle,) = spans.recent_traces()
    assert [(e.name, e.depth) for e in cycle.events] == [
        ("window.snapshot", 1), ("window.snapshot", 1),
        ("aggregator.window", 0)]
    assert cycle.events[1].duration_s == rec.snapshot - rec.begin


def test_disabled_telemetry_keeps_span_under_a_microsecond():
    """The existing pin (tests/test_telemetry.py), with the new argument."""
    assert not telemetry.recorder().enabled
    n, best = 100_000, float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("window.history", window=7):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"disabled span cost {best * 1e9:.0f}ns/call"


def test_mark_span_lays_a_leg_on_readings_already_taken():
    ticks = iter(range(100, 200))
    rec = SpanRecorder(enabled=True, clock=lambda: 5000.0,
                       monotonic=lambda: float(next(ticks)))
    with rec.span("aggregator.window", window=4):  # opens at 100
        rec.mark_span("window.queued", 90.0, 99.5, window=3)
    rec.mark_span("window.queued", 90.0, 99.5, window=3)  # a cycle alone
    alone, nested = rec.recent_traces()  # by wall start, earliest first
    queued = nested.events[0]
    assert (queued.name, queued.window, queued.depth) == (
        "window.queued", 3, 1)
    assert (queued.rel_start_s, queued.duration_s) == (-10.0, 9.5)
    assert nested.to_dict()["spans"][0]["window"] == 3
    assert alone.name == "window.queued" and alone.duration_s == 9.5
    assert alone.start_wall < 5000.0  # anchored where the leg began
    args = [e["args"] for e in rec.chrome_trace()["traceEvents"]
            if e["ph"] == "X"]
    assert {"depth": 1, "window": 3} in args


# -- names on the device side -------------------------------------------------

SCOPES = ("history_embed", "kv_proj", "last_query_attention", "mlp", "head",
          "attribute")


@pytest.mark.parametrize("compact", [True, False],
                         ids=["as_served", "dense_entry"])
def test_the_lowered_program_holds_its_name_and_the_scope_names(compact):
    """The program the scheduler serves takes the history as its valid
    rows and expands them first; name and scopes are the dense entry's."""
    import jax
    import jax.numpy as jnp

    from kepler_tpu.models import init_temporal
    from kepler_tpu.parallel import (make_fleet_program, make_mesh,
                                     make_temporal_fleet_program)

    n, w, t, z = 8, 16, 4, 2
    params = init_temporal(jax.random.PRNGKey(0), z, t_max=t)
    f32 = jnp.float32
    args = [params, jnp.zeros((n, z), f32), jnp.ones((n, z), bool),
            jnp.zeros(n, f32), jnp.zeros((n, w), f32),
            jnp.ones((n, w), bool), jnp.zeros(n, f32), jnp.ones(n, f32),
            jnp.zeros(n, jnp.int32)]
    if compact:
        n_dev, r = len(jax.devices()), 4
        args += [jnp.zeros((n_dev, r, t * 7), f32),
                 jnp.ones((n_dev, r, t), bool),
                 jnp.zeros((n_dev, n // n_dev * w), jnp.int32)]
    else:
        args += [jnp.zeros((n, w, t, 7), f32), jnp.ones((n, w, t), bool)]
    lowered = make_temporal_fleet_program(
        make_mesh(), compact=compact).lower(*args)
    text = lowered.as_text(debug_info=True)
    assert "jit_temporal_fleet_window" in text
    for scope in SCOPES:
        assert f"temporal_fleet_window)/{scope}/" in text, scope
    hlo = lowered.compile().as_text()
    assert "jit_temporal_fleet_window" in hlo and "unknown" not in \
        hlo.splitlines()[0]
    assert "/last_query_attention/" in hlo
    ratio = make_fleet_program(make_mesh()).lower(jnp.zeros(()), *args[1:9])
    assert "jit_fleet_window" in ratio.as_text()


def test_window_spans_reach_the_profilers_host_plane(tmp_path):
    """A leg of the record is mirrored into
    ``jax.profiler.TraceAnnotation`` under its name, with the window's id
    as a stat; a plain ``telemetry.span`` is not (the recorder knows
    neither jax nor the fleet path's names)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    rec = SpanRecorder(enabled=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.installed(rec):
            record = WindowRecord(7, 1000.0, begin=time.monotonic())
            record.batch = record.begin
            with telemetry.span("aggregator.window", window=7):
                with telemetry.span("window.compile", window=7):
                    pass
                with record.leg("window.history"):
                    jnp.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("window.", "aggregator.")):
                    seen[ev.name] = dict(ev.stats)
    assert seen == {"window.history": {"window": 7}}
