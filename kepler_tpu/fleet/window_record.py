"""One record per fleet window: every boundary of the served window path.

A window gets its record (and its sequence number) at its snapshot; the
record travels on the window's ``_Pending`` from dispatch to publication
and is complete when ``_publish`` has stored the results. With
``pipelineDepth`` 2 a window is dispatched in one ``aggregate_once`` call
and published by the served loop's publisher thread when its program is
done (in the next call, where ``aggregate_once`` is called directly), so a
record — not a telemetry cycle — is what follows one window.

One clock: a leg of the serial (einsum-f32 / temporal) path ends on a mark
of the record, the ``last_*_ms`` gauges are differences of the marks, and
the leg's span is laid on the same two marks (``telemetry.mark_span``), so
the stage histograms, ``/debug/traces`` and the served records cannot
disagree. What still reads the clock itself: the ``aggregator.window``
cycle span around the legs, and the spans the packed and fused paths
already had (``window.h2d_delta``, ``window.fused_scan``,
``window.compile``, ``window.publish_fetch`` with the ``last_fetch_ms``
timer) — those paths fill the marks they time (begin, assembled,
dispatched, fetched, scattered, published) and gain no leg.

Marks are ``time.monotonic`` readings (a stepped wall clock never yields a
negative leg). Wall time enters once, at the snapshot: ``stamp`` is the
aggregator's clock there, the ``timestamp`` the window is published under,
and a served boundary is ``stamp`` + (mark − the monotonic reading taken
beside ``stamp``), as a cycle trace is laid on its one wall anchor.

The records are taken whether or not ``telemetry.enabled`` is set: the
gauges need them. Only span objects, histograms and ``/debug/traces`` stay
behind that switch.
"""

from __future__ import annotations

import collections
import contextlib
import json
import time
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

from kepler_tpu import telemetry

# in the order the serial path passes them
MARKS = ("tick", "begin", "snapshot", "batch", "assembled", "h2d",
         "dispatched", "publish_begin", "fetched", "scattered", "published")

# span name → (from mark, to mark); a leg with an unset mark does not exist.
# Served beside the records, so a reader keeps no copy of this table.
LEGS = {
    "window.tick_wait": ("tick", "begin"),
    "window.snapshot": ("begin", "snapshot"),
    "window.batch": ("snapshot", "batch"),
    "window.history": ("batch", "assembled"),
    "window.h2d": ("assembled", "h2d"),
    "window.dispatch": ("h2d", "dispatched"),
    "window.queued": ("dispatched", "publish_begin"),
    "window.pipeline_wait": ("publish_begin", "fetched"),
    "window.scatter": ("fetched", "scattered"),
    "window.publish": ("scattered", "published"),
}

# counted where the work happens; summed over windows in ``counts``.
# ROW_COUNTS are columns of a served row too; what the put over the mesh
# counts is served in the sums alone (``chipbench`` pins a row's columns)
ROW_COUNTS = ("rows_program", "rows_work", "h2d_bytes")
SUM_COUNTS = ("devices", "h2d_bytes_max_device", "published_early",
              "hist_rows_sent", "rows_fused")
COUNTS = ROW_COUNTS + SUM_COUNTS

FIELDS = ("seq", "stamp", "kind") + MARKS + ("assembly_cpu_s",) \
    + ROW_COUNTS + ("compiled",)

# complete records served on /debug/window. A reader that joins a run's
# windows to their records needs every window of its measured stretch in
# the last body: a benchmark run publishes some 600 at 0.12 s a window.
# Raise this before a program publishes more than some 1800 in a run
# (PERF.md section 7).
RECORDS_KEPT = 2048


class WindowRecord:
    """The marks and counts of one window. Written by the aggregation loop
    until the window is dispatched, then by the one thread that publishes
    it (under the aggregator's pipeline lock); read-only once complete."""

    __slots__ = FIELDS + SUM_COUNTS + ("base", "cpu_begin_ns", "text")

    def __init__(self, seq: int, stamp: float, begin: float,
                 tick: float | None = None) -> None:
        self.seq = seq
        self.stamp = stamp
        self.base = begin  # the monotonic reading beside ``stamp``
        self.begin = begin
        self.tick = tick
        self.snapshot: float | None = None
        self.cpu_begin_ns = time.thread_time_ns()
        self.text: str | None = None  # the served row, rendered once
        self.restart()

    def restart(self) -> None:
        """Unset what a dispatch and a publication fill: a window that a
        ladder rung failed is computed again on the next, under the same
        record, and keeps none of the failed attempt's marks."""
        self.kind = ""
        for name in MARKS[3:]:
            setattr(self, name, None)
        # the loop thread's CPU seconds from begin to assembled: wall − CPU
        # is the time assembly was kept off the processor (GIL, locks)
        self.assembly_cpu_s: float | None = None
        # the rows the estimator ran on: node bucket × workload bucket, or
        # of a compact temporal window, shards × history rows a shard
        self.rows_program = 0
        self.rows_work = 0  # pods of model nodes: the estimates published
        self.h2d_bytes = 0
        self.devices = 0  # the devices the window's program ran over
        self.h2d_bytes_max_device = 0  # of h2d_bytes, the most one was sent
        # 1 where the publication began before a later window took its
        # sequence number: the window did not wait for the loop's next step
        self.published_early = 0
        # history rows the temporal program was sent: shards × the bucket
        # that holds the fullest shard's valid rows (padding included)
        self.hist_rows_sent = 0
        # of rows_program, the rows the fused trunk kernel ran on: all of
        # them where the program was built with it, else none
        self.rows_fused = 0
        self.compiled = False

    @contextlib.contextmanager
    def leg(self, name: str, devices: int | None = None) -> Iterator[None]:
        """One leg as a with-block: it starts on the mark the leg before
        it ended on and ends with the block, on a mark of its own, and its
        span (with ``window=seq``) lies on those two marks. The block is
        mirrored onto the JAX profiler's host timeline under the same
        name, with ``window`` as a stat, so a ``/debug/pprof/jax`` capture
        shows host legs and device ops together (no profile running: well
        under a microsecond). ``devices``, on the legs that put to or
        fetch from the mesh, is a second attribute of both. A block that
        raises sets no mark."""
        a, b = LEGS[name]
        attrs = {"window": self.seq}
        if devices is not None:
            attrs["devices"] = devices
        sp = telemetry.span(name, **attrs)
        sp.open_at(getattr(self, a))
        now = None
        try:
            with TraceAnnotation(name, **attrs):
                yield
            now = time.monotonic()
            setattr(self, b, now)
        finally:
            sp.close_at(time.monotonic() if now is None else now)

    def ms(self, a: str, b: str) -> float:
        """Mark ``b`` − mark ``a`` in milliseconds, for the gauges (0.0
        where either is unset)."""
        t0, t1 = getattr(self, a), getattr(self, b)
        return 0.0 if t0 is None or t1 is None else (t1 - t0) * 1e3

    def row(self) -> list:
        """``FIELDS`` as served: a mark is seconds after ``stamp`` on the
        wall clock (``stamp`` + value = when), to a tenth of a µs."""
        out: list[Any] = [self.seq, self.stamp, self.kind]
        for name in MARKS:
            t = getattr(self, name)
            out.append(None if t is None else round(t - self.base, 7))
        cpu = self.assembly_cpu_s
        out.append(None if cpu is None else round(cpu, 7))
        out += [getattr(self, name) for name in ROW_COUNTS]
        out.append(self.compiled)
        return out


class WindowLedger:
    """The last ``RECORDS_KEPT`` complete records and, since start, the
    windows published and their summed counts — so a reader takes (last −
    first) however many windows lie between two reads. Guarded by its
    owner's lock (the aggregator's ``_results_lock``)."""

    def __init__(self) -> None:
        self.records: collections.deque[WindowRecord] = collections.deque(
            maxlen=RECORDS_KEPT)
        self.counts: dict[str, int] = {"windows": 0,
                                       **dict.fromkeys(COUNTS, 0)}

    def add(self, rec: WindowRecord) -> None:
        self.records.append(rec)
        self.counts["windows"] += 1
        for name in COUNTS:
            self.counts[name] += getattr(rec, name)

    def snapshot(self) -> tuple[list[WindowRecord], dict]:
        """A copy to serialise outside the lock."""
        return list(self.records), dict(self.counts)


def records_json(records: list[WindowRecord]) -> str:
    """The ``records`` value of ``/debug/window``, as JSON text. A
    complete record never changes, so its row is rendered once, by the
    first request that serves it: a poller asks for the whole ring once per
    published window, and a full ring's rows rendered anew each time would
    hold the interpreter for tens of milliseconds of every window."""
    rows = []
    for rec in records:
        if rec.text is None:
            rec.text = json.dumps(rec.row())
        rows.append(rec.text)
    return '{"fields": %s, "legs": %s, "rows": [%s]}' % (
        json.dumps(list(FIELDS)), json.dumps(LEGS), ", ".join(rows))
