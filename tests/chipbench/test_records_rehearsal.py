"""Whole runs at a tiny size against a CPU child, for the metrics that read
the aggregator's window records (``test_rehearsal.py``'s way, in its
temporary copy of the benchmark's data): every span- and counter-sourced
metric is printed, and every window the client saw joins by its stamp to
exactly one record. The runs are untraced — these metrics read
``/debug/window``, not the profiler, and the suite keeps to the one traced
CPU child it had (``test_rehearsal.py``'s) — and their per-layer metrics
are taken from the run as a traced run's line takes them.
``JAX_PLATFORMS=cpu`` on purpose: no number these runs print is a device
metric, and none is asserted on as a time."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import records, run  # noqa: E402
from test_rehearsal import SEED, child_env, root  # noqa: E402,F401

SPAN_AND_COUNTER = ("batch_ms", "history_ms", "assembly_stall_ms", "h2d_ms",
                    "queued_ms", "tick_wait_ms", "h2d_mb", "rows_useful_pct",
                    "ingest_lock_wait_us")
FLOOD_ONLY = ("ingest_decode_us", "ingest_merge_us", "ingest_history_us")
FROM_THE_TRACE = ("idle_in_assembly_pct", "idle_in_tick_pct")


@pytest.fixture(scope="module")
def ran(root, tmp_path_factory):  # noqa: F811
    """One run of each tiny cell → {cell: (its per-layer metrics, the
    Run)}."""
    kept = []

    class Kept(run.Run):
        def __init__(self, *args) -> None:
            super().__init__(*args)
            kept.append(self)

    original, run.Run = run.Run, Kept
    try:
        out = {}
        for k, cell in enumerate(("tiny.trickle", "tiny.flood")):
            rc, line = run.run_cell(cell, SEED + 20 + k, 2.0, False,
                                    root=root, platform="cpu",
                                    env=child_env(tmp_path_factory))
            assert rc == 0 and line["correct"] is True
            out[cell] = (kept[-1].metrics("per_layer"), kept[-1])
        return out
    finally:
        run.Run = original


@pytest.mark.parametrize("cell, kind, extra", [
    ("tiny.trickle", "paced", ()), ("tiny.flood", "flood", FLOOD_ONLY)])
def test_every_span_and_counter_metric_is_printed(ran, cell, kind, extra):
    got, _run = ran[cell]
    for base in SPAN_AND_COUNTER + extra:
        assert f"{base}.{kind}" in got, base
        assert got[f"{base}.{kind}"]["value"] >= 0.0
    # an untraced run has no device plane: what reads the trace stays silent
    assert not {f"{base}.{kind}" for base in FROM_THE_TRACE} & set(got)
    # counts, which a CPU run can give: 8 nodes x 8 slots, 4 model nodes
    # of 4 pods; one H2D of the same arrays every window
    assert got[f"rows_useful_pct.{kind}"]["value"] == pytest.approx(
        100.0 * 16 / 64)
    assert got[f"h2d_mb.{kind}"]["value"] == pytest.approx(
        (8 * 8 * 4 * 7 * 4 + 8 * 8 * 4 + 8 * 8 * 5 + 8 * 4 * 5 + 8 * 16)
        / 1e6)


@pytest.mark.parametrize("cell", ["tiny.trickle", "tiny.flood"])
def test_every_window_joins_by_its_stamp_to_one_record(ran, cell):
    _metrics, this = ran[cell]
    recs = records.window_records(this.drive.debug["last"])
    assert len(this.windows_in) >= 2
    assert records.joined(this) is not None
    for win in this.windows_in:
        mine = [r for r in recs if r["stamp"] == win.stamp]
        assert len(mine) == 1
        assert win.stamp <= mine[0]["published"] <= win.seen
        assert mine[0]["kind"] == "legacy"
    # the loop's wait is the tick leg of every window but a hand-made one
    assert all(r["tick"] is not None and r["tick"] <= r["begin"]
               for r in recs)
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
