"""The aggregator's window records, and the device trace laid on them.

``GET /debug/window`` serves one record per published window (``records``:
``fields``, ``rows``, and ``legs``, each leg's span name with the two marks
it lies on) and sums since start (``counts``, ``ingest``); a run keeps the
whole body as read before the window opened and after the close
(``run.drive.debug["first"]``, ``["last"]``). A record's boundaries are
seconds after its ``stamp`` on the wall clock. The body holds the last 256
records only: what joins a run's windows to their records says nothing
when one is missing (``joined``). An older program serves none of this:
every function here then returns nothing, never 0.

The device trace counts from its own start, so one offset (the wall time of
the trace's zero) puts it on the records' clock. Two bounds on it are hard,
for every record whose run of the window's program is found on the ``XLA
Modules`` line:

- the run starts no earlier than the record's ``window.dispatch`` began
  (the leg's first mark, ``h2d``): offset >= dispatch_begin - run_start, for every
  window, and the LARGEST of these is the estimate — its error is the
  smallest launch delay among the windows of the run;
- the run ends no later than the record's ``window.pipeline_wait`` ended
  (the leg's second mark, ``fetched``), and the trace's zero is no later than the
  launcher's ``marks["start"]``: the estimate may exceed neither.

Runs and records are both in order, one run a record, so they are matched
by their spacing: the shift of one list against the other under which
(dispatch_begin - run_start) varies least.
"""

from __future__ import annotations

from chipbench import trace


def delta(first: dict, last: dict, path: list):
    """(last - first) of one cumulative number of the body, by its path of
    keys; None where either body lacks it."""
    values = []
    for body in (first, last):
        for key in path:
            if not isinstance(body, dict) or key not in body:
                return None
            body = body[key]
        values.append(body)
    return values[1] - values[0]


def leg_marks(body: dict) -> dict:
    """Span name → [from mark, to mark], as the body serves the table; {}
    where it serves none."""
    return ((body or {}).get("records") or {}).get("legs") or {}


def joined(run) -> list[dict] | None:
    """The record of every window of the run's measured window, joined by
    the stamp, in the windows' order. None where the last body has no
    record for one of them: it keeps the last 256, and a median over the
    windows that are left would read the run's tail for the run."""
    by_stamp = {r["stamp"]: r
                for r in window_records(run.drive.debug.get("last"))}
    found = [by_stamp.get(win.stamp) for win in run.windows_in]
    return None if None in found else found


def window_records(body: dict) -> list[dict]:
    """The body's records as dicts, every boundary a wall time (``stamp`` +
    the served offset); [] where the body has none."""
    table = (body or {}).get("records")
    if not table:
        return []
    fields = table["fields"]
    first_mark, last_mark = fields.index("tick"), fields.index("published")
    out = []
    for row in table["rows"]:
        rec = dict(zip(fields, row))
        for name in fields[first_mark:last_mark + 1]:
            if rec[name] is not None:
                rec[name] += rec["stamp"]
        out.append(rec)
    return out


def program_runs(planes: list) -> list[tuple[float, float]]:
    """Whole runs of the window's program on the first plane that has any,
    in seconds of the trace's clock, in order. The program is picked as
    ``trace.program_ms`` picks it: the runs of at least half the longest,
    and of those the ones within a tenth of their median."""
    for plane in planes:
        runs = [(s, s + d) for _n, s, d in trace.module_events(plane)
                if d > 0]
        if not runs:
            continue
        longest = max(e - s for s, e in runs)
        big = sorted(e - s for s, e in runs if e - s >= 0.5 * longest)
        median = big[len(big) // 2]
        return sorted((s / 1e9, e / 1e9) for s, e in runs
                      if 0.9 * median <= e - s <= 1.1 * median)
    return []


def align(body: dict, planes: list, marks: dict) -> dict | None:
    """Match the program's runs to the body's records and estimate the
    offset → {"offset_s", "pairs": [(record, run)], "launch_delay_s":
    [...]} or None where nothing can be matched or a hard bound is
    contradicted."""
    legs = leg_marks(body)
    runs = program_runs(planes)
    if not ({"window.dispatch", "window.pipeline_wait"} <= set(legs)
            and runs and "start" in marks):
        return None
    began, ended = legs["window.dispatch"][0], legs["window.pipeline_wait"][1]
    recs = [r for r in window_records(body)
            if r.get(began) is not None and r.get(ended) is not None]
    if not recs:
        return None
    n = min(len(recs), len(runs))
    best = None
    for shift in range(len(recs) - n + 1):
        for skip in range(len(runs) - n + 1):
            gaps = [recs[shift + k][began] - runs[skip + k][0]
                    for k in range(n)]
            spread = max(gaps) - min(gaps)
            if best is None or spread < best[0]:
                best = (spread, shift, skip, gaps)
    _spread, shift, skip, gaps = best
    pairs = [(recs[shift + k], runs[skip + k]) for k in range(n)]
    offset = max(gaps)
    latest = min(rec[ended] - run[1] for rec, run in pairs)
    if offset > latest or offset > marks["start"]:
        return None
    return {"offset_s": offset, "pairs": pairs,
            "launch_delay_s": sorted(offset - g for g in gaps)}


def _clip(spans: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in spans
            if min(b, hi) > max(a, lo)]


def _overlap(a: list, b: list) -> float:
    """Seconds two sorted lists of disjoint intervals have in common."""
    total, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            total += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return total


def _complement(spans: list, lo: float, hi: float) -> list:
    out, at = [], lo
    for a, b in spans:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def idle_by_leg(body: dict, planes: list, marks: dict,
                groups: dict) -> dict | None:
    """The device's idle seconds inside the stretch that both the trace
    and the body's records cover, how much of it falls inside each group
    of legs (``groups``: name → span names of legs, as the body's table
    has them) and how much in none — by intersection of intervals."""
    fit = align(body, planes, marks)
    if fit is None:
        return None
    offset = fit["offset_s"]
    busy = []
    for plane in planes:
        busy = [(offset + s / 1e9, offset + e / 1e9)
                for s, e in trace.union(trace.op_events(plane))]
        if busy:
            break
    records, table = window_records(body), leg_marks(body)
    edges = [r[m] for r in records for m in ("tick", "begin", "published")
             if r.get(m) is not None]
    lo, hi = max(busy[0][0], min(edges)), min(busy[-1][1], max(edges))
    if hi <= lo or not all(g in table for legs in groups.values()
                           for g in legs):
        return None
    idle = _complement(_clip(busy, lo, hi), lo, hi)

    def spans(legs: list) -> list:
        found = sorted((r[a], r[b]) for r in records
                       for a, b in (table[g] for g in legs)
                       if r.get(a) is not None and r.get(b) is not None)
        return _clip(found, lo, hi)

    inside = {name: spans(legs) for name, legs in groups.items()}
    rest = _complement(sorted(x for found in inside.values() for x in found),
                       lo, hi)
    return {"idle_s": sum(b - a for a, b in idle),
            "in_s": {name: _overlap(idle, found)
                     for name, found in inside.items()},
            "rest_s": _overlap(idle, rest),
            "offset_s": offset,
            "launch_delay_s": fit["launch_delay_s"]}
