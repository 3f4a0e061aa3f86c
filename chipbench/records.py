"""The aggregator's window records, and the device trace laid on them.

``GET /debug/window`` serves one record per published window (``records``:
``fields``, ``rows``, and ``legs``, each leg's span name with the two marks
it lies on) and sums since start (``counts``, ``ingest``); a run keeps the
whole body as read before the window opened and after the close
(``run.drive.debug["first"]``, ``["last"]``). A record's boundaries are
seconds after its ``stamp`` on the wall clock. The body holds the last
2048 records only: what joins a run's windows to their records says
nothing when one is missing (``joined``). An older program serves none of
this: every function here then returns nothing, never 0.

The device trace counts from its own start, and says when that was
(``profile_start_time``, Unix ns, kept by the launcher): it lies on the
records' clock with no estimate, and the records can contradict it (``align``).
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
    record for one of them: a median over the rest reads the run's tail."""
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


# ``program`` below is the prefix of the window's program's name, which the
# cell's estimator states (``estimators/<name>.py``: ``PROGRAM``) and the
# caller passes (``run.Run.program``): nothing here knows an estimator.
FETCH_SLACK_S = 1e-3  # the two clocks' rounding, and the host's half ms


def program_runs(planes: list, program: str) -> list[tuple[float, float]]:
    """Runs of the window's program (``XLA Modules`` events that carry its
    name) on the first plane that has any, in trace seconds, in order."""
    for plane in planes:
        runs = sorted((s / 1e9, (s + d) / 1e9)
                      for name, s, d in trace.module_events(plane)
                      if d > 0 and name.startswith(program))
        if runs:
            return runs
    return []


def align(body: dict, planes: list, launch: dict,
          program: str) -> dict | None:
    """Hold the trace's own zero against the body's records → {"offset_s",
    "checked", "contradicted", the least "launch_delay_s" and
    "fetch_margin_s" of the runs that fit, "against": for the first three
    that did not, [window, run start − its dispatch's begin, run end − its
    fetch]}, or None without ``profile_start_time`` in the launcher's
    report (an older JAX), records in the body or a run of the program in
    the trace. Held is what the program guarantees: runs and windows come
    in one order, a run starts no earlier than its window's
    ``window.dispatch`` began and ends at most ``FETCH_SLACK_S`` after its
    ``window.pipeline_wait`` did. So a run takes the next window not
    fetched before the run ended, and contradicts the zero if that
    window's dispatch had not begun when it started. (How soon a put lands
    is no guarantee: under a busy host a run starts after the NEXT
    window's dispatch began, and is its own window's still.)"""
    legs = leg_marks(body)
    runs = program_runs(planes, program)
    zero_ns = (launch or {}).get("profile_start_time")
    if not ({"window.dispatch", "window.pipeline_wait"} <= set(legs)
            and runs and zero_ns):
        return None
    began, ended = legs["window.dispatch"][0], legs["window.pipeline_wait"][1]
    recs = sorted((r for r in window_records(body)
                   if r.get(began) is not None and r.get(ended) is not None),
                  key=lambda r: r[began])
    offset = zero_ns / 1e9
    k, delays, margins, against = 0, [], [], []
    for start, end in runs:
        start, end = offset + start, offset + end
        if not recs or start < recs[0][began]:
            continue  # a run from before the first record the body keeps
        while k < len(recs) and recs[k][ended] + FETCH_SLACK_S < end:
            k += 1  # fetched before this run ended: another run's window
        rec = recs[min(k, len(recs) - 1)]
        if k < len(recs) and rec[began] <= start:
            delays.append(start - rec[began])
            margins.append(rec[ended] - end)
            k += 1
        else:
            against.append([rec["seq"], start - rec[began], end - rec[ended]])
    if not delays and not against:
        return None
    return {"offset_s": offset, "checked": len(delays) + len(against),
            "contradicted": len(against),
            "launch_delay_s": min(delays, default=None),
            "fetch_margin_s": min(margins, default=None),
            "against": against[:3]}


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


def idle_by_leg(body: dict, planes: list, launch: dict, groups: dict,
                program: str) -> dict | None:
    """The device's idle seconds inside the stretch that both the trace
    and the body's records cover, how much of it falls inside each group
    of legs (``groups``: name → span names of legs, as the body's table
    has them) and how much in none — by intersection of intervals.
    Nothing where the zero is unknown or contradicted: it is one number for
    all runs, so by over one run in twenty (one says its record is off)."""
    fit = align(body, planes, launch, program)
    if fit is None or 20 * fit["contradicted"] > fit["checked"]:
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
            "rest_s": _overlap(idle, rest), "offset_s": offset}
