"""From the profiler's device planes to numbers.

Input is what ``launch.py`` wrote: per device plane, per line, events as
``[name, start_ns, duration_ns]``. On a TPU the plane ``/device:TPU:n``
carries a line ``XLA Ops`` (one event per executed HLO op or fusion) beside
``XLA Modules`` (one per program run) and ``Steps``. Busy time is the UNION
of the op intervals, so overlapping events are not counted twice; where a
plane has no op line, the module line stands in.
"""

from __future__ import annotations

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def _line(plane: dict, names: tuple) -> list:
    for line in plane["lines"]:
        if line["line"] in names:
            return line["events"]
    return []


def op_events(plane: dict) -> list:
    return _line(plane, OP_LINES) or _line(plane, MODULE_LINES)


def module_events(plane: dict) -> list:
    return _line(plane, MODULE_LINES)


def union(events: list) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of the events, in ns."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out: list[tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(planes: list) -> float:
    """Seconds in which an operation ran, averaged over the device planes
    that ran any."""
    per = [sum(e - s for s, e in union(op_events(p))) / 1e9 for p in planes]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def idle_share(planes: list, window_s: float) -> float | None:
    busy = busy_seconds(planes)
    if busy <= 0 or window_s <= 0:
        return None
    return 1.0 - busy / window_s


def program_ms(planes: list) -> float | None:
    """Device time of one window's program, ms: the sum of the device-op
    durations inside the program's WHOLE runs over the number of those
    runs. The window's program is the one that takes the time (runs of at
    least half the longest, and of those the ones within a tenth of their
    median); a run the trace caught only the end of, and the small helper
    programs, are left out of both."""
    for plane in planes:
        runs = [(s, s + d) for _n, s, d in module_events(plane) if d > 0]
        if not runs:
            continue
        longest = max(e - s for s, e in runs)
        big = sorted(e - s for s, e in runs if e - s >= 0.5 * longest)
        median = big[len(big) // 2]
        whole = [(s, e) for s, e in runs if e - s >= 0.9 * median]
        ops = sorted((s, d) for _n, s, d in _line(plane, OP_LINES))
        if not ops:
            return sum(e - s for s, e in whole) / len(whole) / 1e6
        total = i = 0
        for s, e in sorted(whole):
            while i < len(ops) and ops[i][0] < s:
                i += 1
            while i < len(ops) and ops[i][0] < e:
                total += ops[i][1]
                i += 1
        return total / len(whole) / 1e6
    return None


def top_ops(planes: list, n: int = 10) -> list[list]:
    total: dict[str, int] = {}
    for plane in planes:
        for name, _s, d in op_events(plane):
            total[name] = total.get(name, 0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:120], ns / 1e9] for name, ns in ranked]


def idle_gaps(planes: list, n: int = 10) -> list[tuple[int, int]]:
    """The ``n`` longest gaps between busy intervals of the first busy
    plane → [(start_ns, end_ns)], longest first."""
    for plane in planes:
        spans = union(op_events(plane))
        if spans:
            gaps = [(a[1], b[0]) for a, b in zip(spans, spans[1:])]
            return sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return []
