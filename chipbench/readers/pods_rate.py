"""Pods published per second of the measured window.

A round is one report from every node; it is done when the first window
stamped after the end of its POST is first seen on ``/v1/results``. In the
closed loop rounds follow each other without a gap, so they tile the
measured window; a round's pods count evenly over the time the round took,
and the one round that straddles the close counts by the share of its time
that lies inside. All the work over all the time, with no step of a whole
round (a twelfth of the window) when a run ends a moment earlier or later.
A round that never got its window counts nothing."""

from chipbench.stats import rate


def read(run):
    d = run.drive
    credit = 0.0
    for rnd in d.rounds:
        cover = next((w for w in d.windows if w.stamp > rnd.end), None)
        if cover is None or cover.seen <= rnd.start:
            continue
        inside = min(cover.seen, d.t_close) - max(rnd.start, d.t_open)
        credit += max(0.0, inside) / (cover.seen - rnd.start)
    return rate(credit * d.fleet.total_pods, d.seconds) or None
