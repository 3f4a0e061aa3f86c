"""Median over the measured window's windows of a sum of the aggregator's
own per-window host clocks (``/debug/window`` ``stats.last_*_ms``), one
sample per published window."""

from statistics import median


def read(run, gauges: list):
    sums = [sum(w.gauges[g] for g in gauges) for w in run.windows_in]
    return median(sums) if sums else None
