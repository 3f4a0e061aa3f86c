"""Median of (posted - due) over the measured rounds, ms: how late the
generator ran."""

from statistics import median


def read(run):
    late = [(r.start - r.due) * 1e3 for r in run.drive.rounds]
    return median(late) if late else None
