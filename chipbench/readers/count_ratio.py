"""(last − first) of one cumulative number of ``/debug/window`` over (last
− first) of another, times ``scale``: bytes a window, useful rows a row,
seconds a report. ``num`` and ``den`` are paths of keys into the body.
Nothing where the program serves neither or the denominator stood still."""

from chipbench.records import delta


def read(run, num: list, den: list, scale: float = 1.0):
    first = run.drive.debug.get("first")
    last = run.drive.debug.get("last")
    above, below = delta(first, last, num), delta(first, last, den)
    if above is None or not below:
        return None
    return scale * above / below
