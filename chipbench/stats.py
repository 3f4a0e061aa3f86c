"""The arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks; +inf values (answers never seen) sort last, so they
    raise a tail exactly as a request that never returned must."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    a, b = ordered[lo], ordered[hi]
    if lo == hi or a == b:
        return a
    if math.isinf(b):
        return b  # any step towards +inf is +inf
    return a + (b - a) * (pos - lo)


def window_latencies(windows: list, t_open: float, t_close: float,
                     published: int, count_from: float,
                     count_to: float) -> tuple[list[float], int, int]:
    """Latency samples of the measured window → (ms per window, attempted,
    failed). A sample is first sight minus the window's own stamp, over ALL
    windows first seen inside [t_open, t_close]. ``published`` is how many
    windows the aggregator says it published between two readings of its
    counter, taken at ``count_from`` (just before the open) and
    ``count_to`` (just after the close); each one the client did not see
    between them counts as failed and as +inf."""
    seen = [(w.seen - w.stamp) * 1e3 for w in windows
            if t_open <= w.seen <= t_close]
    counted = sum(1 for w in windows if count_from <= w.seen <= count_to)
    failed = max(0, published - counted)
    return seen + [math.inf] * failed, len(seen) + failed, failed


def rate(amount: float, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0:
        raise ValueError("no time")
    return amount / seconds
