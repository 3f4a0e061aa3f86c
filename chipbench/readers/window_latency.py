"""Percentile of (first sight - the window's own stamp), ms, over all the
windows of the measured window; one never seen is +inf."""

from chipbench.stats import percentile


def read(run, q: float):
    samples = run.latencies_ms
    return percentile(samples, q) if samples else None
