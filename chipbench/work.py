"""What one window has to do, and the least time the chip could take for it.

The count itself is the estimator's (``estimators/<name>.py``: ``work``), of
the algorithm and not of any implementation, so the number reads the same
whoever computes it; here are the chip's published peaks and the roofline
arithmetic over them.
"""

from __future__ import annotations

import json
import os

from chipbench.estimators.temporal import window_work  # noqa: F401 (tests)
from chipbench.spec import estimator_of

HERE = os.path.dirname(os.path.abspath(__file__))


def of_config(config: dict, model_pods: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one window of ``config``'s estimator, as its module
    counts them. An estimator whose algorithm nobody has counted is an
    error (``spec.SpecError``), never a guess."""
    return estimator_of(config).work(config, model_pods)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[
        float, str]:
    """→ (the least time the chip could take, which bound sets it)."""
    by_flops = flops / peak["flops_per_s"]
    by_bytes = nbytes / peak["bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (
        by_bytes, "bandwidth")
