"""What one window of the temporal estimator has to do, from its shapes.

The count is of the ALGORITHM the estimator's description states — project
T ticks of every pod of a model node to keys and values, one query (the
newest tick) through attention, the MLP, the head and the skip — and not of
any implementation: rows a program computes for padding or for ratio nodes
(whose estimate is thrown away) are no work, and what a program keeps
between steps is its own affair. So the number reads the same whoever
computes it. Bytes are what must cross HBM at least once: each pod's
feature history in, its watts out, the parameters once.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def window_work(model_pods: int, t: int, f: int, d: int, d_mlp: int,
                z: int) -> tuple[float, float]:
    """→ (FLOPs, bytes) of one window: ``model_pods`` pods of model nodes,
    ``t`` ticks of ``f`` features, width ``d``, MLP width ``d_mlp``, ``z``
    zones. A multiply-add is two operations."""
    per_tick = 2 * f * d + 2 * (2 * d * d)  # in-projection, K and V
    per_pod = (t * per_tick
               + 2 * d * d  # the one query
               + 2 * t * d + 2 * t * d  # scores and weighted values
               + 2 * d * d  # attention output projection
               + 2 * (2 * d * d_mlp)  # MLP up and down
               + 2 * d * z + 2 * f * z)  # head and skip
    flops = float(model_pods) * per_pod
    params = (f * d + t * d + 4 * d * d + 2 * d * d_mlp + d_mlp + d
              + 6 * d + d * z + z + f * z)
    nbytes = float(model_pods) * (t * f * 4 + t + z * 4) + params * 4.0
    return flops, nbytes


def of_config(config: dict, model_pods: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one window of ``config``'s estimator. An estimator
    whose algorithm nobody has counted here is an error, never a guess: a
    configuration that brings one brings its count (and its reference)."""
    if config["estimator"] != "temporal":
        raise KeyError(f"no operation count for the estimator "
                       f"{config['estimator']!r} in chipbench/work.py")
    return window_work(
        model_pods, int(config["history_window"]), int(config["n_features"]),
        int(config["d_model"]), int(config["mlp_dim"]), len(config["zones"]))


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
