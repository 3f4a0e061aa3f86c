"""The ``temporal`` estimator: an attention block over each pod's last ticks.

Everything the yardstick knows of it, found by the configuration's
``estimator`` (``spec.estimator_of``): seeded parameters, the plain
reference, the operation count, the window program's name and the control.

The reference is written from the estimator's published description
(``models/temporal.py`` docstring: in-projection, learned positions, one
pre-LN attention block whose only query is the newest tick, one pre-LN GELU
MLP, final LN, a linear head plus a linear skip from the newest tick's raw
features) and independent of it: it imports nothing of ``kepler_tpu``,
takes no weights, tables or features the program has made, and computes in
float32 NumPy on the host throughout (NumPy's float32 matmul is true
float32).

``quantize`` puts a lower precision in the matmuls' operands, with float32
accumulation, as the program does it: ``"bf16"`` is the precision the
configurations state (what the chip computes; used by the tests to stand in
for the program), ``"fp8"`` (e4m3) is ``CONTROL``, the step below it, which
the comparison has to fail.

The count (``work``) is of the ALGORITHM the description states — project
T ticks of every pod of a model node to keys and values, one query (the
newest tick) through attention, the MLP, the head and the skip — and not of
any implementation: rows a program computes for padding or for ratio nodes
(whose estimate is thrown away) are no work, and what a program keeps
between steps is its own affair. So the number reads the same whoever
computes it. Bytes are what must cross HBM at least once: each pod's
feature history in, its watts out, the parameters once.
"""

from __future__ import annotations

import numpy as np

from chipbench.precision import QUANTIZERS

F32 = np.float32
N_FEATURES = 7  # the rows of ``reference.features``: the fleet's, not ours
LN_EPS = 1e-6

PROGRAM = "jit_temporal_fleet_window"  # the window's program, by its name
CONTROL = "fp8"  # the configurations state bf16 operands: the step below
# the sizes of ``models/temporal.py``'s trunk, which every configuration of
# it holds uncut
WIDTHS = {"d_model": 128, "n_heads": 4, "mlp_dim": 512, "n_features": 7}


def make_params(seed: int, config: dict) -> dict[str, np.ndarray]:
    """Seeded parameters in the estimator's flat ``.npz`` layout.

    An untrained ``init_temporal`` has a zero head and a zero skip, which
    would make every model row 0 W. Here the skip carries a positive,
    watt-scaled linear signal and the head puts the attention trunk's
    output on top at about a watt, around a bias of a few watts — so a row
    is several watts, is seldom clamped at 0, and every layer's rounding
    reaches the published number."""
    rng = np.random.default_rng([int(seed), 3])
    d = int(config["d_model"])
    d4 = int(config["mlp_dim"])
    t_max = int(config["t_max"])
    z = len(config["zones"])

    def glorot(*shape):
        return (rng.standard_normal(shape)
                * np.sqrt(2.0 / (shape[-2] + shape[-1]))).astype(F32)

    def near(center, spread, n):
        return (center + spread * rng.standard_normal(n)).astype(F32)

    w_skip = np.zeros((N_FEATURES, z), F32)
    w_skip[0] = rng.uniform(0.4, 1.2, z)  # cpu seconds
    w_skip[4] = rng.uniform(1.0, 3.0, z)  # cores in use
    w_skip[5] = rng.uniform(0.2, 0.6, z)
    return {
        "in_proj": glorot(N_FEATURES, d),
        "pos_emb": (0.5 * rng.standard_normal((t_max, d))).astype(F32),
        "ln1_scale": near(1.0, 0.1, d), "ln1_bias": near(0.0, 0.1, d),
        "wq": glorot(d, d), "wk": glorot(d, d), "wv": glorot(d, d),
        "wo": glorot(d, d),
        "ln2_scale": near(1.0, 0.1, d), "ln2_bias": near(0.0, 0.1, d),
        "w_mlp0": glorot(d, d4), "b_mlp0": near(0.0, 0.05, d4),
        "w_mlp1": glorot(d4, d), "b_mlp1": near(0.0, 0.05, d),
        "ln_f_scale": near(1.0, 0.1, d), "ln_f_bias": near(0.0, 0.1, d),
        "w_head": (0.09 * rng.standard_normal((d, z))).astype(F32),
        "b_head": rng.uniform(3.0, 6.0, z).astype(F32),
        "w_skip": w_skip,
    }


class _Math:
    def __init__(self, quantize: str | None) -> None:
        self.q = QUANTIZERS[quantize]

    def mm(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        if self.q is not None:
            a, b = self.q(a), self.q(b)
        return np.matmul(a, b, dtype=F32)


def _layer_norm(x, scale, bias):
    mu = x.mean(axis=-1, keepdims=True, dtype=F32)
    xc = x - mu
    var = np.mean(xc * xc, axis=-1, keepdims=True, dtype=F32)
    return xc / np.sqrt(var + F32(LN_EPS)) * scale + bias


def _gelu(x):
    c = F32(np.sqrt(2.0 / np.pi))
    return F32(0.5) * x * (F32(1.0) + np.tanh(
        c * (x + F32(0.044715) * x * x * x)))


def temporal_watts(params: dict, hist: np.ndarray, t_valid: np.ndarray,
                   quantize: str | None = None) -> np.ndarray:
    """hist f32 [b, t, 7], oldest tick first; t_valid bool [b, t], a pod's
    ticks at the front (a pod younger than ``t`` rounds has fewer) → watts
    f32 [b, z] of the newest tick, not below 0. The one query is the newest
    valid tick; ticks that are not valid are no keys."""
    m = _Math(quantize)
    p = params
    b, t, _ = hist.shape
    d = p["in_proj"].shape[1]
    heads = 4
    dh = d // heads
    rows = np.arange(b)
    last = np.maximum(t_valid.sum(axis=1) - 1, 0)
    x = m.mm(hist, p["in_proj"]) + p["pos_emb"][:t]
    x = np.where(t_valid[:, :, None], x, F32(0.0))
    y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    q = m.mm(y[rows, last], p["wq"]).reshape(b, heads, dh)
    k = m.mm(y, p["wk"]).reshape(b, t, heads, dh)
    v = m.mm(y, p["wv"]).reshape(b, t, heads, dh)
    if m.q is not None:
        q, k = m.q(q), m.q(k)
    scores = np.einsum("bhd,bthd->bht", q, k, dtype=F32) / F32(np.sqrt(dh))
    scores = np.where(t_valid[:, None, :], scores, F32(-1e30))
    scores -= scores.max(axis=-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(axis=-1, keepdims=True, dtype=F32)
    if m.q is not None:
        probs, v = m.q(probs), m.q(v)
    attn = np.einsum("bht,bthd->bhd", probs, v, dtype=F32).reshape(b, d)
    x_last = x[rows, last] + m.mm(attn, p["wo"])
    y = _layer_norm(x_last, p["ln2_scale"], p["ln2_bias"])
    y = _gelu(m.mm(y, p["w_mlp0"]) + p["b_mlp0"])
    x_last = x_last + m.mm(y, p["w_mlp1"]) + p["b_mlp1"]
    pooled = _layer_norm(x_last, p["ln_f_scale"], p["ln_f_bias"])
    watts = (m.mm(pooled, p["w_head"]) + m.mm(hist[rows, last], p["w_skip"])
             + p["b_head"])
    return np.maximum(watts, F32(0.0))


def watts(params: dict, hist: np.ndarray, t_valid: np.ndarray, config: dict,
          quantize: str | None = None) -> np.ndarray:
    """The seam's name for ``temporal_watts``; the sizes are the parameters'
    own, so ``config`` has nothing more to say."""
    return temporal_watts(params, hist, t_valid, quantize)


def small(config: dict) -> dict:
    """``config`` at a size a CPU test holds: itself, since a trunk 128
    wide is one already (its parameters are 0.9 MB)."""
    return config


def block_rows(config: dict) -> int:
    """Rows a block of the reference may hold: ~30 MB an activation."""
    return 60_000 // int(config["history_window"])


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


def work(config: dict, model_pods: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one window of ``config`` over ``model_pods``."""
    return window_work(
        model_pods, int(config["history_window"]), int(config["n_features"]),
        int(config["d_model"]), int(config["mlp_dim"]), len(config["zones"]))
