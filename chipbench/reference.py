"""The plain reference: what the aggregator must publish, in NumPy.

Written from the estimator's published description (``models/temporal.py``
docstring: in-projection, learned positions, one pre-LN attention block
whose only query is the newest tick, one pre-LN GELU MLP, final LN, a
linear head plus a linear skip from the newest tick's raw features) and
from ``docs``' ratio attribution, and independent of both: it imports
nothing of ``kepler_tpu``, takes no weights, tables or features the program
has made, and computes in float32 throughout (NumPy's float32 matmul is
true float32). It reads the same seeded inputs as the generator
(``fleetgen.Fleet.state``) and the same seeded parameters (``make_params``).

``quantize`` puts a lower precision in the matmuls' operands, with float32
accumulation, as the program does it: ``"bf16"`` is the precision the
configurations state (what the chip computes; used by the tests to stand in
for the program), ``"fp8"`` (e4m3) is the control, the step below it, which
the comparison has to fail.
"""

from __future__ import annotations

import numpy as np

from chipbench.fleetgen import Fleet

F32 = np.float32
N_FEATURES = 7
LN_EPS = 1e-6


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


# -- precisions ---------------------------------------------------------------


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even) and back, in integer arithmetic."""
    bits = np.ascontiguousarray(x, F32).view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return rounded.view(F32)


def _fp8(x: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3 (3 mantissa bits; saturating at 448) and back."""
    import ml_dtypes

    x = np.clip(np.asarray(x, F32), -448.0, 448.0)
    return x.astype(ml_dtypes.float8_e4m3fn).astype(F32)


QUANTIZERS = {None: None, "f32": None, "bf16": _bf16, "fp8": _fp8}


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


# -- the estimator --------------------------------------------------------------


def features(cpu, node_cpu, ratio, dt: float) -> np.ndarray:
    """One tick's feature rows: cpu [..., w], node_cpu and ratio [...] →
    f32 [..., w, 7]: cpu seconds, share of the node's, the node's usage
    ratio, dt, cores in use, 1, log1p(node cpu)."""
    cpu = np.asarray(cpu, F32)
    d = np.asarray(node_cpu, F32)[..., None]
    share = np.divide(cpu, d, out=np.zeros_like(cpu), where=d > 0)
    ones = np.ones_like(cpu)
    return np.stack([
        cpu, share, ones * np.asarray(ratio, F32)[..., None],
        ones * F32(dt), cpu / F32(dt), ones,
        ones * np.log1p(np.maximum(d, F32(0.0))),
    ], axis=-1).astype(F32)


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


class Reference:
    """Expected publications of one fleet, round by round."""

    def __init__(self, fleet: Fleet, params: dict, history: int,
                 quantize: str | None = None) -> None:
        self.fleet, self.params, self.t = fleet, params, int(history)
        self.quantize = quantize
        # the attribution product is float32 in the program; its control is
        # the step below float32, bf16, whatever the estimator's control is
        self.ratio_quantize = "bf16" if quantize == "fp8" else None
        self._states: dict[int, tuple] = {}

    def state(self, r: int):
        """(round ``r``'s state, its feature rows [n, w, 7])."""
        hit = self._states.get(r)
        if hit is None:
            st = self.fleet.state(r)
            feats = features(st.cpu, self.fleet.node_cpu(st), st.ratio,
                             self.fleet.dt)
            hit = self._states[r] = (st, feats)
            if len(self._states) > 2 * self.t + 8:
                self._states.pop(next(iter(self._states)))
        return hit

    def history(self, nodes: np.ndarray, r: int) -> tuple[
            np.ndarray, np.ndarray]:
        """(feature history [len(nodes), w, t, 7], its valid ticks
        [len(nodes), w, t]) after round ``r``: the rounds, oldest first, in
        which the pod that holds the slot in round ``r`` reported, at most
        ``t`` of them, at the front."""
        nodes = np.asarray(nodes, np.intp)
        if r < 0:
            raise ValueError("no round yet")
        born = self.state(r)[0].born[nodes]  # [m, w]
        first = np.maximum(born, r - self.t + 1)
        count = (r - first + 1) * self.fleet.valid[nodes]  # ticks held
        m, w = born.shape
        hist = np.zeros((m, w, self.t, N_FEATURES), F32)
        tick = np.arange(self.t)
        for rr in range(max(0, r - self.t + 1), r + 1):
            feats = self.state(rr)[1][nodes]  # [m, w, 7]
            pos = rr - first  # where this round sits in each pod's history
            here = (pos >= 0) & (count > 0)
            hist[here, pos[here]] = feats[here]
        return hist, tick[None, None, :] < count[:, :, None]

    def model_nodes(self, nodes, r: int) -> np.ndarray:
        """Watts [len(nodes), w, z] the model nodes publish after round r
        (0 where a node has no pod), in blocks of nodes so that it fits."""
        nodes = np.asarray(nodes, np.intp)
        w = self.fleet.w
        block = max(1, 60_000 // (w * self.t))  # ~30 MB an activation
        z = len(self.fleet.zones)
        out = np.zeros((len(nodes), w, z), F32)
        for lo in range(0, len(nodes), block):
            part = nodes[lo:lo + block]
            hist, t_valid = self.history(part, r)
            watts = temporal_watts(
                self.params, hist.reshape(-1, self.t, N_FEATURES),
                t_valid.reshape(-1, self.t),
                self.quantize).reshape(len(part), w, z)
            out[lo:lo + block] = np.where(
                self.fleet.valid[part][:, :, None], watts, F32(0.0))
        return out

    def ratio_nodes(self, nodes, r: int):
        """(pod watts [len(nodes), w, z], node watts [len(nodes), z]) of
        ratio nodes after round r: a zone's energy over dt is the node's
        power; its active part, the usage ratio's share, is divided over
        the pods by their share of the node's cpu time."""
        nodes = np.asarray(nodes, np.intp)
        st = self.state(r)[0]
        f64 = np.float64
        energy = np.where(self.fleet.zone_valid[nodes], st.zone[nodes],
                          0.0).astype(f64)
        quant = QUANTIZERS[self.ratio_quantize]
        if quant is not None:  # the control of this path: bf16 operands
            energy = quant(energy.astype(F32)).astype(f64)
        node_w = energy / self.fleet.dt * 1e-6
        active_w = node_w * np.clip(st.ratio[nodes].astype(f64), 0.0,
                                    1.0)[:, None]
        cpu = np.where(self.fleet.valid[nodes], st.cpu[nodes],
                       0.0).astype(f64)
        total = self.fleet.node_cpu(st)[nodes].astype(f64)[:, None]
        share = np.divide(cpu, total, out=np.zeros_like(cpu),
                          where=total > 0)
        if quant is not None:
            share = quant(share.astype(F32)).astype(f64)
            active_w = quant(active_w.astype(F32)).astype(f64)
        return share[:, :, None] * active_w[:, None, :], node_w
