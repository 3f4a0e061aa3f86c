"""Temporal power estimator: causal attention over feature history.

The reference attributes power from the *last* tick's deltas only
(`internal/monitor/process.go:123-145` — a single ratio per window). A
single tick is noisy: procfs sampling jitter and RAPL wraparound leave
per-window spikes that Prometheus rate() can only smooth after the fact.
This estimator instead conditions on a **history window** of the last T
ticks per workload (`kepler_tpu.monitor.history` maintains the window) and
predicts the current-tick watts from the whole trajectory — the learned
analog of a cross-tick smoother, and the subsystem that introduces the
sequence axis (SURVEY §5: "if per-workload feature history windows are
added … a time axis appears").

Architecture (shaped for the MXU — all dims lane-width multiples):

    [.., T, F] → in-proj F→D → +learned positional embedding
               → pre-LN causal self-attention (H heads) + residual
               → pre-LN GELU MLP (D→4D→D) + residual
               → LN → head D→Z on the LAST timestep → watts [.., Z]

Short windows (serving default, T≤128) evaluate dense attention on one
chip; long windows shard T over the ``seq`` mesh axis and run ring
attention (`kepler_tpu.parallel.ring`) — same maths, verified equivalent
in tests/test_ring.py.
"""

from __future__ import annotations

from typing import TypedDict

import jax
import jax.numpy as jnp

from kepler_tpu.models.features import NUM_FEATURES
from kepler_tpu.models.nn import acc_matmul, glorot, layer_norm
from kepler_tpu.ops.attention import full_attention


class TemporalParams(TypedDict):
    in_proj: jax.Array  # [F, D]
    pos_emb: jax.Array  # [T_max, D]
    ln1_scale: jax.Array  # [D]
    ln1_bias: jax.Array  # [D]
    wq: jax.Array  # [D, D]
    wk: jax.Array  # [D, D]
    wv: jax.Array  # [D, D]
    wo: jax.Array  # [D, D]
    ln2_scale: jax.Array  # [D]
    ln2_bias: jax.Array  # [D]
    w_mlp0: jax.Array  # [D, 4D]
    b_mlp0: jax.Array  # [4D]
    w_mlp1: jax.Array  # [4D, D]
    b_mlp1: jax.Array  # [D]
    ln_f_scale: jax.Array  # [D]
    ln_f_bias: jax.Array  # [D]
    w_head: jax.Array  # [D, Z]
    b_head: jax.Array  # [Z]
    w_skip: jax.Array  # [F, Z] wide path from the CURRENT tick's features


N_HEADS = 4


def init_temporal(
    key: jax.Array,
    n_zones: int,
    d_model: int = 128,
    t_max: int = 128,
    n_features: int = NUM_FEATURES,
) -> TemporalParams:
    ks = jax.random.split(key, 8)
    d4 = 4 * d_model
    return TemporalParams(
        in_proj=glorot(ks[0], (n_features, d_model)),
        pos_emb=jax.random.normal(ks[1], (t_max, d_model), jnp.float32) * 0.02,
        ln1_scale=jnp.ones((d_model,), jnp.float32),
        ln1_bias=jnp.zeros((d_model,), jnp.float32),
        wq=glorot(ks[2], (d_model, d_model)),
        wk=glorot(ks[3], (d_model, d_model)),
        wv=glorot(ks[4], (d_model, d_model)),
        wo=glorot(ks[5], (d_model, d_model)),
        ln2_scale=jnp.ones((d_model,), jnp.float32),
        ln2_bias=jnp.zeros((d_model,), jnp.float32),
        w_mlp0=glorot(ks[6], (d_model, d4)),
        b_mlp0=jnp.zeros((d4,), jnp.float32),
        w_mlp1=glorot(ks[7], (d4, d_model)),
        b_mlp1=jnp.zeros((d_model,), jnp.float32),
        ln_f_scale=jnp.ones((d_model,), jnp.float32),
        ln_f_bias=jnp.zeros((d_model,), jnp.float32),
        w_head=jnp.zeros((d_model, n_zones), jnp.float32),
        b_head=jnp.zeros((n_zones,), jnp.float32),
        w_skip=jnp.zeros((n_features, n_zones), jnp.float32),
    )


def temporal_trunk(
    params: TemporalParams,
    feat_hist: jax.Array,  # f32 [B, T, F]
    t_valid: jax.Array,  # bool [B, T]
    attention_fn=None,  # (q, k, v, t_valid) → out; default dense causal
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Shared trunk → hidden states f32 [B, T, D].

    ``attention_fn`` is the seam where ring attention plugs in: the
    sequence-parallel program passes the shard-mapped ring kernel, serving
    passes nothing and gets dense causal attention.
    """
    b, t, _ = feat_hist.shape
    d = params["in_proj"].shape[1]
    h = N_HEADS
    cd = compute_dtype

    # half operands, f32 accumulators (KTL120 dtype-flow): every matmul
    # goes through acc_matmul; residual/bias/softmax arithmetic stays f32
    x = acc_matmul(feat_hist, params["in_proj"], cd)
    x = x + params["pos_emb"][:t]
    x = jnp.where(t_valid[..., None], x, 0.0)

    # -- attention block (pre-LN, residual) --------------------------------
    y = layer_norm(x, params["ln1_scale"], params["ln1_bias"])
    q = acc_matmul(y, params["wq"], cd).reshape(b, t, h, d // h)
    k = acc_matmul(y, params["wk"], cd).reshape(b, t, h, d // h)
    v = acc_matmul(y, params["wv"], cd).reshape(b, t, h, d // h)
    if attention_fn is None:
        attn = full_attention(q, k, v, causal=True, t_valid=t_valid,
                              compute_dtype=cd)
    else:
        attn = attention_fn(q, k, v, t_valid)
    attn = attn.reshape(b, t, d)
    x = x + acc_matmul(attn, params["wo"], cd)

    # -- MLP block ---------------------------------------------------------
    y = layer_norm(x, params["ln2_scale"], params["ln2_bias"])
    y = jax.nn.gelu(acc_matmul(y, params["w_mlp0"], cd)
                    + params["b_mlp0"])
    x = x + acc_matmul(y, params["w_mlp1"], cd) + params["b_mlp1"]

    return layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def _last_query_trunk(
    params: TemporalParams,
    feat_hist: jax.Array,  # f32 [B, T, F]
    t_valid: jax.Array,  # bool [B, T]
    compute_dtype: jnp.dtype,
) -> jax.Array:
    """Dense-serving fast path → pooled hidden f32 [B, D].

    Only the LAST valid timestep feeds the head, so the attention block
    needs one query row per sequence (K/V still span the window): at the
    last valid position the causal mask plus right-padding reduces to
    ``t_valid`` itself. Cuts the trunk's matmul FLOPs ~4× vs computing
    all T positions (Q/O/MLP shrink by T; K/V stay) — same math as
    ``temporal_trunk`` + take_along_axis, verified in tests.
    """
    b, t, _ = feat_hist.shape
    d = params["in_proj"].shape[1]
    h = N_HEADS
    dh = d // h
    cd = compute_dtype

    # the scopes reach the HLO's op metadata: a profile of the served
    # program reads by stage
    with jax.named_scope("history_embed"):
        x = acc_matmul(feat_hist, params["in_proj"], cd)
        x = x + params["pos_emb"][:t]
        x = jnp.where(t_valid[..., None], x, 0.0)
        last = jnp.maximum(jnp.sum(t_valid, axis=-1) - 1,
                           0).astype(jnp.int32)
        y = layer_norm(x, params["ln1_scale"], params["ln1_bias"])
    with jax.named_scope("last_query_attention"):
        y_last = jnp.take_along_axis(y, last[:, None, None], axis=1)[:, 0]
        q = acc_matmul(y_last, params["wq"], cd).reshape(b, h, dh)
    with jax.named_scope("kv_proj"):
        k = acc_matmul(y, params["wk"], cd).reshape(b, t, h, dh)
        v = acc_matmul(y, params["wv"], cd).reshape(b, t, h, dh)
    with jax.named_scope("last_query_attention"):
        scores = jnp.einsum("bhd,bthd->bht", q.astype(cd), k.astype(cd),
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.asarray(dh, jnp.float32))
        # finite mask value (not -inf): an all-invalid window must yield
        # 0 attention, not softmax(-inf…)=NaN — parity with
        # full_attention's l_safe clamping for fully-masked rows. The
        # causal constraint (position ≤ last) keeps this path exact on
        # gapped t_valid masks, not just the contiguous right-padded
        # prefixes history windows produce — full parity with the
        # all-positions trunk.
        causal = jnp.arange(t, dtype=jnp.int32)[None, :] <= last[:, None]
        scores = jnp.where((t_valid & causal)[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        any_valid = t_valid.any(axis=-1)
        probs = jnp.where(any_valid[:, None, None], probs, 0.0)
        attn = jnp.einsum("bht,bthd->bhd", probs.astype(cd), v.astype(cd),
                          preferred_element_type=jnp.float32).reshape(b, d)

        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x_last = x_last + acc_matmul(attn, params["wo"], cd)

    with jax.named_scope("mlp"):
        y = layer_norm(x_last, params["ln2_scale"], params["ln2_bias"])
        y = jax.nn.gelu(acc_matmul(y, params["w_mlp0"], cd)
                        + params["b_mlp0"])
        x_last = (x_last + acc_matmul(y, params["w_mlp1"], cd)
                  + params["b_mlp1"])
        return layer_norm(x_last, params["ln_f_scale"], params["ln_f_bias"])


def predict_temporal(
    params: TemporalParams,
    feat_hist: jax.Array,  # f32 [..., W, T, F]
    workload_valid: jax.Array,  # bool [..., W]
    t_valid: jax.Array | None = None,  # bool [..., W, T]
    clamp: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    attention_fn=None,  # override for sequence-parallel ring attention
    last_query_fn=None,  # a kernel in _last_query_trunk's place
) -> jax.Array:
    """→ watts f32 [..., W, Z] predicted from each workload's history.

    Leading axes flatten into the attention batch; the LAST valid timestep's
    hidden state feeds the head (ragged histories right-pad, so that is the
    last ``t_valid`` position, falling back to position 0 when empty).
    Dense serving (no ``attention_fn``) uses the single-query fast path;
    a custom attention_fn (ring attention over a sharded T axis) keeps the
    full-sequence trunk. ``last_query_fn`` (same arguments and result as
    ``_last_query_trunk``) replaces the fast path: the served program passes
    the fused kernel of ``ops.pallas_temporal`` there.
    """
    lead = feat_hist.shape[:-2]
    t, f = feat_hist.shape[-2:]
    x = feat_hist.reshape(-1, t, f)
    tv = (jnp.ones(x.shape[:2], bool) if t_valid is None
          else t_valid.reshape(-1, t))
    last = jnp.maximum(jnp.sum(tv, axis=-1) - 1, 0).astype(jnp.int32)
    if attention_fn is None:
        trunk = last_query_fn or _last_query_trunk
        pooled = trunk(params, x, tv, compute_dtype)
    else:
        hidden = temporal_trunk(params, x, tv, attention_fn=attention_fn,
                                compute_dtype=compute_dtype)
        pooled = jnp.take_along_axis(
            hidden, last[:, None, None], axis=1)[:, 0]
    # wide-and-deep: the current (= last valid) tick's raw features carry
    # the first-order linear power signal in f32; the attention trunk adds
    # the history-conditioned correction (see predict_mlp's w_skip note)
    with jax.named_scope("head"):
        feat_last = jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]
        watts = (pooled @ params["w_head"]
                 + feat_last.astype(jnp.float32) @ params["w_skip"]
                 + params["b_head"])
        watts = watts.reshape(*lead, -1)
        if clamp:
            watts = jnp.maximum(watts, 0.0)
        return jnp.where(workload_valid[..., None], watts, 0.0)
