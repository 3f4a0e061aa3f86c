"""Pallas TPU kernel for the served temporal estimator's trunk.

``models.temporal._last_query_trunk`` as one kernel: each grid step takes
a block of history rows (``[Bb, T·F]`` f32 and their ``t_valid``) and
writes their pooled hidden states (``[Bb, D]`` f32). Every intermediate
— the embedded ticks, their layer norm, K and V, the scores, the
probabilities — stays in VMEM; the XLA path writes and rereads each as an
f32 ``[B, T, D]`` (or ``[B, T, H, Dh]`` relayout) in HBM. The parameters
sit whole in VMEM (under 1 MB in bf16).

Step for step it keeps the XLA path's precisions and semantics:

- matmul operands bf16, accumulators f32 (``models.nn.acc_matmul``);
  layer norms, residuals, softmax and GELU in f32; K, V, the query and
  the probabilities rounded to bf16 where the XLA path casts them;
- ``last = max(Σ t_valid − 1, 0)`` (a count, not the last valid index),
  the causal mask ``t ≤ last`` with ``t_valid``, the −1e30 fill, and
  zero attention for a row with no valid tick.

Layout. The in-projection is one matmul of the row ``[Bb, T·F]`` by the
block-diagonal ``kron(I_T, in_proj)`` ``[T·F, T·D]``, a tick's slice at a
time, so every tick lands on its own 128-lane tile (the zeros add
nothing to a sum). Heads are 32-lane segments of that tile. A score is
the f32 sum of a head's bf16×bf16 products: each product is exact in f32
and splits exactly into two bf16 parts (16 significant bits at most),
whose segment sums one 0/1 matmul forms and spreads back over the head's
lanes. So the softmax runs lane-wise on spread scores, and the value
contraction is the probabilities times V summed over the ticks.

CPU tests run ``interpret=True``; on TPU it compiles with Mosaic. The
served program runs it under ``shard_map`` over the node axis
(``parallel.aggregator_core.make_temporal_fleet_program``), so each
device runs it on its own rows. It has no VJP: training differentiates
the XLA trunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kepler_tpu.models.nn import layer_norm
from kepler_tpu.models.temporal import N_HEADS, TemporalParams

#: rows a grid step runs on. Measured on one TPU v5e (PERF.md section 6):
#: 512 runs a window's 65,544 rows in 3.0 ms, 128 in 4.9, but 512 compiles
#: some 7 s longer, and set-up compiles the program once a bucket of rows;
#: at 128 the program compiles in about the XLA trunk's time
BLOCK_ROWS = 128

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_MASKED = -1e30  # the XLA path's fill: finite, so no row's softmax is NaN


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=_F32)


def _trunk_kernel(hist_ref, tv_ref, w_in_ref, pos_ref, ln1_ref, wq_ref,
                  wkv_ref, wo_ref, ln2_ref, w0_ref, b0_ref, w1_ref, b1_ref,
                  lnf_ref, seg_ref, out_ref, *, t: int, d: int, dh: int):
    """One block of rows: ``[Bb, T·F]`` history → ``[Bb, D]`` pooled."""
    hist = hist_ref[...].astype(_BF16)  # [Bb, T·F]
    tv = tv_ref[...]  # [Bb, T] f32, 1 where the tick is valid
    count = jnp.sum(tv, axis=1, keepdims=True)  # [Bb, 1]
    last = jnp.maximum(count - 1.0, 0.0)
    ln1_s, ln1_b = ln1_ref[0:1, :], ln1_ref[1:2, :]
    wkv = wkv_ref[...]

    # pass 1, tick by tick: embed, mask, LN; K and V; the last tick's x, y
    x_last = y_last = None
    ks, vs = [], []
    for i in range(t):
        lanes = slice(i * d, (i + 1) * d)
        x = _dot(hist, w_in_ref[:, lanes]) + pos_ref[:, lanes]
        x = jnp.where(tv[:, i:i + 1] > 0.5, x, 0.0)
        y = layer_norm(x, ln1_s, ln1_b)
        kv = _dot(y.astype(_BF16), wkv)  # [Bb, 2D]
        ks.append(kv[:, :d].astype(_BF16).astype(_F32))
        vs.append(kv[:, d:].astype(_BF16).astype(_F32))
        if i == 0:
            x_last, y_last = x, y
        else:
            at = last == float(i)
            x_last = jnp.where(at, x, x_last)
            y_last = jnp.where(at, y, y_last)

    # pass 2: the one query against every tick's key, spread over lanes
    q = _dot(y_last.astype(_BF16), wq_ref[...]).astype(_BF16).astype(_F32)
    seg = seg_ref[...]  # [D, D] bf16: 1 where two lanes share a head
    root_dh = np.sqrt(np.float32(dh))
    scores = []
    for i in range(t):
        prod = q * ks[i]  # exact: bf16 × bf16 in f32
        hi = prod.astype(_BF16)
        lo = (prod - hi.astype(_F32)).astype(_BF16)  # exact remainder
        s = (_dot(hi, seg) + _dot(lo, seg)) / root_dh
        keep = (tv[:, i:i + 1] > 0.5) & (float(i) <= last)
        scores.append(jnp.where(keep, s, _MASKED))
    top = functools.reduce(jnp.maximum, scores)
    exps = [jnp.exp(s - top) for s in scores]
    total = functools.reduce(jnp.add, exps)
    any_valid = count > 0.5
    attn = None
    for e, v in zip(exps, vs):
        p = jnp.where(any_valid, e / total, 0.0).astype(_BF16).astype(_F32)
        attn = p * v if attn is None else attn + p * v

    x_last = x_last + _dot(attn.astype(_BF16), wo_ref[...])
    y = layer_norm(x_last, ln2_ref[0:1, :], ln2_ref[1:2, :])
    y = jax.nn.gelu(_dot(y.astype(_BF16), w0_ref[...]) + b0_ref[...])
    x_last = x_last + _dot(y.astype(_BF16), w1_ref[...]) + b1_ref[...]
    out_ref[...] = layer_norm(x_last, lnf_ref[0:1, :], lnf_ref[1:2, :])


def _kernel_operands(params: TemporalParams, t: int) -> list[jax.Array]:
    """The parameters as the kernel reads them: matmul weights rounded to
    bf16 (as ``acc_matmul`` rounds them), vectors as f32 rows."""
    d = params["in_proj"].shape[1]
    dh = d // N_HEADS
    head = np.arange(d) // dh
    seg = jnp.asarray(head[:, None] == head[None, :], _BF16)
    w_in = jnp.kron(jnp.eye(t, dtype=_F32), params["in_proj"])

    def rows(*names):
        return jnp.stack([params[n] for n in names]).astype(_F32)

    return [
        w_in.astype(_BF16),  # [T·F, T·D]
        params["pos_emb"][:t].reshape(1, t * d),
        rows("ln1_scale", "ln1_bias"),
        params["wq"].astype(_BF16),
        jnp.concatenate([params["wk"], params["wv"]], axis=1).astype(_BF16),
        params["wo"].astype(_BF16),
        rows("ln2_scale", "ln2_bias"),
        params["w_mlp0"].astype(_BF16),
        params["b_mlp0"].reshape(1, -1),
        params["w_mlp1"].astype(_BF16),
        params["b_mlp1"].reshape(1, -1),
        rows("ln_f_scale", "ln_f_bias"),
        seg,
    ]


def last_query_trunk_pallas(
    params: TemporalParams,
    feat_hist: jax.Array,  # f32 [B, T, F]
    t_valid: jax.Array,  # bool [B, T]
    compute_dtype: jnp.dtype = _BF16,
    *,
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """→ pooled hidden f32 [B, D]: ``_last_query_trunk`` in one kernel
    (bf16 compute only; a row count that is not a multiple of the block
    leaves the last block part full)."""
    if jnp.dtype(compute_dtype) != jnp.dtype(_BF16):
        raise ValueError(f"the fused trunk computes in bfloat16, "
                         f"not {jnp.dtype(compute_dtype)}")
    b, t, f = feat_hist.shape
    d = params["in_proj"].shape[1]
    operands = _kernel_operands(params, t)
    rows = min(block_rows, -(-b // 8) * 8)

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0, 0))

    kernel = functools.partial(_trunk_kernel, t=t, d=d, dh=d // N_HEADS)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(b, rows),),
        in_specs=[pl.BlockSpec((rows, t * f), lambda i: (i, 0)),
                  pl.BlockSpec((rows, t), lambda i: (i, 0))]
        + [whole(a) for a in operands],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="last_query_trunk",  # the device op's name in a profile
    )(feat_hist.reshape(b, t * f).astype(_F32), t_valid.astype(_F32),
      *operands)
