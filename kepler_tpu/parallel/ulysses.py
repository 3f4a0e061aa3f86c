"""Ulysses-style (all-to-all) sequence parallelism for attention.

The second classic context-parallel scheme beside the ring
(`kepler_tpu.parallel.ring`): instead of rotating K/V blocks around the
mesh, one ``all_to_all`` re-partitions the sharded SEQUENCE axis into a
sharded HEAD axis — each device then runs ordinary dense attention over
the FULL sequence for its subset of heads, and a second ``all_to_all``
restores sequence sharding (DeepSpeed-Ulysses; see PAPERS.md).

Trade-offs vs the ring, as a selection guide:

- Ulysses moves ``O(T·D)`` activations twice per layer through two
  all_to_alls and then attends densely — ONE exchange, latency-bound;
  the ring moves K/V ``P−1`` times in ``P`` overlap-able steps —
  bandwidth-spread, and never materializes full-T anything per device.
- Ulysses parallelism degree is capped by the head count (H must divide
  by the mesh axis; the temporal model has 4 heads); the ring scales to
  any T-divisor.
- Per-device attention memory: Ulysses holds full T for H/P heads
  (``O(T²·H/P)`` scores unless fused); the ring holds one T/P block
  pair at a time.

Both plug into the SAME ``attention_fn`` seam of the temporal trunk and
are verified equivalent to dense single-device attention (and to each
other) in ``tests/test_ulysses.py`` / ``tests/test_ring.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kepler_tpu.ops.attention import full_attention
from kepler_tpu.parallel.ring import SEQ_AXIS


def _ulysses_shard(q, k, v, t_valid, *, axis_name: str, causal: bool,
                   compute_dtype) -> jax.Array:
    """Per-shard body: [B, T/P, H, Dh] in/out, full-T attention inside."""
    # time-gather / head-scatter: [B, T/P, H, Dh] → [B, T, H/P, Dh]
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    tv = lax.all_gather(t_valid, axis_name, axis=1, tiled=True)  # [B, T]
    out = full_attention(qg, kg, vg, causal=causal, t_valid=tv,
                         compute_dtype=compute_dtype)
    # head-gather / time-scatter back: [B, T, H/P, Dh] → [B, T/P, H, Dh]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention_shardmap(
    mesh: Mesh,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
):
    """Un-jitted shard-mapped Ulysses kernel ``(q, k, v, t_valid) → out``.

    The composable form (same contract as
    :func:`~kepler_tpu.parallel.ring.ring_attention_shardmap`): inputs
    ``[B, T, H, Dh]`` with T sharded over ``axis_name``; H must divide
    by the mesh's ``axis_name`` size.
    """
    n = mesh.shape[axis_name]
    body = functools.partial(_ulysses_shard, axis_name=axis_name,
                             causal=causal, compute_dtype=compute_dtype)

    def checked(q, k, v, t_valid):
        if q.shape[2] % n:
            raise ValueError(
                f"Ulysses needs heads ({q.shape[2]}) divisible by the "
                f"'{axis_name}' mesh size ({n}); use the ring for more "
                "parallelism than heads")
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, axis_name), P(None, axis_name),
                      P(None, axis_name), P(None, axis_name)),
            out_specs=P(None, axis_name),
        )(q, k, v, t_valid)

    return checked


def make_ulysses_attention(
    mesh: Mesh,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
):
    """→ jitted ``(q, k, v, t_valid) → out`` with T sharded over the mesh
    and heads re-partitioned internally via all_to_all."""
    seq = NamedSharding(mesh, P(None, axis_name))
    shard = ulysses_attention_shardmap(mesh, axis_name=axis_name,
                                       causal=causal,
                                       compute_dtype=compute_dtype)
    return jax.jit(shard, in_shardings=(seq, seq, seq, seq),
                   out_shardings=seq)


def make_ulysses_temporal_program(
    mesh: Mesh,
    *,
    axis_name: str = SEQ_AXIS,
    clamp: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
):
    """Temporal estimator served with Ulysses context parallelism —
    the all-to-all twin of ``sequence.make_temporal_program``."""
    from kepler_tpu.models.temporal import predict_temporal

    hist = NamedSharding(mesh, P(None, axis_name))
    rep = NamedSharding(mesh, P())
    attn = ulysses_attention_shardmap(mesh, axis_name=axis_name,
                                      causal=True,
                                      compute_dtype=compute_dtype)

    def fn(params, feat_hist, workload_valid, t_valid):
        return predict_temporal(params, feat_hist, workload_valid, t_valid,
                                clamp=clamp, compute_dtype=compute_dtype,
                                attention_fn=attn)

    return jax.jit(fn, in_shardings=(rep, hist, rep, hist),
                   out_shardings=rep)
