"""Ring attention: context parallelism over the history time axis.

The reference has no sequences at all (SURVEY §5 — its signals are scalar
per-tick deltas). The time axis appears in this framework when the
estimator consumes per-workload feature *history* windows
(`kepler_tpu.models.temporal`): a fleet window is ``[B, T, F]`` where ``T``
can grow to hours of ticks. For long windows the KV working set no longer
fits one chip's HBM, so the sequence axis shards across devices and
attention runs as a **ring**: each device keeps its query block resident
and rotates K/V blocks around the mesh axis with ``ppermute`` (one
neighbour hop per step, riding ICI), accumulating flash-attention-style
online-softmax partials (`kepler_tpu.ops.attention`). No device ever
materialises the full ``[T, T]`` score matrix or the full K/V sequence,
and after ``n`` steps the telescoped merge equals exact softmax attention
— verified against the dense reference in ``tests/test_ring.py``.

Built on ``shard_map`` so the collective schedule is explicit; the
per-block compute inside is plain jnp, which XLA fuses and tiles onto the
MXU (bf16 matmuls, f32 accumulators).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kepler_tpu.ops.attention import (
    _NEG_INF,
    block_attn,
    full_attention,
    merge_blocks,
    stats_to_out,
)

SEQ_AXIS = "seq"

__all__ = ["SEQ_AXIS", "full_attention", "make_ring_attention",
           "ring_attention_shardmap"]


def _ring_shard(q, k, v, t_valid, *, axis_name, causal, compute_dtype,
                backend="einsum"):
    """Per-device body: local q block resident, KV ring-rotates n times."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    q_pos = idx * t_loc + jnp.arange(t_loc)  # global positions of my queries

    # zeros-initialised carries must be marked device-varying over the ring
    # axis up front or the fori_loop carry types mismatch (shard_map vma rule)
    def vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")
    o = vary(jnp.zeros((b, t_loc, h, d), jnp.float32))
    m = vary(jnp.full((b, h, t_loc), _NEG_INF, jnp.float32))
    l = vary(jnp.zeros((b, h, t_loc), jnp.float32))  # noqa: E741
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_partials(k, v, kv_val, src):
        if backend == "pallas":
            from kepler_tpu.ops.pallas_attention import flash_block_pallas

            # positions reach the kernel as scalar block starts; the
            # causal mask is rebuilt from iota inside VMEM — the [T, T]
            # mask never exists in HBM
            return flash_block_pallas(
                q, k, v, kv_val, idx * t_loc, src * t_loc, causal=causal,
                compute_dtype=compute_dtype)
        kv_pos = src * t_loc + jnp.arange(t_loc)
        mask = jnp.broadcast_to(kv_val[:, None, None, :],
                                (b, 1, t_loc, t_loc))
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        return block_attn(q, k, v, mask, scale, compute_dtype)

    def step(s, carry):
        o, m, l, k, v, kv_val = carry  # noqa: E741
        src = (idx - s) % n  # shard this KV block originated from
        pv, m_blk, l_blk = block_partials(k, v, kv_val, src)
        o, m, l = merge_blocks(o, m, l, pv, m_blk, l_blk)  # noqa: E741
        # rotate KV (+validity) one hop; after n steps it is home again
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kv_val = jax.lax.ppermute(kv_val, axis_name, perm)
        return o, m, l, k, v, kv_val

    o, m, l, _, _, _ = jax.lax.fori_loop(  # noqa: E741
        0, n, step, (o, m, l, k, v, t_valid))
    l_safe = jnp.maximum(l, 1e-30)
    return (o / stats_to_out(l_safe)).astype(q.dtype)


def ring_attention_shardmap(
    mesh: Mesh,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    backend: str = "einsum",
):
    """Un-jitted shard-mapped ring kernel ``(q, k, v, t_valid) → out``.

    The composable form: call it inside a larger jitted program (the
    sequence-parallel temporal estimator does) or jit it directly via
    :func:`make_ring_attention`.
    """
    body = functools.partial(_ring_shard, axis_name=axis_name,
                             causal=causal, compute_dtype=compute_dtype,
                             backend=backend)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name),
                  P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        # pallas_call defeats the varying-axes checker (same caveat as
        # aggregator_core.shard_by_node)
        check_vma=backend != "pallas",
    )


def make_ring_attention(
    mesh: Mesh,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    backend: str = "einsum",
):
    """→ jitted ``(q, k, v, t_valid) → out`` with T sharded over the mesh.

    Inputs are ``[B, T, H, D]`` (+ ``t_valid`` bool ``[B, T]``); T must
    divide by the ``axis_name`` mesh size. Output shards like q.
    ``backend="pallas"`` computes each block partial with the fused VMEM
    kernel (`ops.pallas_attention`); "einsum" lets XLA fuse the jnp path.
    """
    seq = NamedSharding(mesh, P(None, axis_name))
    shard = ring_attention_shardmap(mesh, axis_name=axis_name, causal=causal,
                                    compute_dtype=compute_dtype,
                                    backend=backend)
    return jax.jit(shard, in_shardings=(seq, seq, seq, seq),
                   out_shardings=seq)
