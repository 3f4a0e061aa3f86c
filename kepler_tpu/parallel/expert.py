"""Expert parallelism: all_to_all dispatch for the MoE estimator.

Dense MoE (`kepler_tpu.models.moe.predict_moe`) runs every expert on every
row — fine for a handful of tiny experts on one chip, wasteful once the
fleet has many node types or the per-type models grow. This module shards
the expert axis over devices and moves **rows to their expert** instead:

    rows [B, F], experts sharded E/n per device
    → top-1 route (explicit node-type id, or learned gate)
    → one-hot dispatch [B_loc, E, C]  (capacity C, cumsum positions)
    → all_to_all: each device receives the rows routed to ITS experts
    → batched expert MLP on local experts only
    → all_to_all back, combine with gate weight

The two collectives are the classic MoE all_to_all pair (Switch/GShard
dispatch–combine, cf. PAPERS.md), riding ICI inside one shard_map; every
other op is a batched einsum. With explicit routing the EP result is
bit-comparable to dense routing — `tests/test_expert.py` asserts it.

Default capacity is lossless (C = per-device row count, covering the
worst case of every local row choosing one expert); pass
``capacity_factor`` < 1 for Switch-style bounded buffers where overflow
rows fall back to zero watts (callers then blend with ratio attribution,
the same degraded-zone philosophy as the reference's skip-on-error,
`internal/monitor/node.go:39-44`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kepler_tpu.models.moe import MoEParams, expert_forward, gate_logits

EXPERT_AXIS = "expert"


def _ep_shard(params, x, expert_id, gate_prob, *, axis_name, capacity,
              compute_dtype):
    """Per-device body. x [B_loc, F]; params hold E_loc local experts."""
    n = jax.lax.psum(1, axis_name)
    e_loc = params["w0"].shape[0]
    e = e_loc * n  # global expert count
    b_loc = x.shape[0]
    c = capacity

    # positions within each expert's capacity buffer (over local rows)
    onehot = jax.nn.one_hot(expert_id, e, dtype=jnp.int32)  # [B_loc, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # [B_loc, E], -1 if unrouted
    keep = (pos >= 0) & (pos < c)
    dispatch = (jax.nn.one_hot(pos.clip(0), c, dtype=jnp.float32)
                * keep[..., None])  # [B_loc, E, C]

    # group rows per global expert, then exchange: axis 0 = destination dev
    ex_in = jnp.einsum("bec,bf->ecf", dispatch, x)  # [E, C, F]
    ex_in = ex_in.reshape(n, e_loc, c, -1)
    ex_in = jax.lax.all_to_all(ex_in, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)  # [n(src), E_loc, C, F]
    ex_in = ex_in.transpose(1, 0, 2, 3).reshape(e_loc, n * c, -1)

    ex_out = expert_forward(params, ex_in, compute_dtype)  # [E_loc, n*C, Z]

    z = ex_out.shape[-1]
    ex_out = ex_out.reshape(e_loc, n, c, z).transpose(1, 0, 2, 3)
    ex_out = jax.lax.all_to_all(ex_out, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    ex_out = ex_out.reshape(e, c, z)  # [E, C, Z], rows back home

    combine = dispatch * gate_prob[:, None, None]  # [B_loc, E, C]
    return jnp.einsum("bec,ecz->bz", combine, ex_out)  # [B_loc, Z]


def make_expert_parallel_moe(
    mesh: Mesh,
    *,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 1.0,
    rows_per_device: int | None = None,
    compute_dtype: jnp.dtype = jnp.bfloat16,
):
    """→ jitted ``(params, features[B,F], expert_id[B], gate_prob[B]) → [B,Z]``.

    ``B`` must divide by the ``axis_name`` mesh size; the global expert
    count must divide by it too (params shard on their leading E axis).
    ``expert_id`` is the per-row routing decision (node type, or
    ``top1_route``'s argmax); ``gate_prob`` its combine weight (1.0 for
    explicit routing). ``capacity_factor`` scales the lossless per-device
    buffer (1.0 = never drop).
    """
    n = mesh.shape[axis_name]
    rows = NamedSharding(mesh, P(axis_name))
    # expert weights shard on their leading E axis; the router's gate_w is
    # [F, E] (E is axis 1) and is only read OUTSIDE the shard_map anyway
    p_spec = dict(gate_w=P(None, axis_name), w0=P(axis_name),
                  b0=P(axis_name), w1=P(axis_name), b1=P(axis_name),
                  w_skip=P(axis_name))
    p_shard = {k: NamedSharding(mesh, s) for k, s in p_spec.items()}
    expert_keys = ("w0", "b0", "w1", "b1", "w_skip")

    def fn(params, features, expert_id, gate_prob):
        b_loc = features.shape[0] // n
        capacity = max(1, math.ceil(b_loc * capacity_factor))
        body = functools.partial(_ep_shard, axis_name=axis_name,
                                 capacity=capacity,
                                 compute_dtype=compute_dtype)
        experts = {k: params[k] for k in expert_keys}
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=({k: P(axis_name) for k in expert_keys},
                      P(axis_name), P(axis_name), P(axis_name)),
            out_specs=P(axis_name),
        )(experts, features, expert_id, gate_prob)

    _ = rows_per_device  # shapes are static under jit; kept for API clarity
    return jax.jit(fn, in_shardings=(p_shard, rows, rows, rows),
                   out_shardings=rows)


def top1_route(params: MoEParams, features: jax.Array):
    """Learned routing → (expert_id int32 [B], gate_prob f32 [B]).

    Switch-style: argmax expert, combine-weighted by its softmax prob.
    """
    logits = gate_logits(params, features)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return idx, jnp.take_along_axis(probs, idx[..., None], axis=-1)[..., 0]
