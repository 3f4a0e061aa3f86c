"""Pipeline parallelism: GPipe-style microbatch streaming over ``stage``.

The deep estimator (`kepler_tpu.models.deep`) is a stack of S identical
residual blocks; here the stack's leading axis shards over the ``stage``
mesh axis (one block — or S/n consecutive blocks — per device) and the
batch splits into M microbatches that stream through: each tick every
device applies its stage to the activation it holds, then ``ppermute``s
the result one hop down the ring. After ``M + S − 1`` ticks every
microbatch has crossed every stage — the classic GPipe schedule with its
S−1-tick bubble, expressed as a ``fori_loop`` inside one ``shard_map``
(the same shape as the scaling-book's shard_map pipeline recipe).

Inference-only by design: the training path already covers DP×TP
(`kepler_tpu.parallel.trainer`), and serving is where the fleet batch is
big enough for microbatching to pay.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STAGE_AXIS = "stage"


def _pp_shard(stage_params, x_mb, *, axis_name, stage_fn):
    """Per-device body. stage_params: local stage(s), leading axis S/n.
    x_mb [M, mB, D] microbatches (replicated; only stage 0 reads them)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def apply_local(params, x):
        # a device may own several consecutive blocks of the stack
        def body(x, block):
            return stage_fn(block, x), None

        out, _ = jax.lax.scan(body, x, params)
        return out

    def tick(t, carry):
        state, out = carry
        # stage 0 ingests microbatch t (garbage past M — masked at write)
        feed = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        x_in = jnp.where(idx == 0, feed, state)
        y = apply_local(stage_params, x_in)
        # last stage emits microbatch t-(n-1) once the bubble has drained
        oi = jnp.clip(t - (n - 1), 0, m - 1)
        valid = t >= (n - 1)
        prev = jax.lax.dynamic_index_in_dim(out, oi, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, prev), oi, 0)
        state = jax.lax.ppermute(y, axis_name, perm)
        return state, out

    # zeros-initialised carries must be marked device-varying over the stage
    # axis up front or the fori_loop carry types mismatch (shard_map vma rule)
    state = jax.lax.pcast(jnp.zeros_like(x_mb[0]), axis_name,
                          to="varying")
    out = jax.lax.pcast(jnp.zeros_like(x_mb), axis_name, to="varying")
    _, out = jax.lax.fori_loop(0, m + n - 1, tick, (state, out))
    # every stage wrote a buffer; only the last stage's is the answer —
    # zero the rest and psum so the result replicates
    out = out * (idx == n - 1)
    return jax.lax.psum(out, axis_name)


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable,  # (block_params_no_stage_axis, x [mB, D]) → [mB, D]
    *,
    axis_name: str = STAGE_AXIS,
    n_microbatches: int = 4,
):
    """→ jitted ``(stacked_stage_params, x [B, D]) → [B, D]``.

    ``stacked_stage_params``: pytree whose leaves have a leading stage axis
    S (divisible by the mesh's ``axis_name`` size). ``B`` must divide by
    ``n_microbatches``. Output equals applying the S stages sequentially.
    """
    stages = NamedSharding(mesh, P(axis_name))
    rep = NamedSharding(mesh, P())
    body = functools.partial(_pp_shard, axis_name=axis_name,
                             stage_fn=stage_fn)

    def fn(stage_params, x):
        b = x.shape[0]
        if b % n_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {n_microbatches} microbatches")
        x_mb = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])
        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(),
        )(stage_params, x_mb)
        return out.reshape(b, *x.shape[1:])

    return jax.jit(fn, in_shardings=(stages, rep), out_shardings=rep)


def make_pipelined_deep(
    mesh: Mesh,
    *,
    axis_name: str = STAGE_AXIS,
    n_microbatches: int = 4,
    clamp: bool = True,
    compute_dtype: jnp.dtype = jnp.bfloat16,
):
    """→ jitted ``(DeepParams, features [B, F], workload_valid [B]) → [B, Z]``.

    Embed and head run replicated outside the pipeline (one tiny matmul
    each); the S-block stack streams through the stage ring.
    """
    from kepler_tpu.models.deep import block_fn, embed, head

    pipeline = make_pipeline(
        mesh,
        functools.partial(block_fn, compute_dtype=compute_dtype),
        axis_name=axis_name, n_microbatches=n_microbatches)
    stages = NamedSharding(mesh, P(axis_name))
    rep = NamedSharding(mesh, P())
    shardings = dict(in_proj=rep, in_bias=rep, w_head=rep, b_head=rep,
                     w_skip=rep,
                     blocks=jax.tree.map(lambda _: stages,
                                         dict(ln_scale=0, ln_bias=0, w0=0,
                                              b0=0, w1=0, b1=0)))

    def fn(params, features, workload_valid):
        x = embed(params, features, compute_dtype)
        x = pipeline(params["blocks"], x)
        return head(params, x, workload_valid, clamp, features=features)

    return jax.jit(fn, in_shardings=(shardings, rep, rep),
                   out_shardings=rep)
