"""The fused trunk kernel (``ops.pallas_temporal``) against the XLA trunk
(``models.temporal._last_query_trunk``), in interpret mode on the CPU, and
where the served program chooses it.

The tolerance is a reading, not a guess: the XLA trunk against itself with
its two attention contractions summed in another order
(:func:`reordered_trunk`) differs from it by up to ``REORDER_READING``
over this file's cases at the served widths (T 16, d 128, 4 heads, MLP
512, F 7, bf16). The kernel sums the same contractions in its own order,
so it is held to that reading.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kepler_tpu.models import temporal
from kepler_tpu.models.nn import LN_EPS, acc_matmul
from kepler_tpu.models.temporal import (N_HEADS, _last_query_trunk,
                                        init_temporal)
from kepler_tpu.ops import pallas_temporal
from kepler_tpu.ops.pallas_temporal import last_query_trunk_pallas
from kepler_tpu.parallel import aggregator_core
from kepler_tpu.parallel.aggregator_core import (fused_trunk_chosen,
                                                 make_temporal_fleet_program)

from tests import test_sharded_put as served_put

T, F, D = 16, 7, 128
BF16 = jnp.bfloat16

#: max |XLA trunk − the XLA trunk reordered| over CASES, 256 rows each
#: (pooled states of unit scale): 9.52e-3, on single_late_tick (4.82e-3
#: gapped, 9.37e-3 contiguous, 2.4e-7 all_invalid), read on the CPU. One
#: f32 sum taken in another order flips a bf16 rounding downstream of it
REORDER_READING = 9.6e-3
ROWS = 256


def served_params(seed: int = 0):
    """``init_temporal`` at the served widths with every vector moved off
    its init (unit scales, zero biases would hide a swapped operand)."""
    params = dict(init_temporal(jax.random.PRNGKey(seed), n_zones=4))
    rng = np.random.default_rng(seed)
    for name in ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                 "b_mlp0", "b_mlp1", "ln_f_scale", "ln_f_bias"):
        params[name] = params[name] + rng.normal(
            0, 0.2, params[name].shape).astype(np.float32)
    return params


def t_valid_of(case: str, rows: int, rng) -> np.ndarray:
    """The masks of ``tests/test_models.py``'s fast-path cases."""
    if case == "contiguous":  # right-padded prefixes of every length
        return np.arange(T) < (np.arange(rows) % (T + 1))[:, None]
    if case == "gapped":
        return rng.random((rows, T)) < 0.5
    if case == "single_late_tick":
        tv = np.zeros((rows, T), bool)
        tv[:, -1 - np.arange(rows) % 3] = True
        return tv
    if case == "all_invalid":
        return np.zeros((rows, T), bool)
    raise ValueError(case)


CASES = ("contiguous", "gapped", "single_late_tick", "all_invalid")


def rows_of(case: str, rows: int = ROWS, seed: int = 1):
    rng = np.random.default_rng(seed)
    hist = rng.lognormal(0.0, 1.0, (rows, T, F)).astype(np.float32)
    return jnp.asarray(hist), jnp.asarray(t_valid_of(case, rows, rng))


def backwards_sum(x, axis: int):
    """A sum over ``axis`` as one add after another from its last element
    to its first (XLA reassociates no float add, and drops a reverse
    before a reduction)."""
    x = jnp.moveaxis(x, axis, 0)
    total = x[-1]
    for i in range(x.shape[0] - 2, -1, -1):
        total = total + x[i]
    return total


def backwards_norm(x, scale, bias):
    """``models.nn.layer_norm`` with its two sums over the lanes backwards."""
    n = x.shape[-1]
    mu = backwards_sum(x, -1)[..., None] / n
    var = backwards_sum(jnp.square(x - mu), -1)[..., None] / n
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def reordered_trunk(params, feat_hist, t_valid):
    """``_last_query_trunk`` in bf16 with every f32 sum that is not a
    matmul's taken backwards: the layer norms', the scores' over a head's
    lanes, the softmax's and the value contraction's over the ticks."""
    b, t, _ = feat_hist.shape
    dh = D // N_HEADS
    x = acc_matmul(feat_hist, params["in_proj"], BF16)
    x = x + params["pos_emb"][:t]
    x = jnp.where(t_valid[..., None], x, 0.0)
    last = jnp.maximum(jnp.sum(t_valid, axis=-1) - 1, 0)
    y = backwards_norm(x, params["ln1_scale"], params["ln1_bias"])
    y_last = jnp.take_along_axis(y, last[:, None, None], axis=1)[:, 0]
    q = acc_matmul(y_last, params["wq"], BF16).astype(BF16)
    k = acc_matmul(y, params["wk"], BF16).astype(BF16)
    v = acc_matmul(y, params["wv"], BF16).astype(BF16)
    prod = (q[:, None].astype(jnp.float32) * k.astype(jnp.float32))
    scores = backwards_sum(prod.reshape(b, t, N_HEADS, dh), -1)
    scores = scores.transpose(0, 2, 1)  # [B, H, T]
    scores = scores / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    causal = jnp.arange(t)[None, :] <= last[:, None]
    scores = jnp.where((t_valid & causal)[:, None, :], scores, -1e30)
    e = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
    probs = e / backwards_sum(e, -1)[..., None]
    probs = jnp.where(t_valid.any(-1)[:, None, None], probs, 0.0)
    probs = probs.astype(BF16).astype(jnp.float32)
    vv = v.astype(jnp.float32).reshape(b, t, N_HEADS, dh)
    attn = backwards_sum(probs.transpose(0, 2, 1)[..., None] * vv, 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    x_last = x_last + acc_matmul(attn.reshape(b, D), params["wo"], BF16)
    y = backwards_norm(x_last, params["ln2_scale"], params["ln2_bias"])
    y = jax.nn.gelu(acc_matmul(y, params["w_mlp0"], BF16) + params["b_mlp0"])
    x_last = x_last + acc_matmul(y, params["w_mlp1"], BF16) + params["b_mlp1"]
    return backwards_norm(x_last, params["ln_f_scale"], params["ln_f_bias"])


xla_trunk = functools.partial(_last_query_trunk, compute_dtype=BF16)


@pytest.fixture(scope="module")
def pooled():
    """Every case's rows through the XLA trunk, the reordered one and the
    kernel (interpreted, blocks of 128 rows), all cases in one call each."""
    params = served_params()
    hist, tv = zip(*(rows_of(case) for case in CASES))
    hist, tv = jnp.concatenate(hist), jnp.concatenate(tv)
    kernel = functools.partial(last_query_trunk_pallas, block_rows=128,
                               interpret=True)
    return {name: np.asarray(jax.jit(fn)(params, hist, tv)).reshape(
                len(CASES), ROWS, D)
            for name, fn in (("xla", xla_trunk), ("reordered",
                             reordered_trunk), ("kernel", kernel))}


@pytest.mark.parametrize("case", CASES)
def test_the_xla_trunk_reordered_is_within_the_reading(pooled, case):
    """The reading itself: the reordering moves the XLA trunk by no more
    than it, and the reordered trunk is finite where the XLA one is."""
    i = CASES.index(case)
    assert np.abs(pooled["reordered"][i] - pooled["xla"][i]).max() \
        <= REORDER_READING
    assert np.isfinite(pooled["reordered"][i]).all()


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_xla_trunk(pooled, case):
    i = CASES.index(case)
    np.testing.assert_allclose(pooled["kernel"][i], pooled["xla"][i],
                               rtol=0, atol=REORDER_READING)


def test_a_row_count_that_is_not_a_multiple_of_the_block():
    """100 rows in blocks of 32: the last block is part full, and every
    row still reads its own pooled state."""
    params = served_params(3)
    hist, tv = rows_of("gapped", rows=100, seed=4)
    want = np.asarray(jax.jit(xla_trunk)(params, hist, tv))
    got = np.asarray(jax.jit(functools.partial(
        last_query_trunk_pallas, block_rows=32, interpret=True))(
            params, hist, tv))
    assert got.shape == (100, D)
    np.testing.assert_allclose(got, want, rtol=0, atol=REORDER_READING)


def test_the_kernel_computes_in_bf16_only():
    hist, tv = rows_of("contiguous", rows=8)
    with pytest.raises(ValueError, match="bfloat16"):
        last_query_trunk_pallas(served_params(), hist, tv, jnp.float32,
                                interpret=True)


# -- where the served program chooses it ------------------------------------

@pytest.mark.parametrize("platform,accuracy_mode,dtype,fused", [
    ("tpu", False, jnp.bfloat16, True),
    ("cpu", False, jnp.bfloat16, False),
    ("tpu", True, jnp.bfloat16, False),
    ("tpu", False, jnp.float32, False),
    ("gpu", False, jnp.bfloat16, False),
])
def test_the_builder_chooses_the_kernel_on_a_tpu_in_bf16_alone(
        platform, accuracy_mode, dtype, fused):
    assert fused_trunk_chosen(platform, accuracy_mode, dtype) is fused


@pytest.fixture
def kernel_calls(monkeypatch):
    """Every call of the kernel's entry, counted; and the builder's choice
    made as on a TPU (the kernel then runs interpreted on the CPU)."""
    calls = []
    real = pallas_temporal.last_query_trunk_pallas

    def counted(*args, **kw):
        calls.append(args[1].shape)
        return real(*args, **kw)

    monkeypatch.setattr(pallas_temporal, "last_query_trunk_pallas", counted)
    monkeypatch.setattr(aggregator_core, "fused_trunk_chosen",
                        lambda *a, **kw: True)
    from kepler_tpu.fleet import scheduler
    monkeypatch.setattr(scheduler, "fused_trunk_chosen",
                        lambda *a, **kw: True)
    return calls


def test_training_and_the_sequence_program_never_receive_the_kernel(
        kernel_calls):
    """Where the builder would choose the kernel, the train step (which
    differentiates ``predict_temporal``) and ``make_temporal_program``
    (the ring-attention program) still run the XLA trunk."""
    import optax

    from kepler_tpu.models.train import (create_train_state,
                                         make_temporal_train_step)
    from kepler_tpu.parallel import make_temporal_program
    from kepler_tpu.parallel.mesh import make_mesh

    params = init_temporal(jax.random.PRNGKey(0), n_zones=2, d_model=32,
                           t_max=8)
    rng = np.random.default_rng(0)
    hist = jnp.asarray(rng.random((2, 4, 8, F), np.float32))
    wv = jnp.ones((2, 4), bool)
    tv = jnp.ones((2, 4, 8), bool)
    opt = optax.adam(1e-3)
    # traced, not compiled: the kernel's entry is called while tracing
    step = make_temporal_train_step(opt, compute_dtype=BF16)
    jax.make_jaxpr(step)(create_train_state(params, opt), hist, wv, tv,
                         jnp.ones((2, 4, 2)))
    mesh = make_mesh([2], ["seq"], devices=jax.devices()[:2])
    jax.make_jaxpr(make_temporal_program(mesh))(params, hist[0], wv[0],
                                                tv[0])
    assert kernel_calls == []
    # the same stand-in does reach the served program's builder
    batch, sp, dense, dense_tv = served_put.ragged_inputs()
    mesh = served_put.mesh_of(1)
    jax.make_jaxpr(make_temporal_fleet_program(mesh))(
        *served_put.put_fleet_batch(batch, sp, dense, dense_tv, mesh=mesh))
    assert kernel_calls == [(served_put.NODES * served_put.SLOTS,
                             served_put.TICKS, F)]


def altered_predict(monkeypatch):
    """``predict_temporal`` wrapped as ``tests/chipbench/broken_launch.py``
    wraps it for ``answer_altered``."""
    real = temporal.predict_temporal

    def altered(*args, **kw):
        return real(*args, **kw).at[1, 0].multiply(1.1)

    monkeypatch.setattr(temporal, "predict_temporal", altered)


def test_sent_rows_over_four_devices_run_the_kernel_shard_by_shard(
        kernel_calls, monkeypatch):
    """The compact program over four devices with the fused trunk: each
    device's kernel sees its own shard's rows alone, nothing crosses
    devices, the watts are the XLA trunk's within the reading (a watt is
    the pooled state through a head of norm ~1 plus a skip), and an
    estimate altered where ``predict_temporal`` makes it moves one pod."""
    mesh = served_put.mesh_of(4)
    inputs = served_put.ragged_inputs()
    fit = served_put.BucketLadder(2, 16).fit
    args = served_put.compact_put(*inputs, mesh, fit)
    r = args[9].shape[1]  # [S, R, T·F]
    fused = make_temporal_fleet_program(mesh, compact=True)
    got = fused(*args)
    # traced once, on one shard's rows: R and its zero rows
    assert kernel_calls == [(r + 8 - r % 8, served_put.TICKS, F)]
    hlo = fused.lower(*args).compile().as_text()
    for op in served_put.COLLECTIVES:
        assert op not in hlo, op
    # the shard_map hands each device's kernel its own block of rows
    local = jax.make_jaxpr(fused)(*args)
    assert "shard_map" in str(local)

    monkeypatch.setattr(aggregator_core, "fused_trunk_chosen",
                        lambda *a, **kw: False)
    want = make_temporal_fleet_program(mesh, compact=True)(*args)
    # µW: a pooled lane off by the reading moves a watt by at most the
    # reading times the head's largest column sum
    head = np.abs(np.asarray(inputs[1]["w_head"])).sum(0).max()
    np.testing.assert_allclose(np.asarray(got.workload_power_uw),
                               np.asarray(want.workload_power_uw),
                               rtol=0, atol=1e6 * REORDER_READING * head)

    monkeypatch.setattr(aggregator_core, "fused_trunk_chosen",
                        lambda *a, **kw: True)
    altered_predict(monkeypatch)
    moved_by = make_temporal_fleet_program(mesh, compact=True)(*args)
    moved = (np.asarray(moved_by.workload_power_uw)
             != np.asarray(got.workload_power_uw)).any(-1)
    assert moved.sum() == 1


def test_under_the_pallas_backend_the_kernel_runs_in_its_shard_map(
        kernel_calls):
    """With ``tpu.fleetBackend: pallas`` the whole program is already
    mapped over the node axis: the kernel runs there, on a shard's rows,
    with no second map around it."""
    mesh = served_put.mesh_of(4)
    args = served_put.compact_put(*served_put.ragged_inputs(), mesh,
                                  served_put.BucketLadder(2, 16).fit)
    r = args[9].shape[1]
    got = make_temporal_fleet_program(mesh, backend="pallas",
                                      compact=True)(*args)
    assert kernel_calls == [(r + 8 - r % 8, served_put.TICKS, F)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(aggregator_core, "fused_trunk_chosen",
                   lambda *a, **kw: False)
        want = make_temporal_fleet_program(mesh, compact=True)(*args)
    head = np.abs(served_put.seeded_params()["w_head"]).sum(0).max()
    np.testing.assert_allclose(np.asarray(got.workload_power_uw),
                               np.asarray(want.workload_power_uw),
                               rtol=0, atol=1e6 * REORDER_READING * head)


def test_served_windows_count_the_fused_rows(kernel_calls):
    """Served over four devices with the kernel chosen: every window's
    estimator rows are fused rows; served on the CPU as it is, none."""
    def served(n_dev):
        s = served_put.Served(n_dev)
        s.agg.windows._history_rows = served_put.BucketLadder(4, 16)
        try:
            for seq in range(1, 4):
                s.window(seq)
            s.agg.windows.drain()
            return s.get("/debug/window")["counts"]
        finally:
            s.close()

    counts = served(4)
    assert counts["rows_fused"] == counts["rows_program"] > 0
    assert kernel_calls


def test_served_windows_on_the_cpu_count_no_fused_rows():
    s = served_put.Served(1)
    try:
        for seq in range(1, 3):
            s.window(seq)
        s.agg.windows.drain()
        counts = s.get("/debug/window")["counts"]
    finally:
        s.close()
    assert counts["rows_program"] > 0
    assert counts["rows_fused"] == 0
