"""MoE estimator + expert-parallel dispatch.

Load-bearing assertion: the all_to_all expert-parallel program produces
the SAME watts as dense evaluation with the same routing — moving rows to
experts is an execution strategy, not a different model.
"""

import jax
import jax.numpy as jnp
import numpy as np

from kepler_tpu.models.moe import (
    expert_forward,
    init_moe,
    predict_moe,
)
from kepler_tpu.parallel import (
    make_expert_parallel_moe,
    make_mesh,
    top1_route,
)

N_ZONES = 2
F = 7


def params_and_rows(n_experts=8, b=32, seed=0):
    params = init_moe(jax.random.PRNGKey(seed), N_ZONES,
                      n_experts=n_experts, hidden=32)
    # init zero-inits the output projection and wide skip (training
    # stability); these tests need NONZERO outputs so routed-vs-dropped
    # rows are distinguishable — give both random weights
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 7))
    params["w1"] = jax.random.normal(k1, params["w1"].shape,
                                     jnp.float32) * 0.3
    params["w_skip"] = jax.random.normal(k2, params["w_skip"].shape,
                                         jnp.float32) * 0.2
    feats = jax.random.uniform(jax.random.PRNGKey(seed + 1), (b, F),
                               jnp.float32, 0.0, 2.0)
    return params, feats


class TestDenseMoE:
    def test_shapes_masking_clamp(self):
        params, feats = params_and_rows()
        feats = feats.reshape(4, 8, F)
        valid = jnp.arange(8)[None, :] < jnp.array([[8], [3], [0], [5]])
        watts = predict_moe(params, feats, valid)
        assert watts.shape == (4, 8, N_ZONES)
        w = np.asarray(watts)
        assert np.all(w[~np.asarray(valid)] == 0.0)
        assert np.all(w >= 0.0)

    def test_explicit_routing_selects_single_expert(self):
        """Hard routing by node type must equal running ONLY that expert."""
        params, feats = params_and_rows(n_experts=4, b=8)
        feats = feats.reshape(2, 4, F)  # [nodes=2, W=4, F]
        eid = jnp.array([1, 3], jnp.int32)
        watts = predict_moe(params, feats, jnp.ones((2, 4), bool),
                            expert_id=eid, clamp=False)
        for node, e in enumerate([1, 3]):
            one = {k: v[e:e + 1] for k, v in params.items()
                   if k != "gate_w"}
            want = expert_forward(one, feats[node][None])[0]
            np.testing.assert_allclose(np.asarray(watts[node]),
                                       np.asarray(want), rtol=1e-3,
                                       atol=1e-4)

    def test_learned_gate_is_convex_mix(self):
        """Soft-gated output lies inside the experts' output hull."""
        params, feats = params_and_rows(n_experts=4, b=4)
        watts = predict_moe(params, feats, jnp.ones(4, bool), clamp=False)
        e = 4
        per = np.asarray(expert_forward(
            params, jnp.broadcast_to(feats[None], (e, 4, F))))
        lo, hi = per.min(axis=0), per.max(axis=0)
        w = np.asarray(watts)
        assert np.all(w >= lo - 1e-4) and np.all(w <= hi + 1e-4)


class TestExpertParallel:
    def test_matches_dense_with_explicit_routing(self):
        mesh = make_mesh([8], ["expert"])
        params, feats = params_and_rows(n_experts=8, b=64)
        eid = (jnp.arange(64) * 7 % 8).astype(jnp.int32)
        ep = make_expert_parallel_moe(mesh)
        out = ep(params, feats, eid, jnp.ones(64, jnp.float32))
        dense = predict_moe(params, feats.reshape(64, 1, F),
                            jnp.ones((64, 1), bool),
                            expert_id=eid, clamp=False)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=2e-3, atol=1e-4)

    def test_matches_dense_with_learned_top1(self):
        mesh = make_mesh([8], ["expert"])
        params, feats = params_and_rows(n_experts=8, b=32)
        eid, prob = top1_route(params, feats)
        ep = make_expert_parallel_moe(mesh)
        out = np.asarray(ep(params, feats, eid, prob))
        # dense top-1: run each row's argmax expert, weight by its prob
        per = np.asarray(expert_forward(
            params, jnp.broadcast_to(feats[None], (8, 32, F))))
        want = per[np.asarray(eid), np.arange(32)] * np.asarray(prob)[:, None]
        np.testing.assert_allclose(out, want, rtol=2e-3, atol=1e-4)

    def test_multiple_experts_per_device(self):
        """E=16 on an 8-device mesh → 2 experts per device."""
        mesh = make_mesh([8], ["expert"])
        params, feats = params_and_rows(n_experts=16, b=32)
        eid = (jnp.arange(32) % 16).astype(jnp.int32)
        out = make_expert_parallel_moe(mesh)(
            params, feats, eid, jnp.ones(32, jnp.float32))
        dense = predict_moe(params, feats.reshape(32, 1, F),
                            jnp.ones((32, 1), bool),
                            expert_id=eid, clamp=False)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=2e-3, atol=1e-4)

    def test_capacity_overflow_drops_to_zero(self):
        """All rows to one expert with capacity_factor → overflow rows 0."""
        mesh = make_mesh([8], ["expert"])
        params, feats = params_and_rows(n_experts=8, b=64)
        eid = jnp.zeros(64, jnp.int32)  # everyone picks expert 0
        ep = make_expert_parallel_moe(mesh, capacity_factor=0.5)
        out = np.asarray(ep(params, feats, eid, jnp.ones(64, jnp.float32)))
        # per device: 8 local rows, capacity 4 → exactly 4 dropped (zeros)
        dropped = np.all(out == 0.0, axis=-1).reshape(8, 8).sum(axis=1)
        np.testing.assert_array_equal(dropped, np.full(8, 4))

    def test_output_row_sharding(self):
        mesh = make_mesh([8], ["expert"])
        params, feats = params_and_rows(n_experts=8, b=64)
        out = make_expert_parallel_moe(mesh)(
            params, feats, jnp.zeros(64, jnp.int32),
            jnp.ones(64, jnp.float32))
        assert out.sharding.spec[0] == "expert"


class TestRegistry:
    def test_moe_served_through_registry(self):
        from kepler_tpu.models.estimator import ModelEstimator

        est = ModelEstimator.create("moe", n_zones=2, n_experts=4, hidden=32)
        watts = est.predict_watts(
            jnp.asarray([1.0, 2.0, 0.0]), jnp.asarray([True, True, False]),
            jnp.asarray(3.0), jnp.asarray(0.5), jnp.asarray(5.0))
        assert watts.shape == (3, 2)
        assert np.asarray(watts)[2].sum() == 0.0

    def test_temporal_rejected_by_registry(self):
        """Temporal needs history windows; single-tick consumers must fail
        loudly at setup, not silently misread the workload axis as time."""
        import pytest

        from kepler_tpu.models.estimator import initializer, predictor

        with pytest.raises(ValueError, match="history"):
            predictor("temporal")
        initializer("temporal")  # param creation stays available

    def test_fleet_aggregator_accepts_moe_params(self):
        from kepler_tpu.fleet.aggregator import Aggregator
        from kepler_tpu.server.http import APIServer

        params = {k: np.asarray(v) for k, v in
                  init_moe(jax.random.PRNGKey(0), 2, n_experts=4,
                           hidden=16).items()}
        agg = Aggregator(APIServer(), model_mode="moe",
                         model_params=params)
        agg.windows._check_params_shape()
        assert agg.windows._model_out_dim() == 2

    def test_fleet_aggregator_rejects_unknown_model_params(self):
        import pytest

        from kepler_tpu.fleet.aggregator import Aggregator
        from kepler_tpu.server.http import APIServer

        agg = Aggregator(APIServer(), model_mode="switch-transformer",
                         model_params={"w": np.zeros(2)})
        with pytest.raises(ValueError, match="unknown aggregator model"):
            agg.windows._check_params_shape()
