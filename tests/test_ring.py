"""Ring attention + temporal estimator: the sequence/context-parallel path.

The load-bearing assertion: ring attention over an 8-way ``seq`` mesh is
numerically the same computation as dense causal attention on one device
(both f32 here so equality is tight), and the sequence-parallel temporal
program matches single-device `predict_temporal`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kepler_tpu.models.temporal import (
    init_temporal,
    predict_temporal,
    temporal_trunk,
)
from kepler_tpu.monitor.history import HistoryBuffer, feature_rows
from kepler_tpu.parallel import (
    full_attention,
    make_mesh,
    make_ring_attention,
    make_temporal_program,
)
from kepler_tpu.resource.informer import FeatureBatch


def qkv(b=2, t=32, h=4, d=16, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (b, t, h, d), jnp.float32),
            jax.random.normal(k2, (b, t, h, d), jnp.float32),
            jax.random.normal(k3, (b, t, h, d), jnp.float32))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = qkv()
        mesh = make_mesh([8], ["seq"])
        ring = make_ring_attention(mesh, causal=causal,
                                   compute_dtype=jnp.float32)
        t_valid = jnp.ones(q.shape[:2], bool)
        dense = full_attention(q, k, v, causal=causal,
                               compute_dtype=jnp.float32)
        out = ring(q, k, v, t_valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)

    def test_ragged_t_valid_matches_dense(self):
        q, k, v = qkv(b=3, t=16)
        t_valid = jnp.arange(16)[None, :] < jnp.array([[5], [16], [9]])
        mesh = make_mesh([8], ["seq"])
        ring = make_ring_attention(mesh, compute_dtype=jnp.float32)
        dense = full_attention(q, k, v, causal=True, t_valid=t_valid,
                               compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(ring(q, k, v, t_valid)),
                                   np.asarray(dense), rtol=1e-5, atol=1e-5)

    def test_output_sharded_over_seq(self):
        q, k, v = qkv(t=16)
        mesh = make_mesh([8], ["seq"])
        out = make_ring_attention(mesh)(q, k, v, jnp.ones(q.shape[:2], bool))
        assert out.sharding.spec[1] == "seq"

    def test_fully_masked_rows_are_zero(self):
        q, k, v = qkv(b=1, t=8)
        mesh = make_mesh([8], ["seq"])
        ring = make_ring_attention(mesh, compute_dtype=jnp.float32)
        out = ring(q, k, v, jnp.zeros((1, 8), bool))
        assert np.all(np.asarray(out) == 0.0)


class TestTemporalModel:
    def test_predicts_shape_and_masking(self):
        params = init_temporal(jax.random.PRNGKey(0), n_zones=3, t_max=16)
        hist = jax.random.uniform(jax.random.PRNGKey(1), (4, 7, 16, 7))
        valid = jnp.tile(
            jnp.array([True, True, False, True, True, False, True]), (4, 1))
        watts = predict_temporal(params, hist, valid)
        assert watts.shape == (4, 7, 3)
        assert np.all(np.asarray(watts)[~np.asarray(valid)] == 0.0)
        assert np.all(np.asarray(watts) >= 0.0)

    def test_last_valid_timestep_pools(self):
        """Right-padded histories: padding rows must not change the output."""
        params = init_temporal(jax.random.PRNGKey(0), n_zones=2, t_max=8)
        hist = np.zeros((1, 8, 7), np.float32)
        hist[0, :3] = np.random.default_rng(0).uniform(0, 1, (3, 7))
        tv = np.zeros((1, 8), bool)
        tv[0, :3] = True
        full = predict_temporal(params, jnp.asarray(hist)[None],
                                jnp.ones((1, 1), bool),
                                jnp.asarray(tv)[None], clamp=False)
        # garbage in the padded tail must be invisible
        hist2 = hist.copy()
        hist2[0, 3:] = 123.0
        full2 = predict_temporal(params, jnp.asarray(hist2)[None],
                                 jnp.ones((1, 1), bool),
                                 jnp.asarray(tv)[None], clamp=False)
        np.testing.assert_allclose(np.asarray(full), np.asarray(full2),
                                   rtol=1e-5, atol=1e-6)

    def test_trunk_is_causal(self):
        """Changing the future must not change earlier hidden states."""
        params = init_temporal(jax.random.PRNGKey(0), n_zones=2, t_max=8)
        rng = np.random.default_rng(1)
        a = rng.uniform(0, 1, (2, 8, 7)).astype(np.float32)
        b = a.copy()
        b[:, 5:] += 1.0
        tv = jnp.ones((2, 8), bool)
        ha = temporal_trunk(params, jnp.asarray(a), tv,
                            compute_dtype=jnp.float32)
        hb = temporal_trunk(params, jnp.asarray(b), tv,
                            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(ha)[:, :5],
                                   np.asarray(hb)[:, :5],
                                   rtol=1e-5, atol=1e-5)
        assert not np.allclose(np.asarray(ha)[:, 5:], np.asarray(hb)[:, 5:])

    def test_sequence_parallel_program_matches_dense(self):
        mesh = make_mesh([8], ["seq"])
        params = init_temporal(jax.random.PRNGKey(0), n_zones=2, t_max=32)
        hist = jax.random.uniform(jax.random.PRNGKey(2), (6, 32, 7))
        wv = jnp.array([True, True, False, True, True, True])
        tv = jnp.arange(32)[None, :] < jnp.array([32, 8, 32, 1, 17, 32])[:, None]
        prog = make_temporal_program(mesh, compute_dtype=jnp.float32)
        dense = predict_temporal(params, hist, wv, tv,
                                 compute_dtype=jnp.float32)
        out = prog(params, hist, wv, tv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-4, atol=1e-4)


class TestHistoryBuffer:
    def batch(self, ids, deltas, node_delta=10.0, ratio=0.5):
        return FeatureBatch(
            kinds=np.zeros(len(ids), np.int8),
            ids=list(ids),
            cpu_deltas=np.asarray(deltas, np.float32),
            node_cpu_delta=node_delta,
            usage_ratio=ratio,
        )

    def test_feature_rows_match_device_features(self):
        from kepler_tpu.models.features import build_features

        b = self.batch(["a", "b"], [2.0, 3.0])
        rows = feature_rows(b, dt_s=5.0)
        dev = build_features(jnp.asarray(b.cpu_deltas),
                             jnp.ones(2, bool),
                             jnp.asarray(b.node_cpu_delta),
                             jnp.asarray(b.usage_ratio),
                             jnp.asarray(5.0))
        np.testing.assert_allclose(rows, np.asarray(dev), rtol=1e-6)

    def test_window_accretes_and_right_pads(self):
        buf = HistoryBuffer(window=4)
        for tick in range(3):
            buf.push(self.batch(["a"], [float(tick + 1)]), dt_s=5.0)
        feats, tv = buf.window_arrays(["a", "ghost"])
        assert feats.shape == (2, 4, 7)
        np.testing.assert_array_equal(tv[0], [True, True, True, False])
        np.testing.assert_allclose(feats[0, :3, 0], [1.0, 2.0, 3.0])
        assert not tv[1].any()

    def test_ring_wraps_oldest_out(self):
        buf = HistoryBuffer(window=3)
        for tick in range(5):
            buf.push(self.batch(["a"], [float(tick)]), dt_s=5.0)
        feats, tv = buf.window_arrays(["a"])
        assert tv[0].all()
        np.testing.assert_allclose(feats[0, :, 0], [2.0, 3.0, 4.0])

    def test_eviction_of_unseen_ids(self):
        buf = HistoryBuffer(window=4, evict_after=2)
        buf.push(self.batch(["a", "b"], [1.0, 1.0]), dt_s=5.0)
        buf.push(self.batch(["a"], [1.0]), dt_s=5.0)
        assert len(buf) == 2
        buf.push(self.batch(["a"], [1.0]), dt_s=5.0)
        assert len(buf) == 1  # "b" unseen for 2 pushes → gone
        _, tv = buf.window_arrays(["b"])
        assert not tv.any()

    def test_feeds_temporal_model(self):
        buf = HistoryBuffer(window=8)
        for tick in range(5):
            buf.push(self.batch(["a", "b"], [1.0 + tick, 2.0]), dt_s=5.0)
        feats, tv = buf.window_arrays(["a", "b"])
        params = init_temporal(jax.random.PRNGKey(0), n_zones=2, t_max=8)
        watts = predict_temporal(params, jnp.asarray(feats),
                                 jnp.ones(2, bool), jnp.asarray(tv))
        assert watts.shape == (2, 2)
        assert np.isfinite(np.asarray(watts)).all()


class PerPodHistory:
    """The oracle: ``HistoryBuffer`` as it was before it became one slab —
    an ndarray and three dict entries per id, a Python step per id in both
    methods. Test code only; every window the slab serves must equal this
    one's bit for bit."""

    def __init__(self, window, n_features=7, evict_after=2):
        self.window = window
        self.n_features = n_features
        self._evict_after = evict_after
        self._tick = 0
        self._rows = {}
        self._count = {}
        self._cursor = {}
        self._seen = {}

    def __len__(self):
        return len(self._rows)

    def push(self, batch, dt_s):
        rows = feature_rows(batch, dt_s)
        self._tick += 1
        for i, wid in enumerate(batch.ids):
            buf = self._rows.get(wid)
            if buf is None:
                buf = np.zeros((self.window, self.n_features), np.float32)
                self._rows[wid] = buf
                self._count[wid] = 0
                self._cursor[wid] = 0
            buf[self._cursor[wid]] = rows[i]
            self._cursor[wid] = (self._cursor[wid] + 1) % self.window
            self._count[wid] = min(self._count[wid] + 1, self.window)
            self._seen[wid] = self._tick
        if self._evict_after > 0:
            dead = [wid for wid, seen in self._seen.items()
                    if self._tick - seen >= self._evict_after]
            for wid in dead:
                for d in (self._rows, self._count, self._cursor, self._seen):
                    del d[wid]

    def window_arrays(self, ids):
        w = len(ids)
        feats = np.zeros((w, self.window, self.n_features), np.float32)
        t_valid = np.zeros((w, self.window), bool)
        for i, wid in enumerate(ids):
            n = self._count.get(wid, 0)
            if not n:
                continue
            ordered = np.roll(self._rows[wid], -self._cursor[wid],
                              axis=0)[self.window - n:]
            feats[i, :n] = ordered
            t_valid[i, :n] = True
        return feats, t_valid


def same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(
        a.view(np.uint8), b.view(np.uint8))


# what a push's id list is drawn from, by scenario: (pool of ids, share of
# the live ids replaced a push, chance an id sits a push out, chance an id
# is listed twice, pushes)
SCENARIOS = {
    "steady": dict(pool=40, churn=0.0, absent=0.0, twice=0.0, pushes=40),
    "churn": dict(pool=60, churn=0.1, absent=0.0, twice=0.0, pushes=60),
    "absences": dict(pool=30, churn=0.0, absent=0.3, twice=0.0, pushes=60),
    "evict_and_return": dict(pool=12, churn=0.4, absent=0.4, twice=0.0,
                             pushes=80),
    "duplicates": dict(pool=20, churn=0.1, absent=0.2, twice=0.2,
                       pushes=50),
    "growth": dict(pool=300, churn=0.05, absent=0.05, twice=0.01,
                   pushes=30),
}


class TestHistoryBufferAgainstPerPodOracle:
    @pytest.mark.parametrize("evict_after", [2, 1, 0, 3])
    @pytest.mark.parametrize("window", [1, 3, 16])
    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    def test_every_window_is_bit_equal(self, scenario, window, evict_after):
        """Seeded random pushes: ids that churn, sit one push out, are
        evicted and come back, are listed twice, are unknown; windows
        shorter than T and wrapped rings; more ids than the first slab
        holds. After EVERY push the slab's windows and length equal the
        per-pod oracle's, bit for bit."""
        cfg = SCENARIOS[scenario]
        rng = np.random.default_rng(
            [sorted(SCENARIOS).index(scenario), window, evict_after])
        new = HistoryBuffer(window=window, evict_after=evict_after)
        old = PerPodHistory(window=window, evict_after=evict_after)
        # a small name space, so an evicted id is created again
        names = [f"pod-{k}" for k in range(2 * cfg["pool"])]
        live = list(rng.choice(names, cfg["pool"], replace=False))
        for push in range(cfg["pushes"]):
            for k in np.flatnonzero(rng.random(len(live)) < cfg["churn"]):
                live[k] = str(rng.choice(names))  # may even be live: twice
            ids = [wid for wid in live if rng.random() >= cfg["absent"]]
            for wid in list(ids):
                if rng.random() < cfg["twice"]:
                    ids.insert(int(rng.integers(len(ids) + 1)), wid)
            if push % 7 == 3:
                ids = ids[::-1]  # same set, another order: new slot vector
            deltas = rng.uniform(0.0, 4.0, len(ids)).astype(np.float32)
            batch = FeatureBatch(
                kinds=np.zeros(len(ids), np.int8), ids=ids,
                cpu_deltas=deltas,
                node_cpu_delta=float(rng.choice([0.0, deltas.sum() + 1.0])),
                usage_ratio=float(rng.random()))
            dt_s = float(rng.choice([0.0, 1.0, 5.0]))
            new.push(batch, dt_s)
            old.push(batch, dt_s)
            assert len(new) == len(old)
            # the pushed list itself; every name there is (known, evicted,
            # never seen) with one that cannot exist, shuffled; and as many
            # of those as were pushed, since a list like the last one in
            # all but its ids must not be taken for it
            ask = names + ["ghost"]
            rng.shuffle(ask)
            for query in (ids, list(ask), ask[:len(ids)]):
                feats, valid = new.window_arrays(query)
                want_feats, want_valid = old.window_arrays(query)
                assert same_bits(feats, want_feats), (push, query)
                assert same_bits(valid, want_valid), (push, query)
        if scenario == "growth":
            assert len(new._ids) > 16 * 8  # grew past the first slab, often

    def test_out_arrays_are_filled_and_returned(self):
        buf = HistoryBuffer(window=4)
        for tick in range(6):
            buf.push(FeatureBatch(
                kinds=np.zeros(2, np.int8), ids=["a", "b"],
                cpu_deltas=np.asarray([tick, 2.0], np.float32),
                node_cpu_delta=10.0, usage_ratio=0.5), dt_s=5.0)
        want = buf.window_arrays(["b", "ghost", "a"])
        # a destination that holds garbage, as a slice of a larger array
        feats = np.full((5, 4, 7), np.nan, np.float32)
        valid = np.ones((5, 4), bool)
        got = buf.window_arrays(["b", "ghost", "a"],
                                out=(feats[1:4], valid[1:4]))
        assert got[0].base is feats and got[1].base is valid
        assert same_bits(feats[1:4], want[0])
        assert same_bits(valid[1:4], want[1])
        assert np.isnan(feats[0]).all() and np.isnan(feats[4]).all()

    def test_a_callers_later_edit_of_its_id_list_changes_nothing(self):
        ids = ["a", "b"]
        buf = HistoryBuffer(window=4)
        batch = FeatureBatch(kinds=np.zeros(2, np.int8), ids=ids,
                             cpu_deltas=np.asarray([1.0, 2.0], np.float32),
                             node_cpu_delta=10.0, usage_ratio=0.5)
        buf.push(batch, dt_s=5.0)
        ids[0] = "c"  # the same list object, now other ids
        buf.push(batch, dt_s=5.0)
        feats, valid = buf.window_arrays(["a", "b", "c"])
        assert valid.sum(axis=1).tolist() == [1, 2, 1]
        assert feats[2, 0, 0] == 1.0


class TestSequenceParallelTraining:
    def test_grads_flow_through_ring_and_match_dense(self):
        """One SP train step == one single-device dense train step: the
        backward pass through ppermute/fori_loop is exact."""
        from kepler_tpu.models.train import (
            create_train_state,
            make_optimizer,
            make_temporal_train_step,
        )
        from kepler_tpu.parallel import make_sequence_parallel_train_step

        mesh = make_mesh([8], ["seq"])
        t = 16
        params = init_temporal(jax.random.PRNGKey(0), 2, d_model=32, t_max=t)
        hist = jax.random.uniform(jax.random.PRNGKey(1), (12, t, 7))
        wv = jnp.ones(12, bool)
        tv = jnp.arange(t)[None, :] < jnp.array([t] * 6 + [5] * 6)[:, None]
        targets = jax.random.uniform(jax.random.PRNGKey(2), (12, 2), (
            jnp.float32), 0.0, 30.0)
        opt = make_optimizer(1e-2)

        fresh = lambda: create_train_state(  # noqa: E731 — donated args
            jax.tree.map(jnp.array, params), opt)
        sp_step = make_sequence_parallel_train_step(mesh, opt)
        sp_state, sp_loss = sp_step(fresh(), hist, wv, tv, targets)

        # same compute dtype as the SP step — parity must hold on
        # dtype-faithful backends, not just ones where bf16 == f32
        dense_step = make_temporal_train_step(opt, compute_dtype=jnp.float32)
        dense_state, dense_loss = dense_step(fresh(), hist, wv, tv, targets)

        np.testing.assert_allclose(float(sp_loss), float(dense_loss),
                                   rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            sp_state.params, dense_state.params)

    def test_remat_matches_no_remat(self):
        from kepler_tpu.models.train import create_train_state, make_optimizer
        from kepler_tpu.parallel import make_sequence_parallel_train_step

        mesh = make_mesh([8], ["seq"])
        t = 8
        params = init_temporal(jax.random.PRNGKey(0), 2, d_model=32, t_max=t)
        hist = jax.random.uniform(jax.random.PRNGKey(1), (4, t, 7))
        wv = jnp.ones(4, bool)
        tv = jnp.ones((4, t), bool)
        targets = jnp.ones((4, 2)) * 10.0
        opt = make_optimizer(1e-2)
        fresh = lambda: create_train_state(  # noqa: E731 — donated args
            jax.tree.map(jnp.array, params), opt)
        _, loss_a = make_sequence_parallel_train_step(mesh, opt)(
            fresh(), hist, wv, tv, targets)
        _, loss_b = make_sequence_parallel_train_step(mesh, opt, remat=True)(
            fresh(), hist, wv, tv, targets)
        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)

    def test_loss_decreases_over_steps(self):
        from kepler_tpu.models.train import create_train_state, make_optimizer
        from kepler_tpu.parallel import make_sequence_parallel_train_step

        mesh = make_mesh([8], ["seq"])
        t = 8
        params = init_temporal(jax.random.PRNGKey(0), 2, d_model=32, t_max=t)
        hist = jax.random.uniform(jax.random.PRNGKey(1), (8, t, 7))
        wv = jnp.ones(8, bool)
        tv = jnp.ones((8, t), bool)
        targets = hist[:, -1, :1] * jnp.asarray([[10.0, 20.0]])
        opt = make_optimizer(1e-3)
        step = make_sequence_parallel_train_step(mesh, opt)
        state = create_train_state(params, opt)
        state, first = step(state, hist, wv, tv, targets)
        for _ in range(40):
            state, loss = step(state, hist, wv, tv, targets)
        assert float(loss) < float(first)
