"""Every device is sent its own rows (ISSUE 31).

``put_fleet_batch`` with the program's mesh puts every per-node argument
with the sharding the program declares for it, so each device of the mesh
takes its own nodes' rows straight from the host and the jit moves
nothing; over a mesh of one device that is the whole array on that device,
bit for bit what the put without a mesh gives. Since ISSUE 37 the
served temporal window sends its history as the rows that hold a tick
(``compact_history``), and the program runs its estimator on those rows
alone and gathers the watts back to the dense slots on each
device (``make_temporal_fleet_program(compact=True)``); the dense entry
stays for the library. Here on the CPU's virtual
devices (conftest gives eight): counts, placements and published values,
never a time. The served path against the plain reference, on a child with
four devices, is ``tests/chipbench/test_four_chip_cell.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import re
import urllib.request

import jax
import numpy as np
import pytest

from kepler_tpu import telemetry
from kepler_tpu.fleet.wire import encode_report
from kepler_tpu.models.temporal import init_temporal
from kepler_tpu.fleet.window import BucketLadder
from kepler_tpu.parallel.aggregator_core import (_rows_with_a_tick,
                                                 compact_history,
                                                 fleet_shardings,
                                                 make_fleet_program,
                                                 make_temporal_fleet_program,
                                                 put_fleet_batch)
from kepler_tpu.parallel.fleet import (MODE_MODEL, MODE_RATIO, NodeReport,
                                       assemble_fleet_batch)
from kepler_tpu.parallel.mesh import make_mesh
from kepler_tpu.telemetry.spans import SpanRecorder

from tests import test_window_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZONES = ["package", "dram"]
NODES, SLOTS, TICKS, FEATURES = 8, 16, 4, 7


def report(k: int, seed: int) -> NodeReport:
    rng = np.random.default_rng([seed, k])
    w = 3 + k % 4
    cpu = rng.uniform(0.1, 5.0, w).astype(np.float32)
    return NodeReport(
        node_name=f"node-{k}",
        zone_deltas_uj=rng.uniform(1e7, 5e8, 2).astype(np.float32),
        zone_valid=np.ones(2, bool), usage_ratio=float(rng.uniform(0.3, 0.8)),
        cpu_deltas=cpu, workload_ids=[f"n{k}-w{j}" for j in range(w)],
        node_cpu_delta=float(cpu.sum()), dt_s=5.0,
        mode=MODE_MODEL if k % 2 else MODE_RATIO,
        workload_kinds=np.ones(w, np.int8))


def seeded_params():
    """An untrained ``init_temporal`` has a zero head and a zero skip:
    every model row would be 0 W and equal whatever was computed. A seeded
    head around a bias of a few watts makes every layer reach the
    published number."""
    params = init_temporal(jax.random.PRNGKey(7), n_zones=2)
    rng = np.random.default_rng(7)
    d = params["w_head"].shape[0]
    return dict(
        params,
        w_head=(0.09 * rng.standard_normal((d, 2))).astype(np.float32),
        b_head=rng.uniform(3.0, 6.0, 2).astype(np.float32))


def mesh_of(n: int):
    return make_mesh(devices=jax.devices()[:n])


def temporal_inputs():
    batch = assemble_fleet_batch(
        [report(k, 1) for k in range(NODES)], n_zones=2, node_bucket=NODES,
        workload_bucket=SLOTS)
    rng = np.random.default_rng(0)
    hist = rng.random((NODES, SLOTS, TICKS, FEATURES), np.float32)
    t_valid = rng.random((NODES, SLOTS, TICKS)) > 0.2
    return batch, seeded_params(), hist, t_valid


def test_on_four_devices_each_holds_a_quarter_of_every_node_argument():
    mesh = mesh_of(4)
    replicated, by_node = fleet_shardings(mesh)
    args = put_fleet_batch(*temporal_inputs(), mesh=mesh)
    assert len(args) == 11
    for leaf in jax.tree.leaves(args[0]):  # the params: whole, everywhere
        assert leaf.sharding.is_equivalent_to(replicated, leaf.ndim)
    for arr in args[1:]:
        assert arr.sharding.is_equivalent_to(by_node, arr.ndim)
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start)
        assert [s.device for s in shards] == list(mesh.devices.flat)
        # no device holds another's rows: a quarter each, in node order
        for k, s in enumerate(shards):
            assert s.data.shape == (NODES // 4,) + arr.shape[1:]
            assert s.index[0] == slice(2 * k, 2 * k + 2)
        assert sum(s.data.nbytes for s in shards) == arr.nbytes
    # the program takes them as they lie; what it returns is by node too,
    # and np.asarray reads it a shard at a time into one host array (no
    # gather on a device: the result is never whole on any of them)
    out = make_temporal_fleet_program(mesh)(*args)
    power = out.workload_power_uw
    assert power.sharding.is_equivalent_to(by_node, 3)
    assert not power.is_fully_replicated
    assert len(power.addressable_shards) == 4
    whole = np.asarray(power)
    for s in power.addressable_shards:
        np.testing.assert_array_equal(whole[s.index], np.asarray(s.data))


def test_on_one_device_the_put_is_bit_equal_to_the_put_without_a_mesh():
    mesh = mesh_of(1)
    inputs = temporal_inputs()
    with_mesh = put_fleet_batch(*inputs, mesh=mesh)
    without = put_fleet_batch(*inputs)
    assert len(with_mesh) == len(without) == 11
    for a, b in zip(with_mesh[1:], without[1:]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.devices() == b.devices() == {jax.devices()[0]}
        assert len(a.addressable_shards) == 1
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    program = make_temporal_fleet_program(mesh)
    for got, want in zip(program(*with_mesh), program(*without)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_dev", [1, 4])
def test_a_ratio_windows_arguments_are_put_the_same_way(n_dev):
    """No history and no params: the ratio program's nine arguments, a
    scalar in the params' place, replicated."""
    mesh = mesh_of(n_dev)
    replicated, by_node = fleet_shardings(mesh)
    batch = temporal_inputs()[0]
    args = put_fleet_batch(batch, mesh=mesh)
    assert len(args) == 9
    assert args[0].sharding.is_equivalent_to(replicated, 0)
    for arr in args[1:]:
        assert arr.sharding.is_equivalent_to(by_node, arr.ndim)
        assert len(arr.addressable_shards) == n_dev
    out = make_fleet_program(mesh)(*args)
    want = make_fleet_program(mesh)(*put_fleet_batch(batch))
    np.testing.assert_array_equal(np.asarray(out.workload_power_uw),
                                  np.asarray(want.workload_power_uw))


class Served(test_window_record.Served):
    """``tests/test_window_record.py``'s served temporal aggregator (8 node
    rows × 16 slots, T 4, ``pipelineDepth`` 2) over ``n_dev`` devices,
    with every node of this file's fleet reporting each window."""

    def __init__(self, n_dev: int, **kw) -> None:
        super().__init__(mesh=mesh_of(n_dev), model_params=seeded_params(),
                         **kw)

    def window(self, seq: int, nodes=range(NODES)):
        for k in nodes:
            req = urllib.request.Request(
                self.url("/v1/report"), method="POST",
                data=encode_report(report(k, seq), ZONES, seq=seq, run="r1"))
            assert urllib.request.urlopen(req, timeout=10).status == 204
        return self.agg.aggregate_once()


def published(n_dev: int) -> tuple:
    rec = SpanRecorder(enabled=True)
    with telemetry.installed(rec):
        s = Served(n_dev)
        try:
            out = [s.window(seq) for seq in range(1, TICKS + 4)]
            out.append(s.agg.windows.drain())
            counts = s.agg.windows._window_ledger.snapshot()[1]
            placed = s.agg.windows._params_placed
            spans = [sp for tr in rec.recent_traces()
                     for sp in tr.to_dict()["spans"]]
            metrics = {m.name: m for m in s.agg.collect()}
            gauge = metrics["kepler_fleet_window_h2d_device_bytes"]
            return ([r for r in out if r is not None], counts, spans,
                    placed, gauge.samples[0].value)
        finally:
            s.close()


def test_the_window_over_four_devices_is_the_window_over_one():
    """The same seeded reports through the served path on a mesh of four
    devices and on a mesh of one. Ratio nodes are equal to the bit: their
    watts are products and sums within one node's row, and a row's
    arithmetic does not know how many rows lie beside it. Model nodes to
    float32 rounding: XLA's CPU matmul blocks its rows by the shape it is
    given, two rows of a device against eight, so a sum may be taken in
    another order; rtol 1e-5 is some eighty float32 ulps, and a hundred
    times under the 1e-3 that one bf16 rounding of an operand would
    show."""
    four, counts4, spans4, placed4, gauge4 = published(4)
    one, counts1, spans1, placed1, gauge1 = published(1)
    assert len(four) == len(one) == TICKS + 3
    for a, b in zip(four, one):
        assert a.names == b.names and a.zones == b.zones
        ratio = np.asarray(a.mode) == MODE_RATIO
        np.testing.assert_array_equal(a.node_power_uw[ratio],
                                      b.node_power_uw[ratio])
        np.testing.assert_array_equal(a.wl_power_uw[ratio],
                                      b.wl_power_uw[ratio])
        np.testing.assert_allclose(a.wl_power_uw[~ratio],
                                   b.wl_power_uw[~ratio], rtol=1e-5,
                                   atol=1e-3)
        np.testing.assert_allclose(a.node_power_uw[~ratio],
                                   b.node_power_uw[~ratio], rtol=1e-5)
        assert a.wl_power_uw[~ratio].max() > 1e6  # watts, in µW
    # the record counts the devices and the bytes of the one sent most:
    # the same bytes a window, a quarter of them to each of four devices
    n = counts4["windows"]
    assert n == counts1["windows"] == TICKS + 3
    assert (counts4["devices"], counts1["devices"]) == (4 * n, n)
    assert counts4["h2d_bytes"] == counts1["h2d_bytes"]
    assert counts1["h2d_bytes_max_device"] == counts1["h2d_bytes"]
    assert counts4["h2d_bytes_max_device"] * 4 == counts4["h2d_bytes"]
    assert gauge4 * 4 == gauge1 == counts1["h2d_bytes"] / n
    # the H2D leg and the fetch leg say how many devices they spoke to
    for spans, n_dev in ((spans4, 4), (spans1, 1)):
        for name in ("window.h2d", "window.pipeline_wait"):
            legs = [sp for sp in spans if sp["name"] == name]
            assert len(legs) == n
            assert all(sp["devices"] == n_dev for sp in legs)
        assert all("devices" not in sp for sp in spans
                   if sp["name"] == "window.history")
    # the params were placed once, replicated, and kept
    for placed, n_dev in ((placed4, 4), (placed1, 1)):
        for leaf in jax.tree.leaves(placed[1]):
            assert len(leaf.devices()) == n_dev


# -- only the history rows that exist go up (ISSUE 37) ------------------------

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def ragged_inputs(seed: int = 0, few: bool = False):
    """The fleet of ``temporal_inputs`` with histories as the served path
    has them: a ratio node pushes none (its rows stay zero), a model
    node's pod has 0…T ticks, right-padded, and zeros wherever no tick is
    valid; node 5 has no pods at all. ``few``: one pod a model node."""
    reports = [report(k, 1) for k in range(NODES)]
    reports[5] = dataclasses.replace(
        reports[5], cpu_deltas=np.zeros(0, np.float32), workload_ids=[],
        workload_kinds=np.zeros(0, np.int8), node_cpu_delta=0.0)
    batch = assemble_fleet_batch(reports, n_zones=2, node_bucket=NODES,
                                 workload_bucket=SLOTS)
    rng = np.random.default_rng(seed)
    ticks = rng.integers(0, TICKS + 1, (NODES, SLOTS))
    ticks[0::2] = 0  # ratio nodes between the model nodes
    for k, r in enumerate(reports):
        ticks[k, (1 if few and k % 2 else len(r.workload_ids)):] = 0
    t_valid = np.arange(TICKS) < ticks[..., None]
    hist = rng.random((NODES, SLOTS, TICKS, FEATURES), np.float32)
    hist *= t_valid[..., None]
    return batch, seeded_params(), hist, t_valid


def compact_put(batch, params, hist, t_valid, mesh, fit_rows) -> list:
    """The put as ``WindowScheduler._dispatch_legacy`` makes it where the
    bucket holds fewer rows than the dense window."""
    rows = compact_history(hist, t_valid, mesh.devices.size, fit_rows)
    assert rows is not None
    return put_fleet_batch(batch, params, *rows, mesh=mesh)


@pytest.mark.parametrize("n_dev", [1, 4, 8])
@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_the_compact_put_and_program_publish_what_the_dense_entry_does(
        n_dev, backend):
    """Every field of the ``FleetResult``, bit for bit, over 1, 4 and 8
    shards, with rows of padding in every shard's block."""
    mesh = mesh_of(n_dev)
    _, by_node = fleet_shardings(mesh)
    inputs = ragged_inputs()
    hist, t_valid = inputs[2:]
    sent = t_valid.any(-1).reshape(n_dev, -1).sum(1)
    # ragged: a shard holds more rows than another, a row 1…T ticks
    assert n_dev == 1 or sent.min() < sent.max()
    assert set(t_valid.sum(-1).ravel()) == set(range(TICKS + 1))
    want = make_temporal_fleet_program(mesh, backend=backend)(
        *put_fleet_batch(*inputs, mesh=mesh))
    ladder = BucketLadder(2, 16)
    args = compact_put(*inputs, mesh, ladder.fit)
    assert len(args) == 12
    rows, tv_rows, row_of = args[-3:]
    per = NODES // n_dev * SLOTS
    assert sent.max() <= ladder.bucket < per  # padding, and fewer rows
    assert rows.shape == (n_dev, ladder.bucket, TICKS * FEATURES)
    assert tv_rows.shape == (n_dev, ladder.bucket, TICKS)
    assert row_of.shape == (n_dev, per) and row_of.dtype == np.int32
    for arr in args[-3:]:  # a block a device, and no other's
        assert arr.sharding.is_equivalent_to(by_node, arr.ndim)
        shards = {s.device: s for s in arr.addressable_shards}
        for k, dev in enumerate(mesh.devices.flat):
            assert shards[dev].data.shape == (1,) + arr.shape[1:]
            assert shards[dev].index[0].indices(n_dev)[:2] == (k, k + 1)
    got = make_temporal_fleet_program(mesh, backend=backend, compact=True)(
        *args)
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    model = np.asarray(inputs[0].mode) == MODE_MODEL
    assert np.asarray(got.workload_power_uw)[model].max() > 1e6  # watts


def test_the_rows_sent_are_the_rows_with_a_tick_and_no_others():
    """``compact_history`` against the dense arrays, read back by the
    index alone; and the padding can touch no real row: whatever lies in a
    block's unused rows, the program publishes the same."""
    batch, params, hist, t_valid = ragged_inputs()
    rows, tv_rows, row_of = compact_history(hist, t_valid, 4,
                                            BucketLadder(2, 16).fit)
    r = rows.shape[1]
    dense_hist = hist.reshape(4, -1, TICKS * FEATURES)
    dense_tv = t_valid.reshape(4, -1, TICKS)
    for s in range(4):
        hit = dense_tv[s].any(-1)
        k = int(hit.sum())
        assert sorted(row_of[s][hit]) == list(range(k))  # each row once
        assert (row_of[s][~hit] == r).all()  # past the end: reads zeros
        np.testing.assert_array_equal(rows[s][row_of[s][hit]],
                                      dense_hist[s][hit])
        np.testing.assert_array_equal(tv_rows[s][row_of[s][hit]],
                                      dense_tv[s][hit])
        assert not rows[s, k:].any() and not tv_rows[s, k:].any()
        assert not dense_hist[s][~hit].any()  # what was left out: zeros
        rows[s, k:] = np.nan  # poison what no index names
        tv_rows[s, k:] = True
    mesh = mesh_of(4)
    want = make_temporal_fleet_program(mesh)(
        *put_fleet_batch(batch, params, hist, t_valid, mesh=mesh))
    args = put_fleet_batch(batch, params, rows, tv_rows, row_of, mesh=mesh)
    got = make_temporal_fleet_program(mesh, compact=True)(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_block_is_written_again_only_after_its_window_is_published(
        monkeypatch):
    """The compact arrays of a published window are the next window's to
    write into (fresh ones are page faults: PERF.md section 6); a window
    still in flight keeps its own. The answers are those of a scheduler
    that keeps nothing."""
    from kepler_tpu.fleet import scheduler

    def served(spare_kept: bool):
        handed, given = [], []

        def spy(hist, t_valid, n_shards, fit_rows, out=None):
            rows = compact_history(hist, t_valid, n_shards, fit_rows, out)
            handed.append(None if out is None else id(out[0]))
            given.append(rows[0])
            return rows

        monkeypatch.setattr(scheduler, "compact_history", spy)
        s = Served(4)
        windows = s.agg.windows
        windows._history_rows = BucketLadder(4, 16)
        if not spare_kept:
            windows._history_spare = collections.deque(maxlen=0)
        try:
            out = []
            for seq in range(1, 8):
                res = s.window(seq)
                # depth 2, called directly: the window just dispatched is
                # in flight, and its block is in no one else's hands
                (flying,) = windows._inflight
                assert flying.history_rows[0] is given[-1]
                assert all(flying.history_rows[0] is not kept[0]
                           for kept in windows._history_spare)
                out.append(res)
            out.append(windows.drain())
        finally:
            s.close()
        return [r for r in out if r is not None], handed, given

    kept, handed, given = served(True)
    fresh, none_handed, _ = served(False)
    assert none_handed == [None] * 7
    # the first two windows find nothing to write into; from the third on
    # each takes the block of the window published just before it
    assert handed[:2] == [None, None] and None not in handed[2:]
    assert [id(g) for g in given[2:]] == handed[2:]
    assert len({id(g) for g in given}) == 2
    assert len(kept) == len(fresh) == 7
    for a, b in zip(kept, fresh):
        np.testing.assert_array_equal(a.wl_power_uw, b.wl_power_uw)
        np.testing.assert_array_equal(a.node_power_uw, b.node_power_uw)


@pytest.mark.parametrize("n_dev, base", [(1, 128), (4, 32), (4, 1024)])
def test_where_the_bucket_holds_no_fewer_rows_the_dense_window_goes_up(
        n_dev, base):
    """A shard's dense rows fit the smallest bucket: nothing to gain, so
    ``compact_history`` hands back nothing and the served window puts the
    dense arrays to the dense program (11 arguments, the dense bytes)."""
    batch, params, hist, t_valid = ragged_inputs()
    per = NODES * SLOTS // n_dev
    ladder = BucketLadder(base, 16)
    assert compact_history(hist, t_valid, n_dev, ladder.fit) is None
    assert ladder.bucket == base >= per
    assert compact_history(hist, t_valid, n_dev,
                           BucketLadder(per // 2, 16).fit) is not None
    s = Served(n_dev)
    s.agg.windows._history_rows = ladder
    try:
        s.window(1)
        s.agg.windows.drain()
        body = s.get("/debug/window")
    finally:
        s.close()
    dense = sum(a.nbytes for a in put_fleet_batch(
        batch, params, hist, t_valid, mesh=mesh_of(n_dev))[1:])
    assert body["counts"]["h2d_bytes"] == dense
    assert body["counts"]["hist_rows_sent"] == NODES * SLOTS


@pytest.mark.parametrize("ticks, whole", [(16, True), (8, True), (24, True),
                                          (4, True), (12, True),
                                          (16, False)])
def test_rows_with_a_tick_is_any_over_the_ticks(ticks, whole):
    rng = np.random.default_rng(ticks)
    t_valid = rng.random((3, 40, ticks)) > 0.9
    t_valid[:, ::3] = False
    if not whole:
        t_valid = t_valid[:, ::2]  # a view: no longer one block of memory
    got = _rows_with_a_tick(t_valid)
    assert got.dtype == bool and 0 < got.sum() < got.size
    np.testing.assert_array_equal(got, t_valid.any(-1))


def test_a_count_inside_the_bucket_compiles_nothing_and_a_growth_once():
    mesh = mesh_of(4)
    program = make_temporal_fleet_program(mesh, compact=True)
    ladder = BucketLadder(2, 16)
    want = make_temporal_fleet_program(mesh)

    def window(seed, few):
        inputs = ragged_inputs(seed, few)
        args = compact_put(*inputs, mesh, ladder.fit)
        out = program(*args)
        ref = want(*put_fleet_batch(*inputs, mesh=mesh))
        np.testing.assert_array_equal(np.asarray(out.workload_power_uw),
                                      np.asarray(ref.workload_power_uw))
        return args[-3].shape[1]

    # one pod a model node: a row or two a shard, inside the first bucket
    assert [window(seed, True) for seed in range(3)] == [2, 2, 2]
    assert program._cache_size() == 1
    # every pod: the fullest shard needs 5 or 6 rows, one growth to 8
    assert [window(seed, False) for seed in range(3)] == [8, 8, 8]
    assert program._cache_size() == 2
    # fewer again: the bucket holds (shrinking waits for 16 such windows)
    assert window(7, True) == 8
    assert program._cache_size() == 2


def test_the_compact_program_over_four_shards_holds_no_collective():
    """What the kepljax registry's KTL122 spec holds for its case, on the
    compiled program of this fleet: the estimate and its gather are
    shard-local."""
    mesh = mesh_of(4)
    args = compact_put(*ragged_inputs(), mesh, BucketLadder(2, 16).fit)
    lowered = make_temporal_fleet_program(mesh, compact=True).lower(*args)
    assert "jit_temporal_fleet_window" in lowered.as_text(debug_info=True)
    hlo = lowered.compile().as_text()
    assert "gather" in hlo
    for op in COLLECTIVES:
        assert op not in hlo, op


# -- the estimator runs on the rows that were sent ----------------------------

@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_a_valid_pod_with_no_tick_reads_the_empty_windows_estimate(
        n_dev, backend):
    """A model node's pod that is in the batch but has no tick is not sent;
    the compact program gives it the estimate of an empty window (the
    head's bias, clamped), bit for bit what the dense program gives it,
    and not 0."""
    mesh = mesh_of(n_dev)
    inputs = ragged_inputs(seed=3)
    batch, _, _, t_valid = inputs
    model = (np.asarray(batch.mode) == MODE_MODEL)[:, None]
    silent = np.asarray(batch.workload_valid) & model & ~t_valid.any(-1)
    assert silent.any(1).sum() > 1  # on more than one node
    want = make_temporal_fleet_program(mesh, backend=backend)(
        *put_fleet_batch(*inputs, mesh=mesh))
    got = make_temporal_fleet_program(mesh, backend=backend, compact=True)(
        *compact_put(*inputs, mesh, BucketLadder(2, 16).fit))
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    power = np.asarray(got.workload_power_uw)[silent]  # [pods, Z]
    assert (power == power[0]).all()  # one window's estimate, every pod
    assert (power > 1e6).all()  # watts, not the 0 of a slot left empty
    assert not np.asarray(got.workload_power_uw)[
        ~np.asarray(batch.workload_valid)].any()


def largest_dot_rows(hlo: str) -> int:
    """The leading dimension of the compiled program's largest ``dot``."""
    shapes = [tuple(int(d) for d in m.split(",")) for m in re.findall(
        r"= \w+\[([\d,]+)\]\{[\d,]*\} dot\(", hlo)]
    assert shapes
    return max(shapes, key=math.prod)[0]


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_compact_programs_largest_dot_has_the_blocks_rows(n_dev):
    """Per shard the trunk's matmuls run over the rows of the block (and
    the zero rows past it, to a multiple of 8), ``T`` positions each; the
    dense window's rows appear in no ``dot``. A return to rebuilding the
    dense history before the trunk fails here."""
    mesh = mesh_of(n_dev)
    inputs = ragged_inputs(few=True)
    per = NODES * SLOTS // n_dev
    args = compact_put(*inputs, mesh, BucketLadder(2, 16).fit)
    r = args[-3].shape[1]
    assert r < 8 < per
    compact = make_temporal_fleet_program(mesh, compact=True).lower(
        *args).compile().as_text()
    dense = make_temporal_fleet_program(mesh).lower(
        *put_fleet_batch(*inputs, mesh=mesh)).compile().as_text()
    assert largest_dot_rows(dense) == per * TICKS
    assert largest_dot_rows(compact) == 8 * TICKS


@pytest.mark.parametrize("n_dev", [1, 4])
def test_an_estimate_altered_where_it_is_made_moves_one_sent_pod(
        monkeypatch, n_dev):
    """``predict_temporal`` wrapped as ``tests/chipbench/broken_launch.py``
    wraps it for ``answer_altered`` (the program looks it up when it is
    traced), served with a ladder of rows small enough that the compact
    program runs: the second row the estimator runs on is a sent model
    pod's, and that pod's watts, every zone, are a tenth higher in every
    window; no other watt moves but its node's sum."""
    from kepler_tpu.models import temporal

    def served():
        s = Served(n_dev)
        s.agg.windows._history_rows = BucketLadder(4, 16)
        try:
            out = [s.window(seq) for seq in range(1, 5)]
            out.append(s.agg.windows.drain())
            counts = s.get("/debug/window")["counts"]
        finally:
            s.close()
        return [r for r in out if r is not None], counts

    want, counts = served()
    real = temporal.predict_temporal

    def altered(*args, **kw):
        watts = real(*args, **kw)
        return watts.at[1, 0].multiply(1.1)

    monkeypatch.setattr(temporal, "predict_temporal", altered)
    got, altered_counts = served()
    assert altered_counts == counts
    # the compact program served every window: fewer rows than the dense
    assert counts["hist_rows_sent"] == counts["rows_program"] \
        < counts["windows"] * NODES * SLOTS
    assert len(got) == len(want) == 4
    for a, b in zip(got, want):
        moved = (a.wl_power_uw != b.wl_power_uw).any(-1)
        assert moved.sum() == 1
        (node,), (slot,) = np.nonzero(moved)
        assert a.mode[node] == MODE_MODEL
        np.testing.assert_allclose(a.wl_power_uw[node, slot],
                                   1.1 * b.wl_power_uw[node, slot],
                                   rtol=1e-6)
        assert b.wl_power_uw[node, slot].min() > 1e6
        others = np.arange(len(a.names)) != node
        np.testing.assert_array_equal(a.node_power_uw[others],
                                      b.node_power_uw[others])
        assert (a.node_power_uw[node] > b.node_power_uw[node]).all()


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_record_counts_the_rows_and_the_bytes_that_were_put(n_dev):
    """``hist_rows_sent``, ``h2d_bytes`` and ``h2d_bytes_max_device`` of
    ``/debug/window`` against the arrays of a put of the same shapes."""
    s = Served(n_dev)
    # model nodes (odd k) hold 3 + k % 4 pods: 4, 6, 4, 6; a ladder from 4
    # rows fits the 6 of the fullest pair of nodes in 8 and all 20 in 32
    s.agg.windows._history_rows = BucketLadder(4, 16)
    try:
        s.window(1)
        s.window(2)
        first = s.get("/debug/window")
        for seq in range(3, 6):
            s.window(seq)
        s.agg.windows.drain()
        last = s.get("/debug/window")
    finally:
        s.close()
    grew = {k: last["counts"][k] - first["counts"][k]
            for k in last["counts"]}
    assert grew["windows"] == 4
    r = {1: 32, 4: 8}[n_dev]
    assert grew["hist_rows_sent"] == 4 * n_dev * r
    batch = temporal_inputs()[0]
    hist = np.zeros((NODES, SLOTS, TICKS, FEATURES), np.float32)
    args = compact_put(batch, seeded_params(), hist,
                       np.zeros((NODES, SLOTS, TICKS), bool),
                       mesh_of(n_dev), lambda need: r)
    assert [a.shape for a in args[-3:]] == [
        (n_dev, r, TICKS * FEATURES), (n_dev, r, TICKS),
        (n_dev, NODES * SLOTS // n_dev)]
    put = sum(a.nbytes for a in args[1:])
    assert put < hist.nbytes  # under the dense history alone
    assert grew["h2d_bytes"] == 4 * put
    assert grew["h2d_bytes_max_device"] * n_dev == grew["h2d_bytes"]
    assert last["stats"]["last_h2d_device_bytes"] * n_dev == put
    rows = last["records"]
    assert "hist_rows_sent" not in rows["fields"]  # in the sums alone
    at = rows["fields"].index("h2d_bytes")
    assert [row[at] for row in rows["rows"][-4:]] == [put] * 4


def test_the_benchmarks_reader_reads_a_quarter_of_the_bytes_a_device():
    """``h2d_max_device_mb.flood4`` as the benchmark reads it: its metric
    file's reader and arguments over two ``/debug/window`` bodies of an
    aggregator served over four devices, beside ``h2d_mb.flood``."""
    from types import SimpleNamespace

    from chipbench import spec

    cell = spec.load_cell(REPO, "temporal-k8s-limit.flood")
    s = Served(4)
    try:
        s.window(1)
        s.window(2)
        first = s.get("/debug/window")
        for seq in range(3, 6):
            s.window(seq)
        last = s.get("/debug/window")
    finally:
        s.close()
    assert last["counts"]["windows"] - first["counts"]["windows"] == 3
    assert last["stats"]["last_h2d_device_bytes"] * 4 * 3 == \
        last["counts"]["h2d_bytes"] - first["counts"]["h2d_bytes"]
    this = SimpleNamespace(drive=SimpleNamespace(
        debug={"first": first, "last": last}))
    got = {}
    for name in ("h2d_max_device_mb.flood4", "h2d_mb.flood"):
        read, args = cell.reader(name)
        got[name] = read(this, **args)
    assert got["h2d_max_device_mb.flood4"] * 4 == pytest.approx(
        got["h2d_mb.flood"])
    assert got["h2d_mb.flood"] > 8 * 16 * 4 * 7 * 4 / 1e6  # the history
    # a body of a program without the counter: the metric is left out
    for body in (first, last):
        del body["counts"]["h2d_bytes_max_device"]
    read, args = cell.reader("h2d_max_device_mb.flood4")
    assert read(this, **args) is None


def test_a_node_silent_past_stale_after_leaves_the_results():
    """What the four-chip cell's ``staleAfter`` leans on: a node whose
    last report is older than ``aggregator.staleAfter`` when a window is
    snapshotted is in none of that window's answers, and one heard from
    within it stays however long the others have been silent."""
    now = [1000.0]
    s = Served(1, stale_after=15.0, clock=lambda: now[0])
    try:
        for seq in range(1, 3):
            s.window(seq)
            now[0] += 1.0
        res = s.agg.windows.drain()
        assert sorted(res.names) == [f"node-{k}" for k in range(NODES)]
        # nodes 0-2 go silent; 14 s later they are still answered
        now[0] += 13.0  # their last report is 14 s old
        s.window(3, nodes=range(3, NODES))
        res = s.agg.windows.drain()
        assert len(res.names) == NODES
        body = s.get("/v1/results")
        assert len(body["nodes"]) == NODES
        # 2 s more and they are past staleAfter: gone from the window,
        # from /v1/results, and the model nodes' histories with them
        now[0] += 2.0
        s.window(4, nodes=range(3, NODES))
        res = s.agg.windows.drain()
        assert sorted(res.names) == [f"node-{k}" for k in range(3, NODES)]
        body = s.get("/v1/results")
        assert sorted(body["nodes"]) == [f"node-{k}"
                                         for k in range(3, NODES)]
        assert sorted(s.agg._history) == [f"node-{k}"
                                          for k in range(3, NODES) if k % 2]
    finally:
        s.close()
