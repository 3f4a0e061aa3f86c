"""The device-program registry: what kepljax traces, and each program's
declared contract.

One :class:`ProgramSpec` per jitted device program the attribution
stack serves, each with representative bucket-shape cases (including
the pad-row/minimal-bucket edges the ladders actually produce) and a
declarative contract the KTL120-123 checks enforce:

- ``donates`` — user-level argument positions whose buffers the
  program consumes; KTL121 requires every flattened leaf of those args
  to carry real input/output aliasing in the lowered module, and no
  undeclared arg to alias.
- ``allowed_collectives`` — the complete set of explicit communication
  primitives the program may contain (KTL122). Empty means "this
  program must be communication-free at the jaxpr tier" — the PR 7
  invariant that the only cross-shard step in a fleet window is the
  caller's result fetch.
- ``allowed_half_casts`` — the half-precision ``convert_element_type``
  pairs that are DECLARED boundaries (the packed f16 wire quantizer,
  bf16 matmul operand feeds). Any other half cast — and any half
  accumulation into a dot/reduction, which no entry may allow — is a
  KTL120 finding.
- ``require_shard_map`` — the program's shard-locality is structural:
  losing the ``shard_map`` (a regression to a replicated-index gather
  GSPMD would satisfy with an all-gather at partitioning time, which
  the jaxpr tier cannot see) fails KTL122 even with an empty
  collective set.

Builders import jax and the program modules lazily so importing the
analysis package (rule registration, docs generation) stays free of
accelerator toolchain costs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

# avals/builders talk in these dtype names; resolved lazily in _sds
_F32 = "float32"
_I32 = "int32"
_BOOL = "bool"

#: packed fleet programs quantize to the f16 wire format exactly once
_F16_OUT = frozenset({"float32->float16"})
#: bf16 matmul-operand feeds (accumulators stay f32 via acc_matmul)
_BF16_OPS = frozenset({"float32->bfloat16"})
#: training graphs additionally carry the transpose of each operand
#: cast (the backward of f32→bf16 is bf16→f32 on the cotangent)
_BF16_TRAIN = frozenset({"float32->bfloat16", "bfloat16->float32"})


@dataclass(frozen=True)
class ProgramCase:
    """One representative shape point for a spec (name + build knobs)."""

    name: str
    note: str = ""
    dims: dict[str, Any] = field(default_factory=dict)


@dataclass(frozen=True)
class ProgramSpec:
    """One registered device program + its declared contract."""

    name: str
    source: str  # repo-relative module the program lives in
    description: str
    build: Callable[[ProgramCase], tuple]  # → (jitted fn, avals tuple)
    cases: tuple[ProgramCase, ...]
    n_devices: int = 8
    donates: tuple[int, ...] = ()
    allowed_collectives: frozenset[str] = frozenset()
    allowed_half_casts: frozenset[str] = frozenset()
    require_shard_map: bool = False


# ---------------------------------------------------------------------------
# builder helpers (lazy jax)
# ---------------------------------------------------------------------------


def _sds(shape: tuple[int, ...], dtype: str) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _tree_avals(tree: Any) -> Any:
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _mesh(n: int, axes: tuple[str, ...] = ("node",),
          shape: tuple[int, ...] | None = None) -> Any:
    import jax

    from kepler_tpu.parallel.mesh import make_mesh

    count = 1
    for s in shape or (n,):
        count *= s
    return make_mesh(shape or (n,), axes, devices=jax.devices()[:count])


def _mlp_avals(n_zones: int) -> Any:
    import jax

    from kepler_tpu.models.mlp import init_mlp

    return _tree_avals(dict(init_mlp(jax.random.PRNGKey(0),
                                     n_zones=n_zones)))


def _temporal_avals(n_zones: int) -> Any:
    import jax

    from kepler_tpu.models.temporal import init_temporal

    return _tree_avals(dict(init_temporal(jax.random.PRNGKey(0),
                                          n_zones=n_zones)))


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def _build_packed(case: ProgramCase) -> tuple:
    from kepler_tpu.parallel.packed import (make_packed_fleet_program,
                                            packed_width)

    d = case.dims
    nb, wb, z = d["n"], d["w"], d["z"]
    mb = d.get("m")
    model_mode = d.get("model_mode")
    backend = d.get("backend", "einsum")
    local = bool(d.get("local", 0))
    mesh = _mesh(d.get("devices", 8))
    fn = make_packed_fleet_program(
        mesh, n_workloads=wb, n_zones=z, model_mode=model_mode,
        backend=backend, model_bucket=mb, local_model_rows=local)
    params = _mlp_avals(z) if model_mode else _sds((), _F32)
    avals: list = [params, _sds((nb, packed_width(wb, z)), _F32)]
    if mb is not None:
        n_seg = d.get("devices", 8) if local else 1
        avals.append(_sds((n_seg * mb,), _I32))
    return fn, tuple(avals)


def _build_window_update(case: ProgramCase) -> tuple:
    from kepler_tpu.fleet.window import (MultiHostWindowEngine,
                                         PackedWindowEngine,
                                         ShardedWindowEngine)
    from kepler_tpu.parallel.packed import packed_width

    d = case.dims
    nb, wb, z, db = d["n"], d["w"], d["z"], d["db"]
    width = packed_width(wb, z)
    if d.get("multihost"):
        # virtual 2-host split over the 8 traced devices: the update is
        # the HOST-LOCAL donated scatter (identical discipline, owned
        # shards only) — traced from process 0's perspective
        mesh = _mesh(8)
        devs = list(mesh.devices.flat)
        proc_of = {dev: (0 if k < 4 else 1)
                   for k, dev in enumerate(devs)}.get
        engine: Any = MultiHostWindowEngine(mesh, process_index=0,
                                            device_process=proc_of)
    elif d.get("sharded"):
        engine = ShardedWindowEngine(_mesh(8))
    else:
        engine = PackedWindowEngine(_mesh(8))
    fn = engine._update_for(nb, width, db)[0]
    return fn, (_sds((nb, width), _F32), _sds((db, width), _F32),
                _sds((db,), _I32))


def _build_fused(case: ProgramCase) -> tuple:
    from kepler_tpu.parallel.packed import (make_fused_window_program,
                                            packed_width)

    d = case.dims
    nb, wb, z, k, db = d["n"], d["w"], d["z"], d["k"], d["db"]
    mb = d.get("m")
    model_mode = d.get("model_mode")
    mesh = _mesh(d.get("devices", 8))
    fn = make_fused_window_program(
        mesh, n_workloads=wb, n_zones=z, model_mode=model_mode,
        backend=d.get("backend", "einsum"), model_bucket=mb)
    params = _mlp_avals(z) if model_mode else _sds((), _F32)
    width = packed_width(wb, z)
    avals: list = [params, _sds((nb, width), _F32),
                   _sds((k, db, width), _F32), _sds((k, db), _I32)]
    if mb is not None:
        avals.append(_sds((k, mb), _I32))
    return fn, tuple(avals)


def _build_fleet(case: ProgramCase) -> tuple:
    from kepler_tpu.parallel.aggregator_core import (
        make_fleet_program, make_temporal_fleet_program)

    d = case.dims
    n, w, z = d["n"], d["w"], d["z"]
    mesh = _mesh(8)
    batch = (
        _sds((n, z), _F32), _sds((n, z), _BOOL), _sds((n,), _F32),
        _sds((n, w), _F32), _sds((n, w), _BOOL), _sds((n,), _F32),
        _sds((n,), _F32), _sds((n,), _I32),
    )
    if d.get("temporal"):
        # the program the aggregator serves: the history as its valid rows
        # (``r`` a shard), the estimator run on them on each device
        t, f, r = d["t"], 7, d["r"]
        fn = make_temporal_fleet_program(mesh, compact=True)
        return fn, (_temporal_avals(z),) + batch + (
            _sds((8, r, t * f), _F32), _sds((8, r, t), _BOOL),
            _sds((8, n // 8 * w), _I32))
    fn = make_fleet_program(mesh, model_mode="mlp")
    return fn, (_mlp_avals(z),) + batch


def _build_pallas_temporal(case: ProgramCase) -> tuple:
    import functools

    import jax

    from kepler_tpu.ops.pallas_temporal import last_query_trunk_pallas

    d = case.dims
    b, t = d["b"], d["t"]
    fn = jax.jit(functools.partial(last_query_trunk_pallas,
                                   block_rows=d["block"], interpret=True))
    return fn, (_temporal_avals(d["z"]), _sds((b, t, 7), _F32),
                _sds((b, t), _BOOL))


def _build_pallas_attribution(case: ProgramCase) -> tuple:
    import functools

    import jax

    from kepler_tpu.ops.pallas_attribution import attribute_fleet_pallas

    d = case.dims
    n, w, z = d["n"], d["w"], d["z"]
    fn = jax.jit(functools.partial(attribute_fleet_pallas, interpret=True))
    return fn, (
        _sds((n, z), _F32), _sds((n, z), _BOOL), _sds((n,), _F32),
        _sds((n, w), _F32), _sds((n, w), _BOOL), _sds((n,), _F32),
        _sds((n,), _F32))


def _build_ring(case: ProgramCase) -> tuple:
    from kepler_tpu.parallel.ring import make_ring_attention

    d = case.dims
    b, t, h, dh = d["b"], d["t"], d["h"], d["dh"]
    fn = make_ring_attention(_mesh(8, ("seq",)))
    q = _sds((b, t, h, dh), _F32)
    return fn, (q, q, q, _sds((b, t), _BOOL))


def _build_ulysses(case: ProgramCase) -> tuple:
    from kepler_tpu.parallel.ulysses import make_ulysses_attention

    d = case.dims
    b, t, h, dh = d["b"], d["t"], d["h"], d["dh"]
    fn = make_ulysses_attention(_mesh(4, ("seq",)))
    q = _sds((b, t, h, dh), _F32)
    return fn, (q, q, q, _sds((b, t), _BOOL))


def _build_pipeline(case: ProgramCase) -> tuple:
    import jax

    from kepler_tpu.models.deep import init_deep
    from kepler_tpu.parallel.pipeline import make_pipelined_deep

    d = case.dims
    fn = make_pipelined_deep(_mesh(8, ("stage",)),
                             n_microbatches=d.get("mb", 4))
    params = dict(init_deep(jax.random.PRNGKey(0), n_zones=d["z"],
                            n_stages=8))
    return fn, (_tree_avals(params), _sds((d["n"], 7), _F32),
                _sds((d["n"],), _BOOL))


def _build_expert(case: ProgramCase) -> tuple:
    import jax

    from kepler_tpu.models.moe import init_moe
    from kepler_tpu.parallel.expert import make_expert_parallel_moe

    d = case.dims
    fn = make_expert_parallel_moe(_mesh(8, ("expert",)))
    params = dict(init_moe(jax.random.PRNGKey(0), n_zones=d["z"],
                           n_experts=8))
    return fn, (_tree_avals(params), _sds((d["n"], 7), _F32),
                _sds((d["n"],), _I32), _sds((d["n"],), _F32))


def _build_sequence(case: ProgramCase) -> tuple:
    import jax

    from kepler_tpu.models.temporal import init_temporal
    from kepler_tpu.models.train import create_train_state, make_optimizer
    from kepler_tpu.parallel.sequence import (
        make_sequence_parallel_train_step, make_temporal_program)

    d = case.dims
    w, t, z, f = d["w"], d["t"], d["z"], 7
    mesh = _mesh(8, ("seq",))
    hist = _sds((w, t, f), _F32)
    wl_valid = _sds((w,), _BOOL)
    t_valid = _sds((w, t), _BOOL)
    params = dict(init_temporal(jax.random.PRNGKey(0), n_zones=z))
    if d.get("train"):
        step = make_sequence_parallel_train_step(mesh, make_optimizer())
        state = create_train_state(params, make_optimizer())
        return step, (_tree_avals(state), hist, wl_valid, t_valid,
                      _sds((w, z), _F32))
    fn = make_temporal_program(mesh)
    return fn, (_tree_avals(params), hist, wl_valid, t_valid)


def _build_trainer(case: ProgramCase) -> tuple:
    import jax

    from kepler_tpu.models.mlp import init_mlp
    from kepler_tpu.models.train import create_train_state, make_optimizer
    from kepler_tpu.parallel.trainer import make_distributed_train_step

    d = case.dims
    mesh = _mesh(8, ("node", "model"), shape=(4, 2))
    step = make_distributed_train_step(mesh, make_optimizer())
    state = create_train_state(
        init_mlp(jax.random.PRNGKey(0), n_zones=d["z"]), make_optimizer())
    return step, (_tree_avals(state), _sds((d["n"], d["w"], 7), _F32),
                  _sds((d["n"], d["w"]), _BOOL),
                  _sds((d["n"], d["w"], d["z"]), _F32))


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

DEVICE_PROGRAMS: tuple[ProgramSpec, ...] = (
    ProgramSpec(
        name="packed.dense_ratio",
        source="kepler_tpu/parallel/packed.py",
        description="packed-f16 ratio-only fleet program (einsum, GSPMD "
                    "node sharding)",
        build=_build_packed,
        cases=(
            ProgramCase("n16_w8_z2", dims={"n": 16, "w": 8, "z": 2}),
            ProgramCase("pad_n8_w1_z1", "minimal ladder rung: one "
                        "workload column, one zone, one row per shard",
                        dims={"n": 8, "w": 1, "z": 1}),
        ),
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="packed.dense_mlp",
        source="kepler_tpu/parallel/packed.py",
        description="packed-f16 mixed-fleet program, dense mlp estimator "
                    "(f32 compute off-TPU)",
        build=_build_packed,
        cases=(
            ProgramCase("n16_w8_z2",
                        dims={"n": 16, "w": 8, "z": 2,
                              "model_mode": "mlp"}),
        ),
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="packed.sparse_mlp",
        source="kepler_tpu/parallel/packed.py",
        description="sparse MODE_MODEL gather variant (replicated "
                    "model_rows; single-device engine path)",
        build=_build_packed,
        cases=(
            ProgramCase("n8_w8_z2_m4",
                        dims={"n": 8, "w": 8, "z": 2, "m": 4,
                              "model_mode": "mlp", "devices": 1}),
        ),
        n_devices=1,
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="packed.sparse_local_mlp",
        source="kepler_tpu/parallel/packed.py",
        description="shard_map sparse variant: shard-local model_rows "
                    "gather/scatter, zero collectives (PR 7 invariant)",
        build=_build_packed,
        cases=(
            ProgramCase("n16_w8_z2_m2",
                        dims={"n": 16, "w": 8, "z": 2, "m": 2,
                              "model_mode": "mlp", "local": 1}),
            ProgramCase("pad_n8_w1_z1_m1", "pad-heavy edge: every shard "
                        "one row, model bucket 1",
                        dims={"n": 8, "w": 1, "z": 1, "m": 1,
                              "model_mode": "mlp", "local": 1}),
        ),
        allowed_half_casts=_F16_OUT,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="packed.sparse_local_multihost",
        source="kepler_tpu/parallel/packed.py",
        description="the multi-host window's SPMD program: shard_map "
                    "sparse variant at the GLOBAL-mesh shape two "
                    "processes' device counts span (2 hosts x 4 "
                    "devices traced as one 8-shard mesh) — zero "
                    "collectives pins that the only cross-host traffic "
                    "in a window is the dispatch itself (ISSUE 15)",
        build=_build_packed,
        cases=(
            ProgramCase("hosts2_n16_w8_z2_m2",
                        "per-host bucket 2 over 2x4 devices",
                        dims={"n": 16, "w": 8, "z": 2, "m": 2,
                              "model_mode": "mlp", "local": 1}),
            ProgramCase("hosts2_pad_n8_w1_z1_m1", "minimal multi-host "
                        "rung: one row per shard across both hosts",
                        dims={"n": 8, "w": 1, "z": 1, "m": 1,
                              "model_mode": "mlp", "local": 1}),
        ),
        allowed_half_casts=_F16_OUT,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="packed.pallas_dense",
        source="kepler_tpu/parallel/packed.py",
        description="packed program with the Mosaic attribution kernel "
                    "(shard_map over node, interpret off-TPU)",
        build=_build_packed,
        cases=(
            ProgramCase("n16_w8_z2",
                        dims={"n": 16, "w": 8, "z": 2,
                              "backend": "pallas"}),
        ),
        allowed_half_casts=_F16_OUT,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="window.fused_ratio",
        source="kepler_tpu/parallel/packed.py",
        description="fused device-resident window loop, ratio-only: one "
                    "donated lax.scan applies K intervals' delta rows "
                    "and emits K packed f16 outputs per dispatch — the "
                    "per-window host↔device sync amortized K× (zero "
                    "collectives: the only cross-shard step stays the "
                    "caller's batched publish fetch)",
        build=_build_fused,
        cases=(
            ProgramCase("n16_w8_z2_k4_d8",
                        dims={"n": 16, "w": 8, "z": 2, "k": 4, "db": 8}),
            ProgramCase("pad_n8_w1_z1_k2_d1", "minimal fused rung: "
                        "steady fleet, one delta row per interval",
                        dims={"n": 8, "w": 1, "z": 1, "k": 2, "db": 1}),
        ),
        donates=(1,),
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="window.fused_sparse_mlp",
        source="kepler_tpu/parallel/packed.py",
        description="fused window loop, sparse MODE_MODEL variant: each "
                    "scan step gathers the interval's model rows "
                    "(replicated indices, single-device engine path) "
                    "through the mlp estimator — f32 accumulators, f16 "
                    "only at the packed output boundary",
        build=_build_fused,
        cases=(
            ProgramCase("n8_w8_z2_m4_k2_d4",
                        dims={"n": 8, "w": 8, "z": 2, "m": 4, "k": 2,
                              "db": 4, "model_mode": "mlp",
                              "devices": 1}),
        ),
        n_devices=1,
        donates=(1,),
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="window.fused_pallas",
        source="kepler_tpu/ops/pallas_attribution.py",
        description="fused window mega-kernel scan (single-device "
                    "pallas path): scatter + unpack + ratio attribution "
                    "as ONE kernel body per scan step, interpret mode "
                    "off-TPU",
        build=_build_fused,
        cases=(
            ProgramCase("n16_w8_z2_k2_d4",
                        dims={"n": 16, "w": 8, "z": 2, "k": 2, "db": 4,
                              "backend": "pallas", "devices": 1}),
        ),
        n_devices=1,
        donates=(1,),
        allowed_half_casts=_F16_OUT,
    ),
    ProgramSpec(
        name="window.update",
        source="kepler_tpu/fleet/window.py",
        description="donated in-place scatter-update of the resident "
                    "packed batch (delta H2D path)",
        build=_build_window_update,
        cases=(
            ProgramCase("n16_w8_z2_d8",
                        dims={"n": 16, "w": 8, "z": 2, "db": 8}),
            ProgramCase("d1", "single-row delta (the steady-fleet case)",
                        dims={"n": 16, "w": 8, "z": 2, "db": 1}),
        ),
        donates=(0,),
    ),
    ProgramSpec(
        name="window.update_sharded",
        source="kepler_tpu/fleet/window.py",
        description="shard-local donated scatter-update (per-shard ring "
                    "of the ShardedWindowEngine)",
        build=_build_window_update,
        cases=(
            ProgramCase("s2_w8_z2_d2",
                        dims={"n": 2, "w": 8, "z": 2, "db": 2,
                              "sharded": 1}),
        ),
        donates=(0,),
    ),
    ProgramSpec(
        name="window.update_multihost",
        source="kepler_tpu/fleet/window.py",
        description="host-local donated scatter-update of the "
                    "multi-host engine (a virtual 2-host topology's "
                    "process-0 view: same donation discipline, owned "
                    "shards only)",
        build=_build_window_update,
        cases=(
            ProgramCase("hosts2_s2_w8_z2_d2",
                        dims={"n": 2, "w": 8, "z": 2, "db": 2,
                              "multihost": 1}),
        ),
        donates=(0,),
    ),
    ProgramSpec(
        name="fleet.dense_mlp",
        source="kepler_tpu/parallel/aggregator_core.py",
        description="unpacked sharded fleet program with mlp estimator "
                    "(GSPMD node sharding, no explicit collectives)",
        build=_build_fleet,
        cases=(
            ProgramCase("n16_w4_z2", dims={"n": 16, "w": 4, "z": 2}),
        ),
        allowed_half_casts=_BF16_OPS,
    ),
    ProgramSpec(
        name="fleet.temporal",
        source="kepler_tpu/parallel/aggregator_core.py",
        description="temporal fleet program as served (dense causal "
                    "attention over per-workload history windows, sent as "
                    "their valid rows and expanded shard by shard)",
        build=_build_fleet,
        cases=(
            ProgramCase("n16_w4_t8_z2_r4",
                        dims={"n": 16, "w": 4, "z": 2, "t": 8, "r": 4,
                              "temporal": 1}),
        ),
        allowed_half_casts=_BF16_OPS,
    ),
    ProgramSpec(
        name="ops.pallas_attribution",
        source="kepler_tpu/ops/pallas_attribution.py",
        description="Mosaic outer-product attribution kernel, unsharded "
                    "(interpret mode off-TPU)",
        build=_build_pallas_attribution,
        cases=(
            ProgramCase("n8_w8_z2", dims={"n": 8, "w": 8, "z": 2}),
        ),
        n_devices=1,
    ),
    ProgramSpec(
        name="ops.pallas_temporal",
        source="kepler_tpu/ops/pallas_temporal.py",
        description="the served temporal trunk as one Mosaic kernel over "
                    "blocks of history rows, unsharded (interpret mode "
                    "off-TPU)",
        build=_build_pallas_temporal,
        cases=(
            ProgramCase("b40_t16_block16", "a part-full last block",
                        dims={"b": 40, "t": 16, "block": 16, "z": 2}),
        ),
        n_devices=1,
        # bf16 matmul operands; bf16-rounded K, V, query and
        # probabilities widened back to f32 for exact VPU products
        allowed_half_casts=frozenset({"float32->bfloat16",
                                      "bfloat16->float32"}),
    ),
    ProgramSpec(
        name="ring.attention",
        source="kepler_tpu/parallel/ring.py",
        description="ring attention: KV blocks rotate via ppermute, "
                    "online-softmax partials merge in f32",
        build=_build_ring,
        cases=(
            ProgramCase("b2_t16_h4_d8",
                        dims={"b": 2, "t": 16, "h": 4, "dh": 8}),
        ),
        allowed_collectives=frozenset({"ppermute"}),
        allowed_half_casts=_BF16_OPS,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="ulysses.attention",
        source="kepler_tpu/parallel/ulysses.py",
        description="Ulysses attention: all_to_all head/sequence "
                    "re-partition around dense attention",
        build=_build_ulysses,
        cases=(
            ProgramCase("b2_t16_h4_d8",
                        dims={"b": 2, "t": 16, "h": 4, "dh": 8}),
        ),
        n_devices=4,
        allowed_collectives=frozenset({"all_to_all", "all_gather"}),
        allowed_half_casts=_BF16_OPS,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="pipeline.deep",
        source="kepler_tpu/parallel/pipeline.py",
        description="GPipe microbatch pipeline over the deep estimator's "
                    "stage ring",
        build=_build_pipeline,
        cases=(
            ProgramCase("n16_z2_mb4", dims={"n": 16, "z": 2, "mb": 4}),
        ),
        allowed_collectives=frozenset({"ppermute", "psum"}),
        allowed_half_casts=_BF16_OPS,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="expert.moe",
        source="kepler_tpu/parallel/expert.py",
        description="expert-parallel MoE: all_to_all dispatch/combine "
                    "around batched expert MLPs",
        build=_build_expert,
        cases=(
            ProgramCase("n16_z2", dims={"n": 16, "z": 2}),
        ),
        allowed_collectives=frozenset({"all_to_all"}),
        allowed_half_casts=_BF16_OPS,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="sequence.temporal",
        source="kepler_tpu/parallel/sequence.py",
        description="sequence-parallel temporal estimator (ring attention "
                    "inside the trunk)",
        build=_build_sequence,
        cases=(
            ProgramCase("w4_t16_z2", dims={"w": 4, "t": 16, "z": 2}),
        ),
        allowed_collectives=frozenset({"ppermute"}),
        allowed_half_casts=_BF16_OPS,
        require_shard_map=True,
    ),
    ProgramSpec(
        name="sequence.train_step",
        source="kepler_tpu/parallel/sequence.py",
        description="sequence-parallel temporal TRAIN step (donated "
                    "state, ring reversed in the backward)",
        build=_build_sequence,
        cases=(
            ProgramCase("w4_t16_z2",
                        dims={"w": 4, "t": 16, "z": 2, "train": 1}),
        ),
        donates=(0,),
        allowed_collectives=frozenset({"ppermute", "psum"}),
        require_shard_map=True,
    ),
    ProgramSpec(
        name="trainer.train_step",
        source="kepler_tpu/parallel/trainer.py",
        description="DP×TP mlp train step (donated state; collectives "
                    "derived by GSPMD at partitioning, none explicit)",
        build=_build_trainer,
        cases=(
            ProgramCase("b8_w4_z2", dims={"n": 8, "w": 4, "z": 2}),
        ),
        donates=(0,),
        allowed_half_casts=_BF16_TRAIN,
    ),
)


def spec_by_name(name: str) -> ProgramSpec:
    for spec in DEVICE_PROGRAMS:
        if spec.name == name:
            return spec
    raise KeyError(name)
