"""kepljax device-tier tests: KTL120-123 fixtures, the snapshot
ratchet, CLI surface, and the shipped-tree acceptance gates.

Fixture specs are tiny synthetic jitted programs exercising exactly one
failure mode each (the bad/good pairs every rule family must have);
the acceptance tests additionally regress REAL registry entries —
flipping the window update's donation off, deleting the sparse
program's shard-local indexing — and assert the right family fires.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kepler_tpu.analysis import all_rules  # noqa: E402
from kepler_tpu.analysis.__main__ import main, render_sarif  # noqa: E402
from kepler_tpu.analysis.device import (  # noqa: E402
    DEVICE_PROGRAMS,
    ProgramCase,
    ProgramSpec,
    SNAPSHOT_NAME,
    analyze_device_programs,
    clear_trace_cache,
    load_snapshots,
    spec_by_name,
    write_snapshots,
)
from kepler_tpu.analysis.engine import LintResult  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FIXTURE_SOURCE = "kepler_tpu/parallel/packed.py"


@pytest.fixture(autouse=True)
def _fresh_traces():
    clear_trace_cache()
    yield
    clear_trace_cache()


def _f32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _spec(name, build, **kw):
    kw.setdefault("n_devices", 1)
    return ProgramSpec(
        name=name, source=FIXTURE_SOURCE, description="fixture",
        build=build, cases=(ProgramCase("c"),), **kw)


def _ids(diags):
    return [d.rule_id for d in diags]


# ---------------------------------------------------------------------------
# KTL120 dtype-flow
# ---------------------------------------------------------------------------


class TestDtypeFlow:
    def test_bad_f16_dot_accumulation_fires(self):
        def build(case):
            fn = jax.jit(lambda x: x.astype(jnp.float16)
                         @ x.astype(jnp.float16))
            return fn, (_f32((8, 8)),)

        spec = _spec("fx.bad_dot", build,
                     allowed_half_casts=frozenset({"float32->float16"}))
        diags = analyze_device_programs(REPO, only={"KTL120"},
                                        specs=(spec,))
        assert _ids(diags) == ["KTL120"]
        assert "accumulates in half precision" in diags[0].message

    def test_bad_half_reduction_fires(self):
        def build(case):
            def f(x, idx):
                acc = jnp.zeros((4,), jnp.float16)
                return acc.at[idx].add(x.astype(jnp.float16))

            return jax.jit(f), (_f32((8,)), _i32((8,)))

        spec = _spec("fx.bad_reduce", build,
                     allowed_half_casts=frozenset({"float32->float16"}))
        diags = analyze_device_programs(REPO, only={"KTL120"},
                                        specs=(spec,))
        assert _ids(diags) == ["KTL120"]
        assert "reduction over half-precision operands" in diags[0].message

    def test_bad_undeclared_cast_fires(self):
        def build(case):
            fn = jax.jit(
                lambda x: (x * 2).astype(jnp.float16).astype(jnp.float32))
            return fn, (_f32((4,)),)

        diags = analyze_device_programs(
            REPO, only={"KTL120"}, specs=(_spec("fx.bad_cast", build),))
        assert _ids(diags) == ["KTL120", "KTL120"]
        assert any("float32->float16" in d.message for d in diags)
        assert any("float16->float32" in d.message for d in diags)

    def test_good_acc_matmul_pattern_is_clean(self):
        from kepler_tpu.models.nn import acc_matmul

        def build(case):
            fn = jax.jit(lambda x: acc_matmul(x, x, jnp.bfloat16))
            return fn, (_f32((8, 8)),)

        spec = _spec("fx.good_dot", build,
                     allowed_half_casts=frozenset({"float32->bfloat16"}))
        assert analyze_device_programs(REPO, only={"KTL120"},
                                       specs=(spec,)) == []


# ---------------------------------------------------------------------------
# KTL121 donation-alias
# ---------------------------------------------------------------------------


class TestDonationAlias:
    def test_flipping_real_window_donation_off_fires(self):
        """The acceptance regression: the window update's declared
        donation is no longer realized → KTL121."""
        real = spec_by_name("window.update")

        def build(case):
            from kepler_tpu.parallel.packed import packed_width

            d = case.dims
            width = packed_width(d["w"], d["z"])

            def scatter_rows(resident, rows, idx):
                return resident.at[idx].set(rows, mode="drop")

            fn = jax.jit(scatter_rows)  # donate_argnums flipped OFF
            return fn, (_f32((d["n"], width)), _f32((d["db"], width)),
                        _i32((d["db"],)))

        flipped = dataclasses.replace(real, build=build,
                                      cases=real.cases[:1], n_devices=1)
        diags = analyze_device_programs(REPO, only={"KTL121"},
                                        specs=(flipped,))
        assert _ids(diags) == ["KTL121"]
        assert "not realized" in diags[0].message

    def test_undeclared_donation_fires(self):
        def build(case):
            fn = jax.jit(lambda r, v: r + v, donate_argnums=(0,))
            return fn, (_f32((8, 4)), _f32((8, 4)))

        diags = analyze_device_programs(
            REPO, only={"KTL121"},
            specs=(_spec("fx.secret_donate", build),))
        assert _ids(diags) == ["KTL121"]
        assert "undeclared donation" in diags[0].message

    def test_good_declared_and_realized_is_clean(self):
        def build(case):
            fn = jax.jit(lambda r, v: r.at[0].set(v),
                         donate_argnums=(0,))
            return fn, (_f32((8, 4)), _f32((4,)))

        spec = _spec("fx.good_donate", build, donates=(0,))
        assert analyze_device_programs(REPO, only={"KTL121"},
                                       specs=(spec,)) == []

    def test_attr_dicts_with_nested_braces_are_read(self):
        """Shardy writes ``[{"node"}, {}]`` INSIDE the attribute dict
        (the lowering of the window update under JAX 0.9). A parser that
        stops at the first inner brace reports the sharded, donated
        argument as unaliased — the stale-checker KTL121 that read as a
        dropped donation."""
        from kepler_tpu.analysis.device.trace import parse_main_arg_attrs

        text = (
            'module @jit_scatter_rows {\n'
            '  sdy.mesh @mesh = <["node"=8]>\n'
            '  func.func public @main('
            '%arg0: tensor<16x16xf32> {sdy.sharding = #sdy.sharding<@mesh, '
            '[{"node"}, {}]>, tf.aliasing_output = 0 : i32}, '
            '%arg1: tensor<8x16xf32> {sdy.sharding = #sdy.sharding<@mesh, '
            '[{}, {}]>}, '
            '%arg2: tensor<8xi32>, '
            '%arg3: tensor<4xf32> {mhlo.sharding = "{devices=[8,1]<=[8]}", '
            'jax.buffer_donor = true}) -> (tensor<16x16xf32> '
            '{jax.result_info = "result"}) {\n'
            '    return %arg0 : tensor<16x16xf32>\n  }\n}\n')
        assert parse_main_arg_attrs(text) == {
            0: {"aliased": True, "donor": False},
            1: {"aliased": False, "donor": False},
            2: {"aliased": False, "donor": False},
            3: {"aliased": False, "donor": True},
        }

    def test_sharded_window_update_donation_is_realized(self):
        """The real single-device-engine update (explicit mesh
        shardings, so Shardy attributes on every argument) lowers with
        its resident batch aliased to the output."""
        from kepler_tpu.analysis.device.trace import trace_case

        real = spec_by_name("window.update")
        report = trace_case(real, real.cases[0])
        assert report.flat_indices_of_arg(0) <= report.aliased_args
        assert report.donation_warnings == []


# ---------------------------------------------------------------------------
# KTL122 collective-discipline
# ---------------------------------------------------------------------------


class TestCollectiveDiscipline:
    def test_replicated_index_gather_regression_fires(self):
        """The acceptance regression: delete the sparse program's
        shard-local indexing (build the replicated-index variant on the
        multi-device mesh) — the shard_map disappears and KTL122 names
        the all-gather hazard."""
        real = spec_by_name("packed.sparse_local_mlp")
        case = real.cases[0]
        regressed_case = ProgramCase(case.name,
                                     dims={**case.dims, "local": 0})
        regressed = dataclasses.replace(real, cases=(regressed_case,))
        diags = analyze_device_programs(REPO, only={"KTL122"},
                                        specs=(regressed,))
        assert _ids(diags) == ["KTL122"]
        assert "lost its shard_map" in diags[0].message

    def test_rogue_collective_outside_allowlist_fires(self):
        def build(case):
            from jax.sharding import PartitionSpec as P

            from kepler_tpu.parallel.mesh import make_mesh

            mesh = make_mesh((8,), ("node",),
                             devices=jax.devices()[:8])
            body = jax.shard_map(lambda x: jax.lax.psum(x, "node"),
                                 mesh=mesh, in_specs=(P("node"),),
                                 out_specs=P(), check_vma=False)
            return jax.jit(body), (_f32((8, 4)),)

        spec = _spec("fx.rogue_psum", build, n_devices=8,
                     require_shard_map=True)
        diags = analyze_device_programs(REPO, only={"KTL122"},
                                        specs=(spec,))
        assert _ids(diags) == ["KTL122"]
        assert "psum" in diags[0].message

    def test_good_allowlisted_collective_is_clean(self):
        def build(case):
            from jax.sharding import PartitionSpec as P

            from kepler_tpu.parallel.mesh import make_mesh

            mesh = make_mesh((8,), ("node",),
                             devices=jax.devices()[:8])
            body = jax.shard_map(lambda x: jax.lax.psum(x, "node"),
                                 mesh=mesh, in_specs=(P("node"),),
                                 out_specs=P(), check_vma=False)
            return jax.jit(body), (_f32((8, 4)),)

        spec = _spec("fx.ok_psum", build, n_devices=8,
                     require_shard_map=True,
                     allowed_collectives=frozenset({"psum"}))
        assert analyze_device_programs(REPO, only={"KTL122"},
                                       specs=(spec,)) == []


# ---------------------------------------------------------------------------
# KTL123 program-ratchet
# ---------------------------------------------------------------------------


def _matmul_spec(name="fx.ratchet", transpose=False):
    def build(case):
        if transpose:
            fn = jax.jit(lambda x: (x @ x).T)
        else:
            fn = jax.jit(lambda x: x @ x)
        return fn, (_f32((8, 8)),)

    return _spec(name, build)


class TestProgramRatchet:
    def test_snapshot_roundtrip_then_drift(self, tmp_path):
        root = str(tmp_path)
        spec = _matmul_spec()
        count, errors = write_snapshots(root, specs=(spec,))
        assert (count, errors) == (1, [])
        assert analyze_device_programs(root, specs=(spec,)) == []

        # same program key, different structure: the extra transpose
        # the ratchet exists to catch
        clear_trace_cache()
        drifted = _matmul_spec(transpose=True)
        diags = analyze_device_programs(root, only={"KTL123"},
                                        specs=(drifted,))
        assert diags and all(d.rule_id == "KTL123" for d in diags)
        assert any("fingerprint drift" in d.message for d in diags)

    def test_missing_snapshot_file_fires(self, tmp_path):
        diags = analyze_device_programs(str(tmp_path), only={"KTL123"},
                                        specs=(_matmul_spec(),))
        assert any("missing " + SNAPSHOT_NAME in d.message for d in diags)

    def test_unsnapshotted_case_and_stale_entry_fire(self, tmp_path):
        root = str(tmp_path)
        two_cases = dataclasses.replace(
            _matmul_spec(), cases=(ProgramCase("a"), ProgramCase("b")))
        write_snapshots(root, specs=(two_cases,))
        clear_trace_cache()
        only_a = dataclasses.replace(two_cases,
                                     cases=(ProgramCase("a"),
                                            ProgramCase("new")))
        diags = analyze_device_programs(root, only={"KTL123"},
                                        specs=(only_a,))
        messages = " | ".join(d.message for d in diags)
        assert "no golden snapshot" in messages  # case "new"
        assert "stale snapshot entry" in messages  # case "b"

    def test_deleting_a_whole_spec_leaves_stale_entries_flagged(
            self, tmp_path):
        """Dead fingerprints of an UNREGISTERED program must not linger
        silently in the golden file (review finding)."""
        root = str(tmp_path)
        gone = _matmul_spec(name="fx.deleted")
        kept = _matmul_spec(name="fx.kept")
        write_snapshots(root, specs=(gone, kept))
        clear_trace_cache()
        diags = analyze_device_programs(root, only={"KTL123"},
                                        specs=(kept,))
        assert ["KTL123"] == _ids(diags)
        assert "stale snapshot entry 'fx.deleted/c'" in diags[0].message


# ---------------------------------------------------------------------------
# shipped tree: registry sanity, committed snapshots, budget
# ---------------------------------------------------------------------------


class TestShippedTree:
    def test_registry_covers_the_device_program_zoo(self):
        names = {s.name for s in DEVICE_PROGRAMS}
        assert len(names) == len(DEVICE_PROGRAMS) >= 15
        for prefix in ("packed.", "window.", "fleet.", "ops.", "ring.",
                       "ulysses.", "pipeline.", "expert.", "sequence.",
                       "trainer."):
            assert any(n.startswith(prefix) for n in names), prefix
        for spec in DEVICE_PROGRAMS:
            assert spec.description and spec.cases
            assert os.path.exists(os.path.join(REPO, spec.source)), \
                spec.source

    def test_committed_snapshots_match_registry_keys(self):
        snapshots = load_snapshots(REPO)
        assert snapshots is not None, "commit .kepljax.json"
        want = {f"{s.name}/{c.name}" for s in DEVICE_PROGRAMS
                for c in s.cases}
        assert set(snapshots) == want

    def test_device_tier_clean_and_within_budget(self):
        """THE acceptance gate: every registered program traces on a
        CPU-only host, every family passes against the committed
        contracts and snapshots, inside the wall-clock budget."""
        t0 = time.monotonic()
        diags = analyze_device_programs(REPO)
        elapsed = time.monotonic() - t0
        assert diags == [], "\n".join(d.render() for d in diags)
        assert elapsed < 60.0, (
            f"device tier took {elapsed:.1f}s (budget 60s); tracing "
            f"cost regressed — did an entry start compiling/executing?")


# ---------------------------------------------------------------------------
# CLI surface: --only, --device-tier plumbing, SARIF catalog
# ---------------------------------------------------------------------------


class TestCli:
    def test_only_unknown_rule_id_is_usage_error(self, tmp_path, capsys):
        (tmp_path / "pyproject.toml").write_text("")
        assert main(["--only=KTL999", str(tmp_path)]) == 2
        assert "unknown rule id" in capsys.readouterr().err

    def test_only_filters_to_named_rule(self, tmp_path, capsys):
        (tmp_path / "pyproject.toml").write_text("")
        mod = tmp_path / "kepler_tpu" / "parallel" / "packed.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(
            "# keplint: monotonic-only\n"
            "import time\n"
            "def f(out, w, z):\n"
            "    t = time.time()\n"  # KTL101
            "    out[:, w + 2 * z + 1] = t\n"  # KTL114
            "    return out\n")
        assert main([str(mod)]) == 1
        both = capsys.readouterr().out
        assert "KTL101" in both and "KTL114" in both
        assert main([f"--only=KTL114", str(mod)]) == 1
        only = capsys.readouterr().out
        assert "KTL114" in only and "KTL101" not in only

    def test_only_device_rule_implies_device_tier(self, tmp_path,
                                                  monkeypatch, capsys):
        """--only=KTL120 without --device-tier must RUN the device tier
        (review finding: it used to print 'clean' without checking)."""
        calls = []

        def fake_analyze(root, only=None, **kw):
            calls.append(set(only or ()))
            return []

        monkeypatch.setattr(
            "kepler_tpu.analysis.device.analyze_device_programs",
            fake_analyze)
        (tmp_path / "pyproject.toml").write_text("")
        mod = tmp_path / "kepler_tpu" / "m.py"
        mod.parent.mkdir()
        mod.write_text("x = 1\n")
        assert main(["--only=KTL120", str(mod)]) == 0
        assert calls == [{"KTL120"}]
        # ...but --device-tier with only host rules named skips traces
        assert main(["--device-tier", "--only=KTL101", str(mod)]) == 0
        assert calls == [{"KTL120"}]

    def test_sarif_catalog_carries_device_rules(self):
        sarif = render_sarif(LintResult())
        ids = {r["id"] for r in sarif["runs"][0]["tool"]["driver"]["rules"]}
        assert {"KTL114", "KTL120", "KTL121", "KTL122", "KTL123"} <= ids

    def test_device_rules_registered_with_docs(self):
        by_id = {r.id: r for r in all_rules()}
        for rid in ("KTL120", "KTL121", "KTL122", "KTL123"):
            assert rid in by_id
            assert by_id[rid].summary and by_id[rid].rationale
