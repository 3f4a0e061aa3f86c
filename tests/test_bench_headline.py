"""The bench evidence contract (ROADMAP item 5, ISSUE 6 satellite).

The driver captures only a bounded TAIL of bench stdout (~2000 chars);
round 5 lost the whole measurement because the detail row outgrew it
(BENCH_r05 ``parsed: null``). The contract pinned here:

* ``bench.py``'s LAST stdout line is a compact single-line JSON headline
  (metric, platform, gate booleans) that stays ≤ 1000
  chars no matter how fat the detail row gets, so it survives any
  ~2000-char tail truncation;
* the full detail row goes to a file (``BENCH_DETAIL.json``), referenced
  from the headline;
* an errored bench leg FAILS its gate in the headline (ADVICE r5: a leg
  that raised is a failure, never a silent skip).

These tests exercise the builder/gate functions directly — no device
work, no subprocesses.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench  # noqa: E402


def fat_result(**overrides) -> dict:
    """A detail row far beyond any tail window: every real key bench
    emits plus pathological bulk."""
    row = {
        "metric": "attribution_program_p99_ms_10k_pods",
        "value": 0.123456,
        "unit": "ms",
        "vs_baseline": 8.1,
        "platform": "tpu",
        "backend": "einsum",
        "accuracy_ok": True,
        "e2e_pipeline_ok": True,
        "soak_ok": True,
        "aggwin_within_budget": True,
        "aggwin_pipeline_ok": True,
        "aggwin_sharded_ok": True,
        "aggwin_host_p50_ms": 21.4,
        "aggwin_host_p99_ms": 55.2,
        "aggwin_pipeline_p50_ms": 101.2,
        "aggwin_pipeline_ratio": 0.41,
        "aggwin_sharded_devices": 8,
        "aggwin_sharded_device_p50_ms": 31.3,
        "aggwin_unsharded_device_p50_ms": 62.5,
        "aggwin_sharded_device_ratio": 0.5,
        "aggwin_sharded_ratio_budget": 0.6,
        "aggwin_sharded_bit_consistent": True,
        "aggwin_multihost_ok": True,
        "aggwin_multihost_hosts": 2,
        "aggwin_multihost_bit_consistent": True,
        "aggwin_multihost_capacity_ratio": 2.0,
        "aggwin_multihost_capacity_budget": 1.8,
        "aggwin_fused_ok": True,
        "aggwin_fused_k": 4,
        "aggwin_fused_device_p50_ms": 0.0,
        "aggwin_fused_sync_per_window_ms": 4.2,
        "aggwin_unfused_device_p50_ms": 17.3,
        "aggwin_fused_ratio": 0.0,
        "aggwin_fused_ratio_budget": 0.5,
        "aggwin_fused_bit_consistent": True,
        "ingest_ok": True,
        "ingest_zero_copy_ok": True,
        "ingest_decode_ratio": 4.9,
        "ingest_decode_ratio_budget": 4.0,
        "ingest_reports_per_s": 1100.0,
        "ingest_bytes_per_report_v1": 2234.7,
        "ingest_bytes_per_report_v2": 143.0,
        "e2e_pipelined_p99_ms": 7.1,
        "sync_floor_p50_ms": 66.0,
        # pathological bulk: thousands of chars of per-leg detail
        **{f"leg_{i}_detail_ms": i * 0.001 for i in range(400)},
        "notes": "x" * 3000,
    }
    row.update(overrides)
    return row


class TestHeadline:
    def test_single_line_bounded_and_parseable(self):
        line = bench.build_headline(fat_result(ok=True), "BENCH_DETAIL.json")
        assert "\n" not in line
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["metric"] == "attribution_program_p99_ms_10k_pods"
        assert head["platform"] == "tpu"
        assert head["ok"] is True
        assert head["detail_file"] == "BENCH_DETAIL.json"
        for gate in ("accuracy_ok", "e2e_pipeline_ok", "soak_ok",
                     "aggwin_within_budget", "aggwin_pipeline_ok",
                     "aggwin_sharded_ok"):
            assert head[gate] is True

    def test_survives_tail_window_truncation(self):
        """The exact failure mode of rounds 4-5: the driver keeps only
        the last ~2000 chars of stdout. The headline is printed LAST, so
        the tail's last line must still parse as the headline row."""
        result = fat_result(ok=True)
        detail_row = json.dumps(result)
        assert len(detail_row) > 2000  # the detail row alone would be lost
        headline = bench.build_headline(result, "BENCH_DETAIL.json")
        stdout = detail_row + "\n" + headline + "\n"
        tail = stdout[-2000:]
        last_line = tail.strip().splitlines()[-1]
        head = json.loads(last_line)
        assert head["metric"] == "attribution_program_p99_ms_10k_pods"
        assert "detail_file" in head

    def test_total_failure_row_is_headline_shaped(self):
        line = bench.build_headline(
            {"metric": "attribution_program_p99_ms_10k_pods",
             "value": None, "unit": "ms", "ok": False,
             "error": "both bench attempts failed (last rc=1)",
             "platform": "none"}, "")
        head = json.loads(line)
        assert head["ok"] is False
        assert head["value"] is None
        assert "error" in head
        assert len(line) <= bench.HEADLINE_MAX_CHARS

    def test_pathological_field_clamps_to_core(self):
        """A pathological env-provided detail path is the one field that
        can actually outgrow the cap: the clamp must fire (not just
        exist) and the clamped line must still honor the size contract.
        The path is dropped from the headline — the file still exists on
        disk — rather than silently breaking tail survival."""
        long_path = "/tmp/" + "d" * 1500 + "/BENCH_DETAIL.json"
        line = bench.build_headline(fat_result(ok=True), long_path)
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["metric"] == "attribution_program_p99_ms_10k_pods"
        assert head["detail_file"] == ""  # dropped, not truncated garbage

    def test_long_error_field_is_truncated_inline(self):
        """error strings are bounded to 200 chars up front, so a fat
        error never needs the clamp and the detail path survives."""
        result = fat_result(ok=False, error="e" * 5000)
        line = bench.build_headline(result, "BENCH_DETAIL.json")
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert len(head["error"]) == 200
        assert head["detail_file"] == "BENCH_DETAIL.json"


class TestErroredLegGates:
    @pytest.mark.parametrize("err_key,gates", sorted(
        bench.LEG_ERROR_GATES.items()))
    def test_errored_leg_fails_its_gate(self, err_key, gates):
        result = fat_result(**{err_key: "TimeoutExpired(900)"})
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        for gate in gates:
            assert result[gate] is False
        # exactly ONE message, naming the errored leg — never a second,
        # fabricated "budget violated" diagnostic for a measurement that
        # never ran
        assert len(messages) == 1
        assert err_key in messages[0]
        result["ok"] = not failed
        head = json.loads(bench.build_headline(result, "f.json"))
        assert head["ok"] is False
        assert err_key in head["leg_errors"]
        for gate in gates:
            assert head[gate] is False

    def test_clean_run_passes(self):
        result = fat_result()
        failed, messages = bench.evaluate_gates(result, on_tpu=True)
        assert not failed
        assert messages == []
        assert result["node_scrape_ok"] is True

    def test_soak_slo_violation_still_gates(self):
        result = fat_result(soak_ok=False)
        failed, _ = bench.evaluate_gates(result, on_tpu=False)
        assert failed

    def test_sharded_window_violation_gates_and_survives_headline(self):
        """The ISSUE-7 sharded-window gate: a measured violation fails
        the run with a scaling/bit-consistency message, lands False in
        the headline, and the headline still honors the size contract."""
        result = fat_result(aggwin_sharded_ok=False,
                            aggwin_sharded_device_ratio=0.91,
                            aggwin_sharded_bit_consistent=True)
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert any("sharded" in m for m in messages)
        result["ok"] = not failed
        line = bench.build_headline(result, "BENCH_DETAIL.json")
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["aggwin_sharded_ok"] is False
        assert head["ok"] is False

    def test_ingest_gate_violation_gates_and_survives_headline(self):
        """The ISSUE-14 wire-v2 ingest gate: a measured decode-ratio
        violation fails the run, lands False in the headline, and the
        headline still honors the size contract."""
        result = fat_result(ingest_ok=False, ingest_decode_ratio=2.1)
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert any("ingest" in m for m in messages)
        result["ok"] = not failed
        line = bench.build_headline(result, "BENCH_DETAIL.json")
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["ingest_ok"] is False
        assert head["ingest_zero_copy_ok"] is True
        assert head["ok"] is False

    def test_absent_ingest_leg_does_not_gate(self):
        """A detail row without the ingest leg (older capture replayed
        through the gate logic) must not fire the new gate on absence."""
        result = fat_result()
        for key in list(result):
            if key.startswith("ingest_"):
                del result[key]
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert not failed
        assert messages == []
        head = json.loads(bench.build_headline(result, "f.json"))
        assert "ingest_ok" not in head

    def test_absent_sharded_leg_does_not_gate(self):
        """A single-device host (standalone scenarios run) emits no
        sharded fields at all — the gate must not fire on absence."""
        result = fat_result()
        for key in list(result):
            if key.startswith("aggwin_sharded") or \
                    key.startswith("aggwin_unsharded"):
                del result[key]
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert not failed
        assert messages == []
        head = json.loads(bench.build_headline(result, "f.json"))
        assert "aggwin_sharded_ok" not in head

    def test_multihost_violation_gates_and_survives_headline(self):
        """The ISSUE-15 multi-host gate: bit-inconsistency or a
        capacity-scaling miss fails the run, lands False in the
        headline, and the headline still honors the size contract."""
        result = fat_result(aggwin_multihost_ok=False,
                            aggwin_multihost_bit_consistent=False,
                            aggwin_multihost_capacity_ratio=1.2)
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert any("multi-host" in m for m in messages)
        result["ok"] = not failed
        line = bench.build_headline(result, "BENCH_DETAIL.json")
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["aggwin_multihost_ok"] is False
        assert head["ok"] is False

    def test_absent_multihost_leg_does_not_gate(self):
        """Below 4 devices the scenario emits no multihost fields —
        absence never gates."""
        result = fat_result()
        for key in list(result):
            if key.startswith("aggwin_multihost"):
                del result[key]
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert not failed
        assert messages == []
        head = json.loads(bench.build_headline(result, "f.json"))
        assert "aggwin_multihost_ok" not in head

    def test_aggwin_error_forces_multihost_gate_false(self):
        """An errored aggwin leg forces every aggwin gate False —
        including the multi-host one — without fabricating a measured
        violation message for it."""
        result = fat_result(aggwin_error="subprocess died")
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert result["aggwin_multihost_ok"] is False
        assert result["aggwin_sharded_ok"] is False
        assert sum("aggwin" in m for m in messages) == 1  # the leg error

    def test_fused_violation_gates_and_survives_headline(self):
        """The ISSUE-20 fused window gate: a measured amortization miss
        (fused device leg not ≤ budget × unfused) or bit-inconsistency
        fails the run, lands False in the headline, and the headline
        still honors the size contract."""
        result = fat_result(aggwin_fused_ok=False,
                            aggwin_fused_ratio=0.83,
                            aggwin_fused_bit_consistent=True)
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert any("fused" in m for m in messages)
        result["ok"] = not failed
        line = bench.build_headline(result, "BENCH_DETAIL.json")
        assert len(line) <= bench.HEADLINE_MAX_CHARS
        head = json.loads(line)
        assert head["aggwin_fused_ok"] is False
        assert head["ok"] is False

    def test_aggwin_error_forces_fused_gate_false(self):
        """An errored aggwin leg forces the fused gate False too (the
        fused measurement runs inside that leg) — with no fabricated
        measured-violation message."""
        result = fat_result(aggwin_error="TimeoutExpired(900)")
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert failed
        assert result["aggwin_fused_ok"] is False
        assert sum("aggwin" in m for m in messages) == 1
        result["ok"] = not failed
        head = json.loads(bench.build_headline(result, "f.json"))
        assert head["aggwin_fused_ok"] is False
        assert "aggwin_error" in head["leg_errors"]

    def test_absent_fused_leg_does_not_gate(self):
        """A detail row captured before the fused leg existed (or a run
        with fusedWindowK pinned to 1) has no fused fields — the gate
        must not fire on absence."""
        result = fat_result()
        for key in list(result):
            if key.startswith("aggwin_fused") or \
                    key.startswith("aggwin_unfused"):
                del result[key]
        failed, messages = bench.evaluate_gates(result, on_tpu=False)
        assert not failed
        assert messages == []
        head = json.loads(bench.build_headline(result, "f.json"))
        assert "aggwin_fused_ok" not in head
