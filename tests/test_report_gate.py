"""The CI report regression gate: tolerance-band math, structural
breaches, and the acceptance-criteria negative test (a synthetic -0.1 F2
perturbation must fail the gate)."""
import copy
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from report_gate import compare_report, gate  # noqa: E402


def _doc():
    return {
        "scenario": "toy",
        "frontend": "confidence",
        "schemes": {
            "surveiledge": {
                "accuracy_F2": 0.90,
                "avg_latency_s": 2.0,
                "p99_latency_s": 8.0,
                "bandwidth_MB": 10.0,
                "lan_MB": 4.0,
                "downloaded_MB": 1.0,
                "queries": {
                    "0": {"f2": 0.95, "avg_latency_s": 1.5},
                    "1": {"f2": 0.85, "avg_latency_s": 3.0},
                },
            },
            "cloud_only": {
                "accuracy_F2": 0.99,
                "avg_latency_s": 12.0,
                "p99_latency_s": 40.0,
                "bandwidth_MB": 90.0,
                "lan_MB": 0.0,
                "downloaded_MB": 0.0,
            },
        },
    }


def test_identical_reports_pass():
    assert compare_report(_doc(), _doc()) == []


def test_f2_regression_breaches():
    """The acceptance criterion's negative test: -0.1 absolute F2 is
    double the +/-0.05 band and must breach."""
    fresh = copy.deepcopy(_doc())
    fresh["schemes"]["surveiledge"]["accuracy_F2"] -= 0.1
    breaches = compare_report(_doc(), fresh)
    assert len(breaches) == 1
    assert "accuracy_F2" in breaches[0] and "surveiledge" in breaches[0]


def test_f2_within_band_passes():
    fresh = copy.deepcopy(_doc())
    fresh["schemes"]["surveiledge"]["accuracy_F2"] -= 0.04
    assert compare_report(_doc(), fresh) == []


def test_latency_and_bandwidth_relative_bands():
    fresh = copy.deepcopy(_doc())
    fresh["schemes"]["cloud_only"]["avg_latency_s"] *= 1.20   # inside 25%
    fresh["schemes"]["cloud_only"]["bandwidth_MB"] *= 0.80
    assert compare_report(_doc(), fresh) == []
    fresh["schemes"]["cloud_only"]["avg_latency_s"] = 12.0 * 1.30
    breaches = compare_report(_doc(), fresh)
    assert len(breaches) == 1 and "avg_latency_s" in breaches[0]


def test_near_zero_baseline_uses_absolute_floor():
    """lan_MB baseline 0.0: a 0.04 MB wobble sits under the floor, a
    0.5 MB jump does not."""
    fresh = copy.deepcopy(_doc())
    fresh["schemes"]["cloud_only"]["lan_MB"] = 0.04
    assert compare_report(_doc(), fresh) == []
    fresh["schemes"]["cloud_only"]["lan_MB"] = 0.5
    assert any("lan_MB" in b for b in compare_report(_doc(), fresh))


def test_per_query_rows_are_gated():
    fresh = copy.deepcopy(_doc())
    fresh["schemes"]["surveiledge"]["queries"]["1"]["f2"] -= 0.1
    breaches = compare_report(_doc(), fresh)
    assert len(breaches) == 1 and "/q1" in breaches[0]
    # a dropped per-query row is structural, not silent
    del fresh["schemes"]["surveiledge"]["queries"]["1"]
    assert any("missing" in b for b in compare_report(_doc(), fresh))


def test_missing_scheme_breaches():
    fresh = copy.deepcopy(_doc())
    del fresh["schemes"]["cloud_only"]
    assert any("missing" in b for b in compare_report(_doc(), fresh))


def test_gate_dir_pairing(tmp_path):
    base_dir, fresh_dir = tmp_path / "base", tmp_path / "fresh"
    base_dir.mkdir(), fresh_dir.mkdir()
    (base_dir / "toy-confidence.json").write_text(json.dumps(_doc()))
    (fresh_dir / "toy-confidence.json").write_text(json.dumps(_doc()))
    assert gate(str(fresh_dir), str(base_dir)) == []
    # a fresh report with no committed baseline is a breach...
    (fresh_dir / "new-confidence.json").write_text(json.dumps(_doc()))
    assert any("no committed baseline" in b
               for b in gate(str(fresh_dir), str(base_dir)))
    # ... and so is a stale baseline with no fresh run
    os.remove(fresh_dir / "new-confidence.json")
    (base_dir / "old-confidence.json").write_text(json.dumps(_doc()))
    assert any("no fresh run" in b for b in gate(str(fresh_dir),
                                                 str(base_dir)))


def test_gate_end_to_end_perturbation(tmp_path):
    """Dir-level negative test: one perturbed metric in one file fails the
    whole gate with a pointed message."""
    base_dir, fresh_dir = tmp_path / "base", tmp_path / "fresh"
    base_dir.mkdir(), fresh_dir.mkdir()
    (base_dir / "toy-confidence.json").write_text(json.dumps(_doc()))
    bad = _doc()
    bad["schemes"]["surveiledge"]["accuracy_F2"] -= 0.1
    (fresh_dir / "toy-confidence.json").write_text(json.dumps(bad))
    breaches = gate(str(fresh_dir), str(base_dir))
    assert len(breaches) == 1
    assert "accuracy_F2" in breaches[0]


# --- bandwidth-endgame columns + fresh-row consistency ------------------------

from report_gate import (  # noqa: E402
    TOLERANCES,
    row_consistency,
    write_summary_md,
)


def test_downlink_columns_are_gated():
    """The new bandwidth columns sit under tolerance bands like the
    legacy ones: fp-reference drift and flip-rate drift both breach."""
    assert "downlink_fp_MB" in TOLERANCES
    assert "uplink_bytes_per_TP" in TOLERANCES
    assert "reconciliation_flip_rate" in TOLERANCES
    assert "provisional_latency_s" in TOLERANCES
    base = _doc()
    row = base["schemes"]["surveiledge"]
    row.update(downlink_fp_MB=4.0, reconciliation_flip_rate=0.02,
               provisional_latency_s=1.0, uplink_bytes_per_TP=50000.0)
    fresh = copy.deepcopy(base)
    fresh["schemes"]["surveiledge"]["downlink_fp_MB"] = 8.0   # +100%
    breaches = compare_report(base, fresh)
    assert len(breaches) == 1 and "downlink_fp_MB" in breaches[0]
    fresh = copy.deepcopy(base)
    fresh["schemes"]["surveiledge"]["reconciliation_flip_rate"] = 0.3
    breaches = compare_report(base, fresh)
    assert len(breaches) == 1 and "reconciliation_flip_rate" in breaches[0]
    # within-band wobbles pass
    fresh = copy.deepcopy(base)
    fresh["schemes"]["surveiledge"]["reconciliation_flip_rate"] = 0.05
    fresh["schemes"]["surveiledge"]["downlink_fp_MB"] = 4.5
    assert compare_report(base, fresh) == []


def test_row_consistency_updates_without_downlink():
    bad = {"model_updates": 3, "downloaded_MB": 0.0, "downloaded_bytes": 0}
    msgs = row_consistency("toy/surveiledge", bad)
    assert len(msgs) == 1 and "zero downlink" in msgs[0]
    ok = {"model_updates": 3, "downloaded_bytes": 24}
    assert row_consistency("toy/surveiledge", ok) == []


def test_row_consistency_quantized_exceeding_fp_fails():
    """Satellite bugfix: model_updates > 0 with quantized bytes LARGER
    than the row's fp reference is a wire-accounting bug, not drift."""
    bad = {"model_updates": 2, "downloaded_bytes": 5000,
           "downlink_fp_bytes": 4000}
    msgs = row_consistency("toy/surveiledge", bad)
    assert len(msgs) == 1 and "fp-equivalent" in msgs[0]
    ok = {"model_updates": 2, "downloaded_bytes": 1300,
          "downlink_fp_bytes": 4000}
    assert row_consistency("toy/surveiledge", ok) == []


def test_gate_fails_on_quantized_exceeding_fp_end_to_end():
    """compare_report applies the consistency check to FRESH rows even
    when the baseline pair is otherwise within tolerance."""
    base = _doc()
    fresh = copy.deepcopy(base)
    fresh["schemes"]["surveiledge"].update(
        model_updates=2, downloaded_bytes=5000, downlink_fp_bytes=4000)
    breaches = compare_report(base, fresh)
    assert any("fp-equivalent" in b for b in breaches)


# --- --summary-md verdict table ----------------------------------------------


def test_summary_md_lists_failures_before_passes(tmp_path):
    checks = []
    base = _doc()
    fresh = copy.deepcopy(base)
    fresh["schemes"]["surveiledge"]["accuracy_F2"] -= 0.2
    compare_report(base, fresh, checks)
    assert any(not c.ok for c in checks)
    assert any(c.ok for c in checks)
    out = tmp_path / "summary.md"
    write_summary_md(str(out), checks)
    text = out.read_text()
    assert "accuracy_F2" in text
    assert text.index("❌") < text.index("<details>")
    assert "✅" in text and "| artifact |" in text
    # appends (GITHUB_STEP_SUMMARY semantics), never truncates
    write_summary_md(str(out), checks)
    assert len(out.read_text()) > len(text)


def test_summary_md_records_passing_metrics_too(tmp_path):
    checks = []
    compare_report(_doc(), _doc(), checks)
    assert checks and all(c.ok for c in checks)
    out = tmp_path / "summary.md"
    write_summary_md(str(out), checks)
    text = out.read_text()
    assert "0 breach(es)" in text and "❌" not in text
