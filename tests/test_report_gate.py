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


# --- kernel-bench gate (BENCH_pixel_cascade.json) ----------------------------

from report_gate import bench_gate  # noqa: E402


def _bench_doc():
    return {
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "shapes": {
            "B4_96x128": {
                "rows": {
                    "staged_interpret": {"us_per_call": 2000.0,
                                         "Mpx_s": 24.0,
                                         "substrate": "pallas_interpret",
                                         "pallas_launches": 3},
                    "fused_xla_ref": {"us_per_call": 500.0, "Mpx_s": 98.0,
                                      "substrate": "xla_ref",
                                      "pallas_launches": 0},
                },
            },
        },
    }


def _bench_pair(tmp_path, base, fresh):
    bp = tmp_path / "base.json"
    fp = tmp_path / "fresh.json"
    bp.write_text(json.dumps(base))
    fp.write_text(json.dumps(fresh))
    return str(fp), str(bp)


def test_bench_identical_passes(tmp_path):
    assert bench_gate(*_bench_pair(tmp_path, _bench_doc(), _bench_doc())) == []


def test_bench_throughput_regression_breaches(tmp_path):
    """The acceptance band: >30% slower must breach."""
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]["Mpx_s"] = 60.0
    breaches = bench_gate(*_bench_pair(tmp_path, _bench_doc(), fresh))
    assert len(breaches) == 1 and "throughput" in breaches[0]


def test_bench_gate_is_one_sided(tmp_path):
    """Getting faster (even 10x) never breaches — regressions only."""
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]["Mpx_s"] = 980.0
    assert bench_gate(*_bench_pair(tmp_path, _bench_doc(), fresh)) == []


def test_bench_small_slowdown_within_band_passes(tmp_path):
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]["Mpx_s"] = 70.0
    assert bench_gate(*_bench_pair(tmp_path, _bench_doc(), fresh)) == []


def test_bench_substrate_flip_breaches(tmp_path):
    """Interpret baseline vs newly-compiled fresh run must be re-blessed,
    not silently absorbed by the band."""
    fresh = copy.deepcopy(_bench_doc())
    row = fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]
    row["substrate"] = "pallas_compiled"
    row["Mpx_s"] = 500.0
    breaches = bench_gate(*_bench_pair(tmp_path, _bench_doc(), fresh))
    assert len(breaches) == 1 and "substrate" in breaches[0]


def test_bench_missing_shape_and_row_breach(tmp_path):
    fresh = copy.deepcopy(_bench_doc())
    del fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]
    base = copy.deepcopy(_bench_doc())
    base["shapes"]["B8_64x64"] = {"rows": {}}
    breaches = bench_gate(*_bench_pair(tmp_path, base, fresh))
    assert any("missing from fresh" in b for b in breaches)
    assert any("B8_64x64" in b for b in breaches)


def test_bench_gate_on_committed_baseline():
    """The committed BENCH_pixel_cascade.json gates cleanly against
    itself and satisfies the acceptance bar: every shape's fused XLA
    reference throughput >= 2x its staged interpret baseline."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "BENCH_pixel_cascade.json")
    assert bench_gate(path, path) == []
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["shapes"], "committed bench must not be empty"
    for key, shape in doc["shapes"].items():
        rows = shape["rows"]
        assert rows["fused_interpret"]["pallas_launches"] == 1
        assert rows["staged_interpret"]["pallas_launches"] == 3
        ratio = (rows["fused_xla_ref"]["Mpx_s"]
                 / rows["staged_interpret"]["Mpx_s"])
        assert ratio >= 2.0, (key, ratio)
        assert "roofline_fraction" in shape["roofline"]["fused"]


# --- bandwidth-endgame columns + fresh-row consistency ------------------------

from report_gate import (  # noqa: E402
    Check,
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


def test_bench_gate_collects_checks(tmp_path):
    checks = []
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]["Mpx_s"] = 60.0
    bench_gate(*_bench_pair(tmp_path, _bench_doc(), fresh), checks=checks)
    bad = [c for c in checks if not c.ok]
    assert len(bad) == 1 and bad[0].metric == "Mpx_s"
    assert isinstance(bad[0], Check) and bad[0].tol.endswith("one-sided")


# --- --bench-substrate filter (PR-time CPU runner) ---------------------------


def test_bench_substrate_filter_skips_other_substrates(tmp_path):
    """A regression in an xla_ref row must NOT fail a gate restricted to
    pallas_interpret rows — other substrates gate where they run."""
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["fused_xla_ref"]["Mpx_s"] = 10.0
    pair = _bench_pair(tmp_path, _bench_doc(), fresh)
    assert bench_gate(*pair, substrates=["pallas_interpret"]) == []
    assert bench_gate(*pair) != []           # unfiltered still catches it


def test_bench_substrate_filter_still_gates_matching_rows(tmp_path):
    fresh = copy.deepcopy(_bench_doc())
    fresh["shapes"]["B4_96x128"]["rows"]["staged_interpret"]["Mpx_s"] = 1.0
    pair = _bench_pair(tmp_path, _bench_doc(), fresh)
    breaches = bench_gate(*pair, substrates=["pallas_interpret"])
    assert len(breaches) == 1 and "staged_interpret" in breaches[0]
