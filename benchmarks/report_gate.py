"""Regression gate over the scenario JSON reports: diff fresh runs
against the committed ``reports/`` baselines with tolerance bands.

CI's ``e2e-smoke`` used to only *upload* the per-scenario reports — a
silent accuracy or bandwidth regression sailed through as a green build
with a quietly different artifact.  This gate makes the reports load-
bearing: ``make report-gate`` regenerates every scenario into a scratch
dir (one process, ``run_scenarios.py --scenario all``) and fails the job
on any breach of:

  * ``accuracy_F2`` — within +/-0.05 ABSOLUTE of the baseline
  * bandwidth (``bandwidth_MB`` / ``lan_MB`` / ``downloaded_MB``) and
    latency (``avg_latency_s`` / ``p99_latency_s``) — within 25%
    relative (plus a small absolute floor so near-zero baselines don't
    flag on noise)
  * per-query rows (multi-query scenarios): each query's ``f2`` and
    ``avg_latency_s``, same bands
  * control-plane columns (``rush_hour``): ``shed_rate`` (±0.10 abs),
    ``alerts_total`` (coarse 50% band), per-tier p99 latencies — and
    ``slo_breach_top_tier`` at ZERO tolerance (the preset exists to
    prove the platinum tier never breaches)
  * structure — a fresh report missing a baseline scenario/scheme/query
    (or vice versa) is a breach: new scenarios ship WITH their committed
    baselines, retired ones delete them

The simulation is seed-deterministic, so on an unchanged tree fresh ==
baseline exactly; the bands exist to absorb *intentional* small behavior
drift (a re-tuned threshold constant) without re-blessing every digit.
A genuine change beyond the bands is re-blessed by regenerating the
baselines in place (``make bench-smoke`` writes into ``reports/``) and
committing the diff — which the PR reviewer then sees as numbers, not as
a silently mutated artifact.

Fresh scheme rows additionally pass physical-consistency checks with no
baseline involved (``row_consistency``): fused recalibration launches
with zero downlink bytes, or quantized downlink bytes exceeding the
row's own fp-equivalent reference, fail the gate outright.

``--summary-md PATH`` appends a per-metric verdict table (value,
baseline, tolerance, pass/fail) to PATH; CI points it at
``$GITHUB_STEP_SUMMARY`` so deltas land on the job page.

  PYTHONPATH=src python benchmarks/report_gate.py --fresh .cache/reports-fresh
  PYTHONPATH=src python benchmarks/report_gate.py --fresh DIR --baseline reports
  PYTHONPATH=src python benchmarks/report_gate.py --fresh DIR \
      --summary-md "$GITHUB_STEP_SUMMARY"
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# metric -> (kind, band, absolute floor for relative bands)
TOLERANCES: Dict[str, Tuple[str, float, float]] = {
    "accuracy_F2": ("abs", 0.05, 0.0),
    "avg_latency_s": ("rel", 0.25, 0.05),
    "p99_latency_s": ("rel", 0.25, 0.10),
    "bandwidth_MB": ("rel", 0.25, 0.05),
    "lan_MB": ("rel", 0.25, 0.05),
    "downloaded_MB": ("rel", 0.25, 0.05),
    # bandwidth-endgame columns: the fp-equivalent downlink reference,
    # upload spent per useful answer, and the speculative-escalation pair
    # (flip rate is an absolute band — its baseline is near zero, so a
    # relative band would either always pass or always fail)
    "downlink_fp_MB": ("rel", 0.25, 0.05),
    "uplink_bytes_per_TP": ("rel", 0.25, 256.0),
    "reconciliation_flip_rate": ("abs", 0.05, 0.0),
    "provisional_latency_s": ("rel", 0.25, 0.05),
    # control-plane columns (rush_hour): admission shed fraction, alert
    # volume (coarse band — queue-depth alerts ride load noise), and the
    # per-tier tail latencies.  slo_breach_top_tier is zero-band: the
    # preset's whole point is that the platinum tier NEVER breaches, so
    # any drift there is a regression, not noise.
    "shed_rate": ("abs", 0.10, 0.0),
    "alerts_total": ("rel", 0.50, 3.0),
    "slo_breach_top_tier": ("abs", 0.0, 0.0),
    "p99_latency_tier0": ("rel", 0.25, 0.10),
    "p99_latency_tier1": ("rel", 0.25, 0.10),
    "p99_latency_tier2": ("rel", 0.25, 0.10),
    # cross-camera track columns (vehicle_pursuit / crowd_flow): identity
    # continuity absolute (it is a 0..1 rate), count columns relative
    # with small floors (association is deterministic, but intentional
    # threshold re-tunes shift counts by a few), and the per-tick fused
    # associate launch budget near-exact
    "track_continuity": ("abs", 0.08, 0.0),
    "id_switches": ("rel", 0.30, 3.0),
    "tracks_born": ("rel", 0.30, 3.0),
    "track_handoffs": ("rel", 0.30, 3.0),
    "prewarms_shipped": ("rel", 0.50, 3.0),
    "prewarm_hits": ("rel", 0.50, 3.0),
    "track_launches_per_tick": ("abs", 0.05, 0.0),
}
PER_QUERY_TOLERANCES: Dict[str, Tuple[str, float, float]] = {
    "f2": ("abs", 0.05, 0.0),
    "avg_latency_s": ("rel", 0.25, 0.10),
}


@dataclasses.dataclass
class Check:
    """One verdict row for the ``--summary-md`` table: every compared
    metric (pass or fail) plus every structural/consistency breach."""
    tag: str                       # e.g. "drifting_city/surveiledge"
    metric: str
    fresh: object                  # value, or None when missing
    base: object
    tol: str                       # human-readable band, e.g. "±25% rel"
    ok: bool
    note: str = ""


def _tol_str(spec: Tuple[str, float, float]) -> str:
    kind, band, floor = spec
    if kind == "abs":
        return f"±{band} abs"
    return f"±{band:.0%} rel (floor {floor})"


def _note(checks: Optional[List[Check]], tag: str, metric: str,
          fresh, base, tol: str, ok: bool, note: str = "") -> None:
    if checks is not None:
        checks.append(Check(tag, metric, fresh, base, tol, ok, note))


def _check(metric: str, base: float, fresh: float,
           spec: Tuple[str, float, float]) -> str:
    """One metric against its band; returns a breach message or ''."""
    kind, band, floor = spec
    if kind == "abs":
        tol = band
    else:
        tol = max(band * abs(base), floor)
    if abs(fresh - base) > tol:
        return (f"{metric}: fresh={fresh} vs baseline={base} "
                f"(|delta|={abs(fresh - base):.4g} > tol={tol:.4g} "
                f"[{kind} {band}])")
    return ""


def compare_rows(base: dict, fresh: dict,
                 tolerances: Dict[str, Tuple[str, float, float]],
                 checks: Optional[List[Check]] = None,
                 tag: str = "") -> List[str]:
    """Diff one scheme (or per-query) row; missing metrics are breaches."""
    out = []
    for metric, spec in tolerances.items():
        if metric not in base:
            continue                  # older baseline without the column
        if metric not in fresh:
            out.append(f"{metric}: missing from fresh report")
            _note(checks, tag, metric, None, base[metric], _tol_str(spec),
                  False, "missing from fresh report")
            continue
        msg = _check(metric, float(base[metric]), float(fresh[metric]), spec)
        _note(checks, tag, metric, fresh[metric], base[metric],
              _tol_str(spec), not msg, msg)
        if msg:
            out.append(msg)
    return out


def row_consistency(tag: str, row: dict,
                    checks: Optional[List[Check]] = None) -> List[str]:
    """Physical-impossibility checks on ONE fresh scheme row.

    These hold regardless of any baseline: a run claiming fused
    recalibration launches must have shipped downlink bytes, and the
    charged (possibly quantized) downlink bytes can never exceed the
    row's own fp-equivalent reference — quantized shipping costing MORE
    than full-width fp is a wire-accounting bug, not drift to absorb."""
    out = []
    down = row.get("downloaded_bytes")
    if down is None:                  # older artifact: MB-only columns
        down = row.get("downloaded_MB", 0.0)
    if row.get("model_updates", 0) > 0 and down == 0:
        msg = (f"model_updates={row['model_updates']} but zero downlink "
               f"bytes — updates that never crossed the downlink")
        out.append(msg)
        _note(checks, tag, "downloaded_bytes", down,
              row.get("model_updates"), "> 0 when updates > 0", False, msg)
    fp_down = row.get("downlink_fp_bytes")
    if fp_down is not None and down > fp_down:
        msg = (f"downloaded_bytes={down} exceeds fp-equivalent reference "
               f"downlink_fp_bytes={fp_down} — quantized shipping cannot "
               f"cost more than full-width fp")
        out.append(msg)
        _note(checks, tag, "downloaded_bytes", down, fp_down,
              "<= downlink_fp_bytes", False, msg)
    # only the full adaptive scheme carries the zero-breach guarantee:
    # the ablation rows (fixed thresholds, edge_only, cloud_only) breach
    # tier 0 BY DESIGN — that contrast is the table's whole argument
    if tag.endswith("/surveiledge") \
            and row.get("slo_breach_top_tier", 0) > 0:
        msg = (f"slo_breach_top_tier={row['slo_breach_top_tier']} — the "
               f"top priority tier breached its SLO; admission control "
               f"failed to protect it")
        out.append(msg)
        _note(checks, tag, "slo_breach_top_tier",
              row["slo_breach_top_tier"], 0, "== 0", False, msg)
    if row.get("shed_queries", 0) > 0 and row.get("alerts_total", 0) == 0:
        msg = (f"shed_queries={row['shed_queries']} but alerts_total=0 — "
               f"admission shed queries without publishing alert events")
        out.append(msg)
        _note(checks, tag, "alerts_total", 0, row.get("shed_queries"),
              "> 0 when sheds > 0", False, msg)
    # the fused-association launch budget: at most ONE
    # ops.associate_tracks launch per scheduler tick, fleet-wide
    lpt = row.get("track_launches_per_tick", 0.0)
    if lpt > 1.0:
        msg = (f"track_launches_per_tick={lpt} > 1 — association must be "
               f"ONE fused launch per tick")
        out.append(msg)
        _note(checks, tag, "track_launches_per_tick", lpt, 1.0, "<= 1",
              False, msg)
    return out


def handoff_wins(name: str, schemes: dict,
                 checks: Optional[List[Check]] = None) -> List[str]:
    """The predictive hand-off must BEAT its own ablation within one
    fresh report.  The row pair is deterministic (same stream, same
    seed), so the comparison is exact: where pre-warms actually landed
    (``prewarm_hits > 0`` — vehicle_pursuit's sparse chase), ID switches
    must be STRICTLY below the no-handoff row; where the fleet stays
    naturally warm (crowd_flow's dense flow, zero hits), hand-off must
    at least do no harm."""
    on = schemes.get("surveiledge", {})
    off = schemes.get("surveiledge_no_handoff", {})
    if not (on.get("track_items") and off.get("track_items")):
        return []
    tag = f"{name}/surveiledge"
    sw_on, sw_off = on.get("id_switches", 0), off.get("id_switches", 0)
    strict = on.get("prewarm_hits", 0) > 0
    ok = sw_on < sw_off if strict else sw_on <= sw_off
    band = "< no_handoff (prewarms hit)" if strict else "<= no_handoff"
    _note(checks, tag, "id_switches(handoff vs ablation)", sw_on, sw_off,
          band, ok,
          "" if ok else "predictive hand-off no longer reduces ID switches")
    if not ok:
        return [f"{tag}: id_switches={sw_on} vs the no-handoff ablation's "
                f"{sw_off} (required {band}) — the predictive hand-off "
                f"stopped winning"]
    return []


def compare_report(baseline: dict, fresh: dict,
                   checks: Optional[List[Check]] = None) -> List[str]:
    """All breaches between one scenario's baseline and fresh report."""
    breaches: List[str] = []
    name = baseline.get("scenario", "?")
    b_schemes = baseline.get("schemes", {})
    f_schemes = fresh.get("schemes", {})
    breaches.extend(handoff_wins(name, f_schemes, checks))
    for scheme in sorted(set(b_schemes) | set(f_schemes)):
        tag = f"{name}/{scheme}"
        if scheme not in f_schemes:
            breaches.append(f"{tag}: scheme missing from fresh report")
            _note(checks, tag, "(scheme)", None, "present", "structure",
                  False, "scheme missing from fresh report")
            continue
        if scheme not in b_schemes:
            breaches.append(f"{tag}: scheme has no committed baseline "
                            f"(regenerate reports/ and commit)")
            _note(checks, tag, "(scheme)", "present", None, "structure",
                  False, "scheme has no committed baseline")
            continue
        b_row, f_row = b_schemes[scheme], f_schemes[scheme]
        breaches.extend(f"{tag}: {m}" for m in
                        compare_rows(b_row, f_row, TOLERANCES, checks, tag))
        breaches.extend(f"{tag}: {m}"
                        for m in row_consistency(tag, f_row, checks))
        b_q = b_row.get("queries", {})
        f_q = f_row.get("queries", {})
        for q in sorted(set(b_q) | set(f_q)):
            qtag = f"{tag}/q{q}"
            if q not in f_q:
                breaches.append(f"{qtag}: query missing from fresh report")
                _note(checks, qtag, "(query)", None, "present", "structure",
                      False, "query missing from fresh report")
            elif q not in b_q:
                breaches.append(f"{qtag}: query has no committed baseline")
                _note(checks, qtag, "(query)", "present", None, "structure",
                      False, "query has no committed baseline")
            else:
                breaches.extend(
                    f"{qtag}: {m}" for m in
                    compare_rows(b_q[q], f_q[q], PER_QUERY_TOLERANCES,
                                 checks, qtag))
    return breaches


def gate(fresh_dir: str, baseline_dir: str,
         checks: Optional[List[Check]] = None) -> List[str]:
    """Diff every ``*.json`` pairwise by filename; structural gaps breach."""
    base_files = {os.path.basename(p)
                  for p in glob.glob(os.path.join(baseline_dir, "*.json"))}
    fresh_files = {os.path.basename(p)
                   for p in glob.glob(os.path.join(fresh_dir, "*.json"))}
    breaches: List[str] = []
    for fn in sorted(base_files - fresh_files):
        breaches.append(f"{fn}: committed baseline has no fresh run "
                        f"(scenario dropped? delete the stale baseline)")
        _note(checks, fn, "(report)", None, "present", "structure", False,
              "committed baseline has no fresh run")
    for fn in sorted(fresh_files - base_files):
        breaches.append(f"{fn}: fresh report has no committed baseline "
                        f"(new scenario? run `make bench-smoke` and commit "
                        f"reports/{fn})")
        _note(checks, fn, "(report)", "present", None, "structure", False,
              "fresh report has no committed baseline")
    for fn in sorted(base_files & fresh_files):
        with open(os.path.join(baseline_dir, fn)) as fh:
            base = json.load(fh)
        with open(os.path.join(fresh_dir, fn)) as fh:
            fresh = json.load(fh)
        breaches.extend(compare_report(base, fresh, checks))
    return breaches


def write_summary_md(path: str, checks: List[Check]) -> None:
    """Append a per-metric verdict table (GitHub-flavored markdown) to
    ``path`` — in CI that is ``$GITHUB_STEP_SUMMARY``, so the deltas land
    on the job page instead of inside an uploaded JSON artifact.
    Failures render first; passing rows fold into a ``<details>``."""
    def fmt(v) -> str:
        if v is None:
            return "—"
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)

    def table(rows: List[Check]) -> List[str]:
        out = ["| artifact | metric | fresh | baseline | tolerance | "
               "verdict |", "|---|---|---|---|---|---|"]
        for c in rows:
            verdict = "✅ pass" if c.ok else f"❌ FAIL {c.note}".rstrip()
            out.append(f"| {c.tag} | {c.metric} | {fmt(c.fresh)} | "
                       f"{fmt(c.base)} | {c.tol} | {verdict} |")
        return out

    fails = [c for c in checks if not c.ok]
    passes = [c for c in checks if c.ok]
    lines = [f"### report-gate: {len(fails)} breach(es), "
             f"{len(passes)} metric(s) within tolerance", ""]
    if fails:
        lines += table(fails) + [""]
    if passes:
        lines += ["<details><summary>"
                  f"{len(passes)} passing metric(s)</summary>", ""]
        lines += table(passes)
        lines += ["", "</details>", ""]
    with open(path, "a") as fh:
        fh.write("\n".join(lines) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh", required=True,
                    help="directory of freshly generated scenario reports")
    ap.add_argument("--baseline", default="reports",
                    help="directory of committed baselines (default: "
                         "reports/)")
    ap.add_argument("--summary-md", metavar="PATH", default=None,
                    help="append a per-metric verdict table (markdown) to "
                         "PATH — point this at $GITHUB_STEP_SUMMARY in CI")
    args = ap.parse_args()
    fresh = glob.glob(os.path.join(args.fresh, "*.json"))
    if not fresh:
        print(f"report-gate: no fresh reports in {args.fresh}",
              file=sys.stderr)
        return 2
    checks: List[Check] = []
    breaches = gate(args.fresh, args.baseline, checks)
    if args.summary_md:
        write_summary_md(args.summary_md, checks)
    if breaches:
        print(f"report-gate: {len(breaches)} breach(es):", file=sys.stderr)
        for b in breaches:
            print(f"  BREACH {b}", file=sys.stderr)
        return 1
    print(f"report-gate: {len(fresh)} artifact(s) within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
