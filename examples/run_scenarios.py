"""Run the end-to-end query pipeline over every registered scenario.

Each scenario is simulated under all four query schemes.  The default
frontend is the model-free synthetic confidence stream (fast; no model in
the loop); ``--frontend pixel`` runs the paper's full pixel path instead
(rendered frames -> Pallas pixel cascade -> motion crops -> CQ
scores).  For the CQ-model-scored workload, see
``benchmarks/table2_single_edge.py`` etc.

``--scenario all`` runs EVERY registered preset in this one process —
each with its designated frontend and smoke-sized overrides (the
``SMOKE_OVERRIDES`` table below) — so ``make bench-smoke`` and CI pay one
interpreter/jit warmup instead of five.  Scenarios with the cloud->edge
feedback loop enabled (``update_period_s`` set) additionally run the
open-loop ablation (``update_period_s=None``) as a fifth
``surveiledge_no_update`` row; scenarios with the bandwidth endgame on
(``quantize_downlink`` / ``speculative_escalation``) add a
``surveiledge_fp_wire`` ablation (full-width fp downlink, blocking
escalation) so the quantized reduction and the speculative latency win
are differential within one report; multi-query scenarios add per-query rows
(``queries``) to the JSON so the Fig. 5 training-scheme trade is visible
per query.

``--json-out DIR`` writes one ``<scenario>-<frontend>.json`` report per
scenario (CI diffs these against the committed ``reports/`` baselines via
``benchmarks/report_gate.py``) and fails the run if any metric comes back
NaN, the pipeline answered zero items, or a row is internally
inconsistent (``model_updates > 0`` with zero downlink bytes means the
loop "ran" without shipping anything — a broken report must fail loudly,
not upload quietly).  ``load_report`` applies the same consistency gate
when reading an artifact back.

  PYTHONPATH=src python examples/run_scenarios.py
  PYTHONPATH=src python examples/run_scenarios.py --scenario all --json-out reports
  PYTHONPATH=src python examples/run_scenarios.py --scenario drifting_city
  PYTHONPATH=src python examples/run_scenarios.py \
      --scenario pixel_city --frontend pixel --json-out reports
"""
import argparse
import dataclasses
import json
import math
import os
import sys

sys.path.insert(0, "src")

from repro.kernels.runtime import enable_compile_cache  # noqa: E402
from repro.system import (  # noqa: E402
    SCENARIOS,
    SCHEMES,
    PixelFrontend,
    run_query,
    synthetic_confidence_stream,
)

# ``--scenario all``: every preset in one process, each at its smoke-sized
# operating point (keys override the CLI defaults; ``frontend`` picks the
# pixel path where the scenario exists to exercise it).  These are also
# exactly the settings the committed ``reports/`` baselines are built
# from, so the report gate compares like with like.
SMOKE_OVERRIDES = {
    "city_scale": dict(duration=20.0),
    # metropolis pins >= 1024 edges; smoke shrinks cameras/duration only.
    # Its report rows stream (Scenario.metrics_window_s), so n_items comes
    # from QueryReport.n_items — the per-item arrays are intentionally empty.
    "metropolis": dict(cameras=1024, duration=12.0),
    "drifting_city": dict(cameras=8, duration=60.0),
    "multi_query_city": dict(cameras=8, duration=60.0),
    "query_churn": dict(cameras=8, duration=60.0),
    "pixel_city": dict(frontend="pixel", duration=10.0),
    "rush_hour": dict(cameras=4, duration=40.0),
    # track presets pin their own camera/edge geometry (the CLI default of
    # 6 cameras would break the alternating-edge chain the hand-off rides)
    "vehicle_pursuit": dict(cameras=12, duration=60.0),
    "crowd_flow": dict(cameras=8, duration=45.0),
}

#: the ``--cameras`` / ``--duration`` that ``make bench-smoke`` passes when
#: it (re)builds the committed ``reports/`` baselines
BASELINE_CAMERAS = 4
BASELINE_DURATION = 30.0


def check_consistency(name: str, scheme: str, summary: dict) -> None:
    """Raise ``ValueError`` on internally inconsistent report rows.

    Shared by the writer (``validate``) and the reader (``load_report``):
    a run that claims fused recalibration launches but shipped zero bytes
    down the WAN downlink cannot have closed the loop.  Gates on the RAW
    byte counter — MB rounding would wave through (or falsely damn) tiny
    ``update_nbytes`` payloads."""
    bytes_down = summary.get("downloaded_bytes",
                             summary.get("downloaded_MB", 0.0))
    if summary.get("model_updates", 0) > 0 and bytes_down == 0:
        raise ValueError(
            f"{name}/{scheme}: model_updates="
            f"{summary['model_updates']} but zero downlink bytes — model "
            f"updates that never crossed the downlink")
    # quantized-payload case: the charged wire bytes can never exceed the
    # fp-equivalent cost of the same shipments — quantized > fp means the
    # wire accounting double-charged (or the codec inflated the payload)
    fp_down = summary.get("downlink_fp_bytes")
    if fp_down is not None and bytes_down > fp_down:
        raise ValueError(
            f"{name}/{scheme}: downloaded_bytes={bytes_down} exceeds the "
            f"fp-equivalent reference downlink_fp_bytes={fp_down} — "
            f"quantized shipping cannot cost more than full-width fp")
    # admission sheds publish alerts/admission/<reason> events: a row
    # claiming shed queries with a silent alert stream means the control
    # plane dropped work without telling anyone — an unobservable shed is
    # an outage, not a policy
    if summary.get("shed_queries", 0) > 0 \
            and summary.get("alerts_total", 0) == 0:
        raise ValueError(
            f"{name}/{scheme}: shed_queries={summary['shed_queries']} but "
            f"alerts_total=0 — admission shed queries without publishing "
            f"alert events")


def validate(name: str, scheme: str, report) -> None:
    """Empty or NaN metrics make the JSON artifact meaningless: die loudly."""
    if report.n_items == 0:
        sys.exit(f"FAIL {name}/{scheme}: pipeline answered zero items")
    s = report.summary()
    bad = [k for k, v in s.items()
           if isinstance(v, (int, float)) and not math.isfinite(v)]
    if bad:
        sys.exit(f"FAIL {name}/{scheme}: non-finite metrics {bad}")
    try:
        check_consistency(name, scheme, s)
    except ValueError as e:
        sys.exit(f"FAIL {e}")


def load_report(path: str) -> dict:
    """Read a scenario JSON artifact back, re-checking row consistency.

    Raises ``ValueError`` for inconsistent rows (e.g. ``model_updates > 0``
    with zero downlink bytes), so downstream consumers never aggregate a
    physically impossible run."""
    with open(path) as fh:
        doc = json.load(fh)
    for scheme, row in doc.get("schemes", {}).items():
        check_consistency(doc.get("scenario", path), scheme, row)
    return doc


def compact_query_row(row: dict) -> dict:
    """Per-query JSON row with the per-edge payloads summarized to counts.

    ``per_query_summary`` rows carry each query's full ``live_edges`` list
    and per-edge ``thresholds`` dict — at metropolis scale (1024 edges x
    24 queries x 4 scheme rows) that is megabytes of JSON per report.  The
    gate (``benchmarks/report_gate.py``) compares only the scalar metrics,
    so the artifact keeps the counts and drops the per-edge bodies."""
    out = {k: v for k, v in row.items()
           if k not in ("live_edges", "thresholds")}
    if "live_edges" in row:
        out["n_live_edges"] = len(row["live_edges"])
    if "thresholds" in row:
        out["n_threshold_rows"] = len(row["thresholds"])
    return out


def run_scenario(name: str, frontend_name: str, cameras: int,
                 duration: float, seed: int, json_out: str = None,
                 **scenario_kw) -> dict:
    """Simulate one scenario under every scheme (+ ablation rows); print
    the table, optionally write/validate its JSON artifact, and return the
    report document.  ``scenario_kw`` goes to the preset factory."""
    sc = SCENARIOS[name](num_cameras=cameras, duration_s=duration, seed=seed,
                         **scenario_kw)
    frontend = PixelFrontend(seed=seed) if frontend_name == "pixel" else None
    if frontend is not None:
        stream = frontend.stream(sc)         # cached across the scheme sweep
    else:
        stream = synthetic_confidence_stream(sc)
    print(f"\n== {name} [{frontend_name}] — {len(stream)} detections, "
          f"{sc.num_edges} edge(s) + cloud, {len(sc.query_ids)} "
          f"quer{'y' if len(sc.query_ids) == 1 else 'ies'} ==")
    print(f"{'scheme':22s}{'F2':>8s}{'avg_lat':>9s}{'p99':>9s}"
          f"{'WAN_MB':>8s}{'LAN_MB':>8s}{'DL_MB':>7s}{'upd':>5s}"
          f"{'escal':>7s}{'flip':>7s}{'rerouted':>9s}{'launches':>9s}"
          f"{'l/tick':>7s}")
    # the feedback loop's ablation rides along as a fifth row wherever
    # the loop is enabled: same stream, update_period_s=None
    variants = [(s, sc.with_scheme(s)) for s in SCHEMES]
    if sc.update_period_s is not None:
        variants.append(("surveiledge_no_update", dataclasses.replace(
            sc.with_scheme("surveiledge"), update_period_s=None)))
    # the bandwidth-endgame ablation rides along wherever either knob is
    # on: same stream, full-width fp downlink + blocking escalation.  The
    # committed row pair is what lets the report gate check the quantized
    # downlink reduction and the speculative latency win differentially.
    if sc.quantize_downlink or sc.speculative_escalation:
        variants.append(("surveiledge_fp_wire", dataclasses.replace(
            sc.with_scheme("surveiledge"), quantize_downlink=False,
            speculative_escalation=False)))
    # the cross-camera track ablation rides along wherever predictive
    # hand-off is on: same stream, hand-off disabled.  The committed row
    # pair is what lets the report gate check the ID-switch win
    # differentially (no_handoff must switch identities MORE).
    if sc.track_query_ids and sc.predictive_handoff:
        variants.append(("surveiledge_no_handoff", dataclasses.replace(
            sc.with_scheme("surveiledge"), predictive_handoff=False)))
    per_scheme = {}
    for label, variant in variants:
        if frontend is not None:
            r = run_query(variant, frontend=frontend)
        else:
            r = run_query(variant, items=stream)
        if json_out:
            validate(name, label, r)
        s = r.summary()
        per_scheme[label] = {
            **s, "n_items": r.n_items,
            "accuracy_timeline": r.accuracy_timeline(),
            "stage_timings": {k: round(v, 4)
                              for k, v in r.stage_timings.items()}}
        if r.queries:
            # per-query rows: the runtime Fig. 5 trade (train_s vs f2 vs
            # head-of-query latency), one dict per live query
            per_scheme[label]["queries"] = {
                str(q): compact_query_row(row)
                for q, row in r.per_query_summary().items()}
        print(f"{label:22s}{s['accuracy_F2']:8.3f}"
              f"{s['avg_latency_s']:9.3f}{s['p99_latency_s']:9.3f}"
              f"{s['bandwidth_MB']:8.2f}{s['lan_MB']:8.2f}"
              f"{s['downloaded_MB']:7.2f}{s['model_updates']:5d}"
              f"{s['escalated']:7d}{s['reconciliation_flip_rate']:7.3f}"
              f"{s['rerouted']:9d}{s['kernel_launches']:9d}"
              f"{s['launches_per_tick']:7.2f}")
        if s.get("track_items"):
            print(f"   tracks: {s['tracks_born']} born, "
                  f"continuity {s['track_continuity']:.3f} "
                  f"({s['id_switches']} switches), "
                  f"{s['track_handoffs']} handoffs, "
                  f"{s['prewarm_hits']}/{s['prewarms_shipped']} "
                  f"prewarm hits, {s['track_launches_per_tick']:.2f} "
                  f"assoc launches/tick")
        if r.queries and label == "surveiledge":
            for q, row in sorted(r.per_query_summary().items()):
                print(f"   q{q} [{row.get('train_scheme', '?'):>12s}]"
                      f"{row['f2']:8.3f}{row['avg_latency_s']:9.3f}"
                      f"  train {row.get('train_s', 0.0):6.2f}s"
                      f"  deferred {row.get('deferred', 0):4d}"
                      f"  n {row['n_items']}")
    doc = {"scenario": name, "frontend": frontend_name,
           "n_detections": len(stream), "num_edges": sc.num_edges,
           "schemes": per_scheme}
    if json_out:
        os.makedirs(json_out, exist_ok=True)
        path = os.path.join(json_out, f"{name}-{frontend_name}.json")
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2)
        load_report(path)            # round-trip the consistency gate
        print(f"   -> {path}")
    return doc


def smoke_args(name: str, frontend: str = "confidence",
               cameras: int = BASELINE_CAMERAS,
               duration: float = BASELINE_DURATION):
    """(frontend, cameras, duration) of a preset's ``--scenario all`` run:
    its ``SMOKE_OVERRIDES`` entry over the given CLI values.  The defaults
    are the values ``make bench-smoke`` passes, which built ``reports/``."""
    ov = SMOKE_OVERRIDES.get(name, {})
    return (ov.get("frontend", frontend), ov.get("cameras", cameras),
            ov.get("duration", duration))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", choices=sorted(SCENARIOS) + ["all"],
                    default=None,
                    help="run just one scenario, or 'all' for every preset "
                         "in one process with per-scenario smoke overrides "
                         "(default: the small-fleet sweep)")
    ap.add_argument("--frontend", choices=("confidence", "pixel"),
                    default="confidence",
                    help="detection stream: model-free confidence synthesis "
                         "(default) or the rendered-frames pixel path")
    ap.add_argument("--json-out", metavar="DIR", default=None,
                    help="write per-scenario JSON reports to DIR and fail "
                         "on NaN/empty/inconsistent metrics")
    ap.add_argument("--cameras", type=int, default=6)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.scenario == "all":
        # every preset, one process: per-scenario frontend + smoke-sized
        # overrides from SMOKE_OVERRIDES, CLI values as the fallback
        for name in sorted(SCENARIOS):
            run_scenario(name, *smoke_args(name, args.frontend, args.cameras,
                                           args.duration),
                         args.seed, args.json_out)
        return
    if args.scenario:
        names = [args.scenario]
    else:
        # city_scale pins 64 edges / 512 cameras regardless of --cameras;
        # the default sweep stays small-fleet (run it explicitly, or via
        # `--scenario all` as `make bench-smoke` does)
        names = [n for n in sorted(SCENARIOS) if n != "city_scale"]
    for name in names:
        run_scenario(name, args.frontend, args.cameras, args.duration,
                     args.seed, args.json_out)


if __name__ == "__main__":
    main()
