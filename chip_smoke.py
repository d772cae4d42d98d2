"""Smoke test of the served query path on a TPU: one process, compiled kernels.

Each phase drives one preset through ``run_query`` at the operating point
that built its committed report (``examples/run_scenarios.py --scenario
all`` with its ``SMOKE_OVERRIDES``), then checks the result two ways:

* the fresh report matches ``reports/<preset>-<frontend>.json`` within
  ``benchmarks/report_gate.py``'s tolerances;
* the phase's kernel, called again on the largest inputs the run gave it,
  matches its oracle in ``repro.kernels.ref``.

Phases: ``pixel_city`` (pixel cascade, CCL, CQ classifier, triage),
``city_scale`` (64-edge fleet triage), ``drifting_city`` (fleet Platt
calibration), ``vehicle_pursuit`` (track association) and ``metropolis``
(scan supersteps).  ``--chips 4`` runs only ``metropolis`` with
``shard_fleet`` over a 4-device fleet mesh and requires every superstep's
outputs, and the report, to be bit-identical to the same run unsharded.

Weights and data come from the seed; only committed files are read.  The
script refuses any backend but TPU.  Its last line on success is one JSON
object naming the device.

    python chip_smoke.py              # one chip, five phases
    python chip_smoke.py --chips 4    # sharded metropolis vs unsharded
"""
import argparse
import hashlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, d) for d in ("src", "examples",
                                                  "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import report_gate  # noqa: E402
import run_scenarios as RS  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.runtime import enable_compile_cache  # noqa: E402
from repro.system import superstep  # noqa: E402

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
PHASES = ("pixel_city", "city_scale", "drifting_city", "vehicle_pursuit",
          "metropolis")


class Capture:
    """Wraps ``owner.<name>`` for one phase and keeps the call whose
    ``rank(args, kwargs, out)`` is highest (ties go to the later call)."""

    def __init__(self, owner, name, rank):
        self.owner, self.name, self.rank = owner, name, rank
        self.calls, self.best = 0, None

    def __enter__(self):
        self.real = getattr(self.owner, self.name)
        setattr(self.owner, self.name, self._wrapped)
        return self

    def __exit__(self, *exc):
        setattr(self.owner, self.name, self.real)

    def _wrapped(self, *args, **kwargs):
        out = self.real(*args, **kwargs)
        self.calls += 1
        r = self.rank(args, kwargs, out)
        if self.best is None or r >= self.best[0]:
            self.best = (r, args, kwargs, out)
        return out


class SuperstepCapture(Capture):
    """``superstep._superstep_fn`` returns a jitted program; wrap that
    program so each launch is ranked by slab size and digested in order."""

    def __init__(self, superstep):
        super().__init__(superstep, "_superstep_fn",
                         lambda a, kw, out: a[0].size)
        self.digests = []

    def _wrapped(self, capacity, n_shards):
        fn = self.real(capacity, n_shards)

        def launch(*args):
            out = fn(*args)
            self.calls += 1
            arrays = [np.asarray(o) for o in out]
            self.digests.append(hashlib.sha256(
                b"".join(a.tobytes() for a in arrays)).hexdigest())
            if self.best is None or args[0].size >= self.best[0]:
                self.best = (args[0].size, args, {"capacity": capacity},
                             arrays)
            return out
        return launch


def _require(ok, what):
    if not ok:
        raise AssertionError(what)


def _exact(what, got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=what)


def check_pixel(cap):
    (f0, f1, f2), kw = cap.best[1], cap.best[2]
    _require(kw.get("use_pallas", True),
             f"pixel path left the Pallas kernel: {kw}")
    mask, counts = ops.pixel_cascade(f0, f1, f2, threshold=kw["threshold"])
    want_mask, want_counts = ref.pixel_cascade_np(
        np.asarray(f0), np.asarray(f1), np.asarray(f2), kw["threshold"])
    _exact("pixel_cascade mask", mask, want_mask)
    _exact("pixel_cascade counts", counts, want_counts)
    return f"pixel_cascade {tuple(f0.shape)} bit-exact, " \
           f"{int(want_counts.sum())} foreground px"


def check_triage(cap):
    (conf, th), kw = cap.best[1], cap.best[2]
    _require(kw.get("use_pallas", True), f"oracle path on the run: {kw}")
    got = ops.triage_fleet(conf, th, capacity=kw["capacity"])
    want = ref.triage_fleet_ref(jnp.asarray(conf, jnp.float32),
                                jnp.asarray(th, jnp.float32),
                                kw["capacity"])
    for what, g, w in zip(("routes", "slots", "counts"), got, want):
        _exact(f"triage_fleet {what}", g, w)
    return f"triage_fleet {np.shape(conf)} bit-exact, " \
           f"{int(np.sum(np.asarray(want[2])))} escalated"


def check_calibrate(cap):
    (scores, truths), kw = cap.best[1], cap.best[2]
    _require(kw.get("use_pallas", True), f"oracle path on the run: {kw}")
    iters, min_count = kw.get("iters", 8), kw.get("min_count", 8)
    params, counts = ops.calibrate_fleet(scores, truths, iters=iters,
                                         min_count=min_count)
    want_p, want_c = ref.calibrate_fleet_ref(np.asarray(scores),
                                             np.asarray(truths), iters,
                                             min_count)
    np.testing.assert_allclose(np.asarray(params), want_p, rtol=1e-3,
                               atol=1e-3, err_msg="calibrate_fleet params")
    _exact("calibrate_fleet counts", counts, want_c)
    return f"calibrate_fleet {np.shape(scores)} within 1e-3 of the " \
           f"float64 oracle, {int(want_c.sum())} labels"


def check_associate(cap):
    args, kw = cap.best[1], cap.best[2]
    _require(kw.get("use_pallas", True), f"oracle path on the run: {kw}")
    assign, sim = ops.associate_tracks(*args)
    want_a, want_s = ref.associate_tracks_ref(*(np.asarray(a) for a in args))
    _exact("associate assign", assign, want_a)
    np.testing.assert_allclose(np.asarray(sim), want_s, rtol=1e-5,
                               atol=1e-5, err_msg="associate sim")
    return f"associate (M, K, D)=({len(args[0])}, {len(args[1])}, " \
           f"{np.shape(args[0])[1]}) matches, " \
           f"{int((want_a >= 0).sum())} matched"


def check_superstep(cap):
    args, capacity, (routes, slots, ths) = \
        cap.best[1], cap.best[2]["capacity"], cap.best[3]
    S, R, N = np.shape(args[0])
    want_r, want_s, _ = ref.triage_fleet_ref(
        jnp.asarray(np.asarray(args[0]).reshape(S * R, N)),
        jnp.asarray(ths.reshape(S * R, 2)), capacity)
    _exact("superstep routes", routes.reshape(S * R, N), want_r)
    _exact("superstep slots", slots.reshape(S * R, N), want_s)
    return f"superstep triage slab ({S}x{R}, {N}) bit-exact"


def _captures(name):
    """The phase's kernel hook and its oracle check."""
    n = lambda x: np.asarray(x)  # noqa: E731
    if name == "pixel_city":
        return (Capture(ops, "pixel_cascade",
                        lambda a, kw, out: int(n(out[1]).sum())),
                check_pixel)
    if name == "city_scale":
        return (Capture(ops, "triage_fleet",
                        lambda a, kw, out: int((n(out[0]) == 2).sum())),
                check_triage)
    if name == "drifting_city":
        return (Capture(ops, "calibrate_fleet",
                        lambda a, kw, out: int(n(out[1]).sum())),
                check_calibrate)
    if name == "vehicle_pursuit":
        return (Capture(ops, "associate_tracks",
                        lambda a, kw, out: (len(a[0]) * len(a[1]),
                                            int((n(out[0]) >= 0).sum()))),
                check_associate)
    return SuperstepCapture(superstep), check_superstep


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.total += secs


def run_phase(name, clock, **scenario_kw):
    """One preset through ``run_query``; returns (doc, capture, message)."""
    frontend, cameras, duration = RS.smoke_args(name)
    cap, check = _captures(name)
    c0, t0 = clock.total, time.perf_counter()
    with cap:
        doc = RS.run_scenario(name, frontend, cameras, duration, 0,
                              json_out=OUT_DIR, **scenario_kw)
    wall, comp = time.perf_counter() - t0, clock.total - c0
    rows = doc["schemes"].values()
    items = sum(r["n_items"] for r in rows)
    launches = sum(r["kernel_launches"] for r in rows)
    print(f"phase {name}: wall {wall} s, compile {comp} s, items {items} "
          f"({doc['n_detections']} detections x {len(doc['schemes'])} "
          f"rows), triage launches {launches}, kernel calls {cap.calls}",
          flush=True)
    if cap.best is None:
        raise AssertionError(f"{name}: the run never called its kernel")
    return doc, cap, check


def check_report(name, doc):
    path = os.path.join(ROOT, "reports",
                        f"{name}-{doc['frontend']}.json")
    with open(path) as fh:
        baseline = json.load(fh)
    breaches = report_gate.compare_report(baseline, doc)
    if breaches:
        raise AssertionError(f"{name}: report breaches its committed "
                             f"baseline:\n  " + "\n  ".join(breaches))
    return f"report within tolerance of reports/{os.path.basename(path)}"


def one_chip(clock):
    failures = []
    for name in PHASES:
        try:
            doc, cap, check = run_phase(name, clock)
            print(f"phase {name}: {check(cap)}", flush=True)
            print(f"phase {name}: {check_report(name, doc)}", flush=True)
        except Exception as e:  # noqa: BLE001 — every phase gets its verdict
            failures.append(f"{name}: {type(e).__name__}: {e}")
            print(f"phase {name}: FAILED\n{traceback.format_exc()}",
                  flush=True)
    return failures


def four_chips(clock):
    """metropolis sharded over the fleet mesh vs the same run unsharded."""
    runs = {}
    for shard in (True, False):
        doc, cap, check = run_phase("metropolis", clock, shard_fleet=shard)
        print(f"metropolis shard_fleet={shard}: {check(cap)}, "
              f"{len(cap.digests)} supersteps", flush=True)
        runs[shard] = (doc, cap.digests)
    failures = []
    (doc_s, dig_s), (doc_u, dig_u) = runs[True], runs[False]
    if dig_s != dig_u:
        diff = sum(a != b for a, b in zip(dig_s, dig_u))
        failures.append(f"metropolis: sharded supersteps differ from "
                        f"unsharded ({diff} of {len(dig_u)} launches, "
                        f"{len(dig_s)} vs {len(dig_u)} calls)")
    strip = lambda d: {s: {k: v for k, v in r.items()  # noqa: E731
                           if k != "stage_timings"}
                       for s, r in d["schemes"].items()}
    if strip(doc_s) != strip(doc_u):
        failures.append("metropolis: sharded report differs from unsharded")
    if not failures:
        print(f"metropolis: sharded over {jax.device_count()} devices is "
              f"bit-identical to unsharded ({len(dig_u)} supersteps, "
              f"every report row)", flush=True)
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 2
    print(f"chip_smoke: {dev.device_kind} x{jax.device_count()}, compile "
          f"cache {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    failures = four_chips(clock) if args.chips == 4 else one_chip(clock)
    print(f"chip_smoke: total wall {time.perf_counter() - t0} s, compile "
          f"{clock.total} s", flush=True)
    if failures:
        print("chip_smoke: FAILED\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
