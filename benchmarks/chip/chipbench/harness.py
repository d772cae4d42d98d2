"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the reading of the per-layer metrics.

``run`` takes the cell and its configuration as data and the devices it
may use; the command line (``run.py``) checks the chip and the peaks
before it calls it, and tests call it on the CPU at a small size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import capture as CP
from chipbench import cells as CL
from chipbench import checks as CK
from chipbench import trace as TR
from chipbench import weights as WT
from traffic import generator as GEN


@dataclasses.dataclass
class Taps:
    """What the window's wrappers saw: sampled calls for the comparison
    and counts for the per-layer metrics."""
    ticks: CP.Reservoir             # pixel ticks: frames, mask, dets, scores
    supersteps: CP.Reservoir        # superstep launches
    pending: Optional[Dict] = None
    last_tick: Optional[Dict] = None
    cascade_shapes: List = dataclasses.field(default_factory=list)
    slab_shapes: List = dataclasses.field(default_factory=list)
    #: per call: each triaged row's (edge, items, routes, slots,
    #: [alpha, beta], capacity), and each finished item's (item, node,
    #: decision, finish time)
    routed: List[List] = dataclasses.field(default_factory=list)
    finished: List[List] = dataclasses.field(default_factory=list)
    #: real (rows, items) of each superstep tick: the ready map's keys and
    #: the items of the rows triaged, before any padding
    ready_sizes: List = dataclasses.field(default_factory=list)
    crops_scored: int = 0
    crop_tokens: int = 0
    detections: int = 0


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _install(stack: contextlib.ExitStack, taps: Taps, traced: bool) -> None:
    """Wrap the program's layer entry points for the window."""
    from repro.data import synthetic_video as SV
    from repro.detection import pipeline as DP
    from repro.kernels import ops
    from repro.system import pipeline, superstep, triage

    def name(s):
        return TR.SPAN + s if traced else None

    def on_cascade(i, args, kw, out):
        taps.cascade_shapes.append(tuple(args[0].shape[:3]))
        taps.pending = {"threshold": kw.get("threshold", 40),
                        "mask": out[0], "counts": out[1]}

    def on_detect(i, args, kw, out):
        rec = taps.pending or {}
        taps.pending = None
        taps.detections += sum(len(per) for per in out)
        rec.update(frames=args[0], dets=out, crop=kw["crop"],
                   min_area=kw["min_area"])
        taps.ticks.offer(lambda: rec)
        taps.last_tick = rec

    def on_score(i, args, kw, out):
        tokens = np.asarray(args[1])
        taps.crops_scored += tokens.shape[0]
        taps.crop_tokens = tokens.shape[1]
        rec = getattr(taps, "last_tick", None)
        if rec is not None:
            rec.update(tokens=tokens, scores=np.asarray(out))

    def on_superstep(i, args, kw, out):
        taps.slab_shapes.append(tuple(np.shape(args[0])))
        taps.supersteps.offer(lambda: dict(
            args=[np.asarray(a) for a in args], capacity=kw["capacity"],
            out=out))

    def on_tick_out(i, args, kw, out):
        drv, ready = args[0], args[2]
        outs, ths = out
        taps.ready_sizes.append((len(ready), sum(
            len(ready[key]) for key in outs)))
        cap = drv.sc.escalation_capacity
        taps.routed[-1].extend(
            (key[1], ready[key], r, s, ths[key], cap)
            for key, (r, s, _) in outs.items())

    def on_triage_tick(i, args, kw, out):
        stage = args[0]
        cap = stage.sc.escalation_capacity
        taps.routed[-1].extend(
            (key[1], args[1][key], r, s,
             (stage.states[key].alpha, stage.states[key].beta), cap)
            for key, (r, s, _) in out.items())

    for cap in (
            CP.Capture(superstep.SuperstepDriver, "tick_out", None,
                       on_tick_out, method=True),
            CP.Capture(triage.TriageStage, "triage_tick", None,
                       on_triage_tick, method=True),
            CP.FinishLog(pipeline.QueryPipeline, taps.finished),
            CP.Capture(SV, "render_triple", name("render")),
            CP.Capture(DP, "detect", name("detect"), on_detect),
            CP.Capture(ops, "pixel_cascade", name("pixel_cascade"),
                       on_cascade),
            CP.Capture(ops, "score_crops", name("score_crops"), on_score),
            CP.Capture(ops, "triage_fleet", name("triage_fleet")),
            CP.SuperstepCapture(superstep, name("superstep"),
                                on_superstep)):
        stack.enter_context(cap)


class Cell:
    """The system under test, set up for one cell: its frontend, its
    streams and its scenarios, warmed up."""

    def __init__(self, cell: Dict, config: Dict, seed: int):
        from repro.serving.simulator import Item
        from repro.system import SCENARIOS, QuerySpec, Scenario
        self.cell, self.config, self.seed = cell, config, seed
        # a named preset of the program, or the configuration's own
        # scenario fields, stated in full
        self.preset = SCENARIOS[config["preset"]] if "preset" in config \
            else Scenario
        self.Item, self.QuerySpec = Item, QuerySpec
        self.frontend = None
        self.weights = None
        self.streams: List = []
        if config["frontend"] == "pixel":
            self._pixel_frontend()
        else:
            n = int(cell["traffic"]["streams"])
            self.streams = [self._items(i) for i in range(n)]

    # --- set-up ---------------------------------------------------------------
    def _pixel_frontend(self):
        import jax
        from repro.system import PixelFrontend
        spec = self.config["classifier"]
        kw = dict(arch=spec["arch"], threshold=spec["cascade_threshold"],
                  crop=spec["crop"], min_area=spec["min_area"], cache=False)
        probe = PixelFrontend(params={}, **kw)
        CK.require_classifier(spec, probe.cfg)
        self.weights = WT.make(spec, self.seed)
        jax.block_until_ready(self.weights)
        self.frontend = PixelFrontend(params=self.weights, **kw)

    def _items(self, index: int) -> List:
        s = GEN.confidence_stream(self.config, self.cell, self.seed, index)
        It = self.Item
        return [It(t_arrival=float(t), camera=int(c), edge_device=int(e),
                   conf=float(f), is_query=bool(q), query=int(k))
                for t, c, e, f, q, k in zip(
                    s["t"], s["camera"], s["edge"], s["conf"],
                    s["is_query"], s["query"])]

    def scenario(self, index: int, span_s: Optional[float] = None):
        args = GEN.scenario_args(self.config, self.cell, self.seed, index)
        if span_s is not None:
            args["duration_s"] = span_s
        book = GEN.query_book(self.cell["traffic"], args["duration_s"])
        if book:
            args["queries"] = tuple(
                self.QuerySpec(q, t_arrive_s=t0, t_retire_s=t1,
                               train_scheme=sch) for q, t0, t1, sch in book)
        if self.config.get("frame_hw"):
            args["frame_hw"] = tuple(self.config["frame_hw"])
        return self.preset(**args).with_scheme(self.config["scheme"])

    def warm_up(self) -> None:
        """Compile the shapes this cell's traffic uses.  On the confidence
        path that is one call on each of the window's streams: the engine
        is deterministic, so a stream's superstep slabs are the same in
        the window.  On the pixel path, one tick on the warm-up seed and
        every padding bucket of the classifier and of the per-tick triage
        that its crop counts reach."""
        import jax
        from repro.kernels import ops
        if self.frontend is None:
            for i in range(len(self.streams)):
                self.call(i)
            return
        interval = float(self.config["scenario"].get("interval_s", 1.0))
        self.call(-1, span_s=interval)
        fe, spec = self.frontend, self.config["classifier"]
        score = functools.partial(fe._conf_fn, fe.params)
        T = spec["tokens"]
        for b in spec["warm_buckets"]:
            jax.block_until_ready(ops.score_crops(
                score, np.zeros((b, T), np.int32)))
        E = int(self.config["scenario"]["num_edges"])
        for n in spec["warm_triage_lanes"]:
            conf = np.full((1, E, n), 0.5, np.float32)
            th = np.tile(np.asarray([0.8, 0.2], np.float32), (1, E, 1))
            jax.block_until_ready(ops.triage_fleet(conf, th, capacity=64))

    # --- one served call --------------------------------------------------------
    def call(self, index: int, items=None, span_s: Optional[float] = None):
        """One ``run_query`` call; returns its report, its scenario and
        the number of detections it was given (None on the pixel path,
        where the program makes them)."""
        from repro.system import run_query
        sc = self.scenario(index, span_s)
        if self.frontend is not None:
            return run_query(sc, frontend=self.frontend), sc, None
        if items is None:
            items = self.streams[index % len(self.streams)]
        return run_query(sc, items=items), sc, len(items)


def _call_record(rep, sc, wall: float, due: int) -> Dict:
    return {"wall_s": wall, "camera_s": sc.num_cameras * sc.duration_s,
            "due": due,
            "stage_timings": dict(rep.stage_timings), "ticks": rep.ticks,
            "items": rep.n_items, "supersteps": rep.supersteps,
            "triaged_ticks": rep.triaged_ticks, "report": rep.summary(),
            "f2": rep.f_score(), "latency_mean": rep.avg_latency,
            "uploaded_bytes": rep.uploaded_bytes}


def run(cell: Dict, config: Dict, *, seed: int, seconds: float,
        trace: bool, devices, peaks: Dict, t_start: Optional[float] = None,
        warm: bool = True, control: bool = False) -> Dict:
    """Set up, measure for ``seconds``, compare, read; returns the
    result line's fields.  ``warm=False`` skips the warm-up (a process
    that has run the cell before has its programs), and ``control=True``
    adds the control's readings on the same samples (``control.py``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    clock = CP.CompileClock()
    try:
        return _run(cell, config, seed, seconds, trace, devices, peaks,
                    t_start, clock, warm, control)
    finally:
        clock.close()


def _run(cell, config, seed, seconds, trace, devices, peaks, t_start,
         clock, warm, control) -> Dict:
    import jax
    samples = cell.get("samples", {})

    unit = Cell(cell, config, seed)
    if warm:
        unit.warm_up()
    setup_s = time.perf_counter() - t_start
    before = clock.snapshot()
    n_compiled = len(clock.compiled)
    _log(f"set-up {setup_s} s, compile {clock.total} s, {before}")

    rng = np.random.SeedSequence([seed % (1 << 64), 99])
    r1, r2 = (int(x) for x in rng.generate_state(2))
    taps = Taps(ticks=CP.Reservoir(int(samples.get("ticks", 2)), r1),
                supersteps=CP.Reservoir(
                    int(samples.get("supersteps", 6)), r2))
    calls: List[Dict] = []
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    with contextlib.ExitStack() as stack:
        _install(stack, taps, trace)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with CP.span(TR.WINDOW if trace else None):
            t0 = time.perf_counter()
            i = 0
            while True:
                taps.routed.append([])
                taps.finished.append([])
                with CP.span(TR.SPAN + "run_query" if trace else None):
                    d0 = taps.detections
                    c0 = time.perf_counter()
                    rep, sc, due = unit.call(i)
                    c1 = time.perf_counter()
                due = taps.detections - d0 if due is None else due
                calls.append(_call_record(rep, sc, c1 - c0, due))
                del rep
                i += 1
                if c1 - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    in_window = {k: v - before[k] for k, v in clock.snapshot().items()}
    _log(f"window {window_s} s, {len(calls)} calls, in-window {in_window}"
         f" {sorted(set(clock.compiled[n_compiled:]))}")
    for k, c in enumerate(calls):
        _log(f"call {k}: wall {c['wall_s']} s, items {c['items']}, ticks "
             f"{c['ticks']}, supersteps {c['supersteps']}, stages "
             f"{c['stage_timings']}, F2 {c['report']['accuracy_F2']}, "
             f"avg latency {c['report']['avg_latency_s']} s, uplink "
             f"{c['report']['bandwidth_MB']} MB")
    _log(f"detections due {[c['due'] for c in calls]}, answered "
         f"{[c['items'] for c in calls]}")
    _log(f"slab shapes {sorted(set(taps.slab_shapes))}")

    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    # the program's state goes before the reference runs
    host_weights = jax.tree.map(np.asarray, unit.weights) \
        if unit.weights is not None else None
    del unit
    checks = CK.compare(cell, config, taps, host_weights, calls)
    extra = {"control": CK.control_numbers(config, taps, host_weights)} \
        if control else {}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"attempted": len(calls), "failed": 0, **extra}
    bench = CL.benchmark()
    if not trace:
        ctx = {"calls": calls, "window_s": window_s, "setup_s": setup_s}
        wanted = CL.metrics_of(cell["name"], "end_to_end", bench)
    else:
        tr = TR.load(TR.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy_s, per_dev = TR.device_busy(tr)
        device.update(busy_s=busy_s,
                      window_s=(tr.window[1] - tr.window[0]) / 1e9)
        result["breakdown"] = TR.breakdown(tr)
        _log(f"trace: devices {tr.devices}, {len(tr.ops)} device ops, "
             f"{len(tr.spans)} host spans, busy {per_dev} ns")
        ctx = {"calls": calls, "window_s": device["window_s"], "trace": tr,
               "peaks": peaks, "config": config, "cell": cell,
               "cascade_shapes": taps.cascade_shapes,
               "ready_sizes": taps.ready_sizes,
               "crops_scored": taps.crops_scored,
               "crop_tokens": taps.crop_tokens}
        wanted = CL.metrics_of(cell["name"], "per_layer", bench)
    metrics = {}
    for m in wanted:
        v = CL.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result.update(correct=all(v <= lim for v, lim in checks.values()),
                  metrics=metrics, device=device, checks=checks)
    return result
