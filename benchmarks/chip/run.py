"""On-chip benchmark of the served query path: one run of one cell.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): refuse any backend but TPU and any device
kind not in ``peaks.json``; turn on the compile cache inside the
checkout; read the cell (``workloads/<cell>.json``) and its configuration
(``configs/<config>.json``); draw the cell's traffic and the classifier's
weights from the seed; warm up the cell's shapes.  The window then drives
``repro.system.run_query`` back to back, one call per simulated span of
the fleet's video, on traffic that differs from call to call, and closes
at the end of the first call that ends at or after ``--seconds``.  After
it, sampled answers of the window are compared with the plain reference
(``chipbench/reference.py``); each number compared is printed beside its
limit, last on standard error and last in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (calls), ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device``, with ``--trace 1`` ``breakdown``, and
``checks``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".cache", "jax")
    from chipbench import cells as CL
    try:
        cell = CL.load_cell(args.workload)
        config = CL.load_config(cell["config"])
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise CL.BenchError(f"needs a TPU, JAX found "
                                f"{devices[0].platform!r}")
        if len(devices) < cell["chips"]:
            raise CL.BenchError(f"cell {args.workload!r} needs "
                                f"{cell['chips']} chips, JAX sees "
                                f"{len(devices)}")
        devices = devices[:cell["chips"]]
        peaks = CL.peaks(devices[0].device_kind)
        from repro.kernels.runtime import enable_compile_cache
        enable_compile_cache()
        # every program goes to the cache, so only a checkout's first run
        # compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        from chipbench import harness
        result = harness.run(cell, config, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             devices=devices, peaks=peaks, t_start=T_START)
    except CL.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    ordered = {k: result[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device")}
    if "breakdown" in result:
        ordered["breakdown"] = result["breakdown"]
    ordered["checks"] = {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}
    print(json.dumps(ordered), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
