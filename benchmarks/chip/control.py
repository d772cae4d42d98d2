"""Readings that set a cell's limits: the program's compared numbers on
many seeds, and the control's on the same samples, in one process.

    python benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 ...

Each seed is one call of the cell at its own size (the window is one
call), compared as a benchmark run compares it; the control is the plain
reference computed in bfloat16, put in the program's place on the same
inputs.  One JSON line per seed, then the largest program reading and
the smallest control reading of each number.  Not run by the benchmark's
own runs; it needs the chip, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".cache", "jax")
    import jax
    from chipbench import cells as CL
    from chipbench import harness
    from repro.kernels.runtime import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = CL.load_cell(args.workload)
    config = CL.load_config(cell["config"])
    if config["frontend"] != "pixel":
        cell["traffic"]["streams"] = 1
    program, ctl = {}, {}
    for k, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        res = harness.run(cell, config, seed=seed, seconds=0.0, trace=False,
                          devices=jax.devices()[:cell["chips"]],
                          peaks=CL.peaks(dev.device_kind), warm=k == 0,
                          control=True)
        nums = {n: v for n, (v, _) in res["checks"].items()}
        for n, v in nums.items():
            program[n] = max(program.get(n, v), v)
        for n, v in res["control"].items():
            ctl[n] = min(ctl.get(n, v), v)
        print(json.dumps({"seed": seed, "program": nums,
                          "control": res["control"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"program_max": program, "control_min": ctl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
