"""The chip benchmark's trace reduction: busy union, idle share, kernel
time per name and labelled gaps, on traces built here."""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from chipbench import trace as TR  # noqa: E402


def _trace():
    # device 0: kernel A 10-30, kernel B 20-40 (overlaps A), A 60-70;
    # device 1: B 0-50 (starts before the window); window 5-105 ns
    ops = [TR.DeviceOp("d0", "fusion.1 cascade_kernel", 10, 30),
           TR.DeviceOp("d0", "custom-call.2 triage_kernel", 20, 40),
           TR.DeviceOp("d0", "fusion.1 cascade_kernel", 60, 70),
           TR.DeviceOp("d1", "custom-call.2 triage_kernel", 0, 50)]
    spans = [("chipbench.window", 5, 105), ("chipbench.run_query", 5, 100),
             ("chipbench.render", 40, 58), ("chipbench.render", 75, 95)]
    return TR.Trace(["d0", "d1"], ops, spans, (5, 105))


def test_busy_union_and_idle_share():
    tr = _trace()
    busy_s, per = TR.device_busy(tr)
    assert per == {"d0": 30 + 10, "d1": 45}
    assert busy_s == pytest.approx((40 + 45) / 2 / 1e9)
    share = 100.0 * (1.0 - busy_s / ((105 - 5) / 1e9))
    assert share == pytest.approx(57.5)


def test_kernel_time_by_name():
    tr = _trace()
    assert TR.op_seconds(tr, "cascade_kernel") == pytest.approx(30 / 2 / 1e9)
    assert TR.op_seconds(tr, "triage_kernel") == pytest.approx(
        (20 + 45) / 2 / 1e9)
    assert TR.op_seconds(tr) == pytest.approx((30 + 20 + 45) / 2 / 1e9)
    assert TR.op_seconds(tr, [("nothing",), ("custom-call", "triage")]) \
        == pytest.approx((20 + 45) / 2 / 1e9)
    assert TR.op_seconds(tr, [("custom-call", "cascade")]) == 0


def test_triage_kernel_found_by_its_hlo_custom_call():
    """A TPU trace names an operation by its HLO text."""
    import importlib
    reader = importlib.import_module("chipbench.cells").reader(
        "triage_roofline")
    tr = TR.Trace(["d0"], [
        TR.DeviceOp("d0", "%body.1 = (s32[256,8]{1,0}, s32[256,8]{1,0}, "
                    "s32[256,1]{1,0}) custom-call(f32[256,8]{1,0} %b)",
                    10, 20),
        TR.DeviceOp("d0", "%copy-done = f32[256,2]{0,1} copy-done(%c)",
                    20, 30)], [], (0, 100))
    ctx = {"trace": tr, "ready_sizes": [(10, 100)],
           "peaks": {"hbm_bytes_per_s": 1e9}}
    want = 100.0 * (100 * 12 + 10 * 13) / 1e9 / (10 / 1e9)
    assert reader(ctx) == pytest.approx(want)


def test_a_launched_kernel_missing_from_the_trace_is_an_error():
    from chipbench.cells import BenchError
    tr = _trace()
    assert TR.kernel_seconds(tr, "cascade_kernel", "x") == \
        pytest.approx(30 / 2 / 1e9)
    with pytest.raises(BenchError, match="no device operation"):
        TR.kernel_seconds(tr, [("custom-call", "cascade")], "x")


def test_gaps_are_labelled_by_the_innermost_span():
    tr = _trace()
    assert TR.gaps([(10, 30), (20, 40), (60, 70)], tr.window) == \
        [(5, 10), (40, 60), (70, 105)]
    bd = TR.breakdown(tr)
    assert bd["idle_gaps"] == [["render", pytest.approx(35e-9)],
                               ["render", pytest.approx(20e-9)],
                               ["run_query", pytest.approx(5e-9)]]
    names = [n for n, _ in bd["device_ops"]]
    assert names == ["custom-call.2", "fusion.1"]


def test_load_reads_the_benchmarks_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(TR.SPAN + "step"):
                f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    tr = TR.load(TR.find_xplane(str(tmp_path)))
    steps = [s for s in tr.spans if s[0] == TR.SPAN + "step"]
    assert len(steps) == 3
    lo, hi = tr.window
    assert all(lo <= s <= e <= hi for _, s, e in steps)
    assert hi - lo >= 6e6
