"""Host spans on the served path (``repro.system.spans``): their totals
tile a call's wall time in ``QueryReport.stage_timings``, a profiler
session changes no answer, the spans land in the profiler's trace with
their ids, and the chip benchmark's readers of them."""
import dataclasses
import glob
import os
import sys
import time

import jax
import pytest

from repro.system import Scenario, run_query
from repro.system.spans import Spans
from test_superstep import _assert_bit_exact

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")

#: keys that together tile a call; the triage_* parts split triage_s
TRIAGE_PARTS = ("triage_plan_s", "triage_pack_s", "triage_launch_s",
                "triage_fold_s")


def _scenario(**kw) -> Scenario:
    """Three edges, one failure mid-run, a calibration loop and 8-tick
    supersteps: every span of the engine opens."""
    return Scenario(
        name="spans", num_cameras=8, duration_s=8.0, interval_s=0.25,
        edge_speeds=(1.0, 0.5, 1.0), edge_service_s=0.04,
        escalation_capacity=4, failures=((4.0, 2),), update_period_s=2.0,
        superstep=8, **kw)


def test_self_seconds_exclude_children():
    sp = Spans()
    with sp.span("outer"):
        time.sleep(0.02)
        for k in range(2):
            with sp.span("inner", tick=k):
                time.sleep(0.01)
    assert sp.total("inner") == pytest.approx(sp.self_s("inner"))
    assert sp.total("inner") >= 0.02
    assert sp.self_s("outer") == pytest.approx(
        sp.total("outer") - sp.total("inner"))
    assert 0.02 <= sp.self_s("outer") < sp.total("outer")
    assert sp.total("absent") == sp.self_s("absent") == 0.0
    assert Spans().call > sp.call


def test_stage_timings_tile_the_call():
    sc = _scenario()
    run_query(sc)                                  # compile outside
    t0 = time.perf_counter()
    rep = run_query(sc)
    wall = time.perf_counter() - t0
    st = rep.stage_timings
    assert rep.supersteps > 0 and rep.model_updates > 0
    assert st["feedback_s"] > 0 and st["triage_launch_s"] > 0
    tiled = sum(v for k, v in st.items() if k not in TRIAGE_PARTS)
    assert tiled == pytest.approx(wall, rel=0.05)
    assert tiled <= wall
    assert sum(st[k] for k in TRIAGE_PARTS) <= st["triage_s"]
    assert all(v >= 0.0 for v in st.values())


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same run without and inside a profiler session, and the
    session's trace."""
    sc = _scenario()
    plain = run_query(sc)
    log_dir = str(tmp_path_factory.mktemp("spans-trace"))
    with jax.profiler.trace(log_dir):
        under = run_query(sc)
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return plain, under, path


def test_a_profiler_session_changes_no_answer(traced):
    plain, under, _ = traced
    _assert_bit_exact(plain, under)
    assert plain.kernel_launches == under.kernel_launches


def _host_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                out.append((ev.name, s, s + int(ev.duration_ns),
                            dict(ev.stats)))
    return out


def test_spans_land_in_the_trace_with_their_ids(traced):
    _, under, path = traced
    events = _host_events(path)
    ticks = [e for e in events if e[0] == "engine.tick"]
    assert ticks and all({"call", "tick"} <= set(e[3]) for e in ticks)
    call = ticks[0][3]["call"]
    assert all(e[3]["call"] == call for e in ticks)
    assert len({e[3]["tick"] for e in ticks}) == len(ticks)
    triage = [e for e in events if e[0] == "triage"]
    launches = [e for e in events if e[0] == "triage.launch"]
    assert len(launches) == under.kernel_launches == len(triage)
    for _, s, e, ids in launches:
        assert any(ts <= s and e <= te and tids["call"] == ids["call"]
                   for _, ts, te, tids in triage)
    runs = [e for e in events if e[0] == "run_query"]
    assert len(runs) == 1 and runs[0][3]["call"] == call


# --- the chip benchmark's readers of the spans --------------------------------


def _ctx():
    """Two calls of 1000 and 3000 items."""
    def call(items, launches, k):
        st = {"stream_s": 0.01 * k, "engine_setup_s": 0.02 * k,
              "engine_drive_s": 0.3 * k, "engine_tick_s": 0.1 * k,
              "triage_s": 0.05 * k, "triage_plan_s": 0.004 * k,
              "triage_pack_s": 0.006 * k, "triage_launch_s": 0.03 * k,
              "triage_fold_s": 0.008 * k, "feedback_s": 0.0,
              "engine_finalize_s": 0.001 * k}
        return {"items": items, "stage_timings": st,
                "report": {"kernel_launches": launches}}
    return {"calls": [call(1000, 6, 1.0), call(3000, 10, 3.0)]}


READS = {
    # metric: (expected on _ctx(), a key whose absence silences it)
    "drive_us_per_item": (1e6 * (0.3 + 0.9) / 4000, "engine_drive_s"),
    "tick_us_per_item": (1e6 * (0.1 + 0.3) / 4000, "engine_tick_s"),
    "call_us_per_item": (1e6 * 4 * 0.031 / 4000, "engine_finalize_s"),
    "triage_host_us_per_item": (1e6 * 4 * 0.018 / 4000, "triage_pack_s"),
    "triage_launch_ms": (1e3 * 4 * 0.03 / 16, "triage_launch_s"),
}


def _reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from chipbench import cells
    return cells.reader(name)


@pytest.mark.parametrize("name", sorted(READS))
def test_span_reader_value(name):
    assert _reader(name)(_ctx()) == pytest.approx(READS[name][0])


@pytest.mark.parametrize("name", sorted(READS))
def test_span_reader_is_silent_without_its_keys(name):
    ctx = _ctx()
    del ctx["calls"][1]["stage_timings"][READS[name][1]]
    assert _reader(name)(ctx) is None
    # a program older than the spans: no key of theirs at all
    old = {"calls": [dict(c, stage_timings={"triage_s": 0.1})
                     for c in _ctx()["calls"]]}
    assert _reader(name)(old) is None
