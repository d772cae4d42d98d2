"""Tests for the end-to-end query harness (repro.system) and its parts:
scheduler edge cases, batched-triage capacity overflow, and run_query
consistency invariants on tiny scenarios."""
import numpy as np
import pytest

from repro.core.scheduler import CLOUD, Scheduler
from repro.kernels import ops, ref
from repro.serving.simulator import Item
from repro.system import (
    Scenario,
    run_query,
    single_edge,
    straggler_edge,
    synthetic_confidence_stream,
)
from repro.system.events import Task
from repro.system.pipeline import QueryPipeline

# --- Eq. 7 scheduler edge cases ----------------------------------------------


def test_select_node_tie_breaks_to_lowest_id():
    s = Scheduler([0, 1, 2])
    # all queues empty -> every cost is 0 -> the cloud (node 0) wins
    assert s.select_node() == CLOUD
    assert s.select_node(exclude_cloud=True) == 1
    assert s.select_node(exclude_cloud=True, exclude={1}) == 2


def test_select_node_exclude_cloud_never_returns_cloud():
    s = Scheduler([0, 1, 2])
    # pile work on the edges so the cloud is by far the cheapest
    for _ in range(50):
        s.on_enqueue(1)
        s.on_enqueue(2)
    assert s.select_node() == CLOUD
    assert s.select_node(exclude_cloud=True) in (1, 2)


def test_select_node_raises_when_nothing_eligible():
    s = Scheduler([0, 1])
    with pytest.raises(ValueError):
        s.select_node(exclude_cloud=True, exclude={1})
    s.mark_down(1)
    with pytest.raises(ValueError):
        s.select_node(exclude_cloud=True)
    s.mark_up(1)
    assert s.select_node(exclude_cloud=True) == 1


def test_select_node_skips_downed_nodes():
    s = Scheduler([0, 1, 2])
    s.mark_down(1)
    assert s.select_node(exclude_cloud=True) == 2


def test_select_node_extra_cost_steers_away():
    s = Scheduler([0, 1])
    # idle cloud would win the tie; an uplink-backlog charge flips it
    assert s.select_node() == CLOUD
    assert s.select_node(extra_cost={CLOUD: 10.0}) == 1


# --- latency-estimator regressions (Eq. 7 inputs must stay unbiased) ---------


def test_cloud_estimator_unbiased_by_wan_congestion():
    """One WAN congestion burst must not inflate the cloud's t_0: transfer
    time belongs to Transport (and Eq. 7's wan_backlog charge), never to
    the node latency estimator.  Before the fix, svc + tx_s fed the
    estimator and a saturated 0.05 MB/s uplink (~1 s per 49 KB crop)
    dragged t_0 orders of magnitude above the true service time."""
    sc = single_edge(num_cameras=6, duration_s=40.0, seed=3,
                     uplink_MBps=0.05).with_scheme("surveiledge_fixed")
    stream = synthetic_confidence_stream(sc)
    p = QueryPipeline(sc)
    r = p.run(stream)
    assert r.escalated > 20                  # the uplink really was stressed
    assert r.wan_transfer_s > 10.0           # ...and transport accounts it
    cloud_svc = sc.edge_service_s / sc.cloud_speedup
    est = p.sched.nodes[CLOUD].estimator
    assert len(est._history) > 0
    # the estimate converges to the true (jittered) service time, not to
    # service + transfer
    assert est.t < 3.0 * cloud_svc


def test_edge_estimator_unbiased_by_reclassify_mix():
    """An edge serving a classify/reclassify mix must still estimate the
    per-CQ-item latency: reclassify observations run reclassify_factor x
    slower and are normalized back, so drain_time (Eqs. 7-9) stays
    anchored to the queue's base service rate."""
    sc = Scenario(name="mix", edge_speeds=(1.0,), num_cameras=1,
                  duration_s=5.0, reclassify_factor=4.0)
    p = QueryPipeline(sc)
    p.run([])                                # initialize run-scoped state
    it = Item(t_arrival=0.0, camera=0, edge_device=1, conf=0.9,
              is_query=True)
    for k in range(300):
        phase = "classify" if k % 2 == 0 else "reclassify"
        task = Task(it, phase, True if phase == "classify" else None)
        p.nodes.push(1, task)
        p.sched.on_enqueue(1)
        started, svc = p.nodes.begin(0.0, 1)
        p._on_done(svc, 1, started, svc)
    est = p.sched.nodes[1].estimator
    # unbiased: ~1.0x the base CQ service time (lognormal jitter only);
    # the pre-fix mixed estimate sat near (1 + factor)/2 = 2.5x
    assert est.t < 1.5 * sc.edge_service_s
    assert est.t > 0.6 * sc.edge_service_s


# --- one edge's triage (a (1, N) fleet row): capacity overflow -------------


def _triage_row(conf, alpha, beta, capacity):
    routes, slots, counts = ops.triage_fleet(
        np.asarray(conf, np.float32)[None], [[alpha, beta]],
        capacity=capacity)
    return routes[0], slots[0], counts[0]


def test_triage_batched_overflow_leaves_tail_unescalated():
    conf = np.full(20, 0.5, np.float32)           # all in the [beta,alpha] band
    routes, slots, count = _triage_row(conf, 0.8, 0.1, 4)
    assert int(count) == 20                       # count reports all escalated
    np.testing.assert_array_equal(slots[:4], [0, 1, 2, 3])
    assert np.all(slots[4:] == -1)                # overflow: no buffer slot
    assert np.all(routes == 2)


@pytest.mark.parametrize("n,cap", [(3, 1), (17, 4), (64, 64), (100, 8)])
def test_triage_batched_matches_ref_under_overflow(n, cap):
    rng = np.random.default_rng(n)
    conf = rng.uniform(0, 1, n).astype(np.float32)
    got = _triage_row(conf, 0.7, 0.2, cap)
    want = ref.triage_ref(conf, 0.7, 0.2, cap)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_triage_batched_thresholds_are_runtime_data():
    """Adapting alpha/beta between calls must not change results vs ref
    (and hits the same cached jit trace — no per-threshold recompiles)."""
    conf = np.linspace(0, 1, 33, dtype=np.float32)
    for a, b in [(0.9, 0.05), (0.8, 0.1), (0.55, 0.3), (0.7, 0.2)]:
        got = _triage_row(conf, a, b, 16)
        want = ref.triage_ref(conf, a, b, 16)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --- run_query smoke: consistency invariants ---------------------------------


@pytest.fixture(scope="module")
def tiny_report():
    sc = single_edge(num_cameras=3, duration_s=30.0, seed=5)
    stream = synthetic_confidence_stream(sc)
    return sc, stream, run_query(sc, items=stream)


def test_run_query_answers_every_item_exactly_once(tiny_report):
    _, stream, r = tiny_report
    assert len(r.latencies) == len(stream)
    assert len(r.decisions) == len(stream)
    assert len(r.truths) == len(stream)


def test_run_query_metrics_are_monotonically_consistent(tiny_report):
    _, stream, r = tiny_report
    # completions are emitted in nondecreasing simulation time
    assert np.all(np.diff(r.finish_times) >= -1e-9)
    # nothing finishes before it arrives, and nothing takes negative time
    assert np.all(r.latencies >= 0)
    # queue samples are counts over exactly `ticks` scheduler intervals
    for node, q in r.queue_timeline.items():
        assert len(q) == r.ticks
        assert np.all(q >= 0)
    # escalations and bandwidth are consistent: uploads are whole crops and
    # only escalated / rerouted items ever leave an edge
    nbytes = stream[0].nbytes
    assert r.uploaded_bytes % nbytes == 0
    assert r.uploaded_bytes + r.lan_bytes \
        <= (r.escalated + r.rerouted) * nbytes
    assert 0.0 <= r.f_score() <= 1.0


def test_run_query_one_fused_launch_per_tick(tiny_report):
    sc, stream, r = tiny_report
    # one fused fleet-triage launch per tick-with-arrivals — NOT per edge:
    # the (E, N) tick matrix goes through ops.triage_fleet in one call
    ticks_with_arrivals = {int(it.t_arrival // sc.interval_s)
                           for it in stream}
    assert r.kernel_launches == len(ticks_with_arrivals)
    assert r.kernel_launches <= r.ticks


def test_run_query_edge_only_never_launches_triage(tiny_report):
    sc, stream, _ = tiny_report
    r = run_query(sc.with_scheme("edge_only"), items=stream)
    assert r.kernel_launches == 0
    assert r.escalated == 0
    assert r.uploaded_bytes == 0
    r = run_query(sc.with_scheme("cloud_only"), items=stream)
    assert r.kernel_launches == 0
    assert r.uploaded_bytes == len(stream) * stream[0].nbytes


def test_run_query_survives_edge_failure():
    sc = straggler_edge(num_cameras=4, duration_s=30.0, seed=3)
    stream = synthetic_confidence_stream(sc)
    r = run_query(sc, items=stream)
    # every item is still answered exactly once, despite edge 1 dying
    assert len(r.latencies) == len(stream)
    # the dead edge's queue is empty from the failure tick onward
    fail_tick = int(sc.failures[0][0] / sc.interval_s)
    assert np.all(r.queue_timeline[1][fail_tick + 1:] == 0)
    # its stranded + re-homed work went somewhere that costs bandwidth
    assert r.rerouted > 0
    assert r.uploaded_bytes + r.lan_bytes > 0
    # edge_only failover stays on the surviving edges: LAN traffic only,
    # and peers answer with the CQ model, not ground truth
    r_eo = run_query(sc.with_scheme("edge_only"), items=stream)
    assert len(r_eo.latencies) == len(stream)
    assert r_eo.uploaded_bytes == 0
    assert r_eo.lan_bytes > 0
    assert r_eo.f_score() < 1.0


def test_run_query_adaptive_sheds_under_burst():
    base = Scenario(name="burst-test", edge_speeds=(1.0,), num_cameras=6,
                    duration_s=40.0, burst_boost=9.0, burst_rate=1.5,
                    seed=7)
    stream = synthetic_confidence_stream(base)
    adaptive = run_query(base, items=stream)
    fixed = run_query(base.with_scheme("surveiledge_fixed"), items=stream)
    # the allocator + adaptive thresholds keep the overloaded system's
    # latency below frozen-threshold local-first operation
    assert adaptive.avg_latency < fixed.avg_latency
    assert adaptive.rerouted > 0
