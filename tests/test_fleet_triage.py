"""Fleet-scale engine tests: the fused (E, N) triage kernel vs E independent
per-edge references (hypothesis property), one-launch-per-tick on multi-edge
fleets, per-edge threshold divergence under asymmetric load, and the
city_scale smoke invariants."""
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import triage as TR
from repro.serving.simulator import Item
from repro.system import (
    Scenario,
    city_scale,
    homogeneous_multi_edge,
    run_query,
    synthetic_confidence_stream,
)

# --- ops.triage_fleet vs independent per-edge triage --------------------------


def _pack(batches, pad=-1.0):
    """Variable-length per-edge confidence lists -> padded (E, N) matrix."""
    n = max((len(b) for b in batches), default=0)
    conf = np.full((len(batches), max(n, 1)), pad, np.float32)
    for i, b in enumerate(batches):
        conf[i, :len(b)] = b
    return conf


def test_triage_fleet_matches_per_edge_batched():
    rng = np.random.default_rng(3)
    batches = [list(rng.uniform(0, 1, n)) for n in (5, 1, 17, 9)]
    th = np.asarray([[0.9, 0.05], [0.8, 0.1], [0.55, 0.3], [0.7, 0.2]],
                    np.float32)
    routes, slots, counts = ops.triage_fleet(_pack(batches), th, capacity=4)
    routes, slots = np.asarray(routes), np.asarray(slots)
    for e, b in enumerate(batches):
        rb, sb, cb = ref.triage_ref(
            np.asarray(b, np.float32), float(th[e, 0]), float(th[e, 1]), 4)
        np.testing.assert_array_equal(routes[e, :len(b)], np.asarray(rb))
        np.testing.assert_array_equal(slots[e, :len(b)], np.asarray(sb))
        assert int(np.asarray(counts)[e]) == int(cb)
        # pad lanes: always reject, never a slot, never counted
        assert np.all(routes[e, len(b):] == 1)
        assert np.all(slots[e, len(b):] == -1)


def test_triage_fleet_matches_ref_fleet():
    rng = np.random.default_rng(11)
    conf = rng.uniform(0, 1, (7, 33)).astype(np.float32)
    th = np.stack([rng.uniform(0.5, 1.0, 7), rng.uniform(0.0, 0.45, 7)],
                  axis=1).astype(np.float32)
    got = ops.triage_fleet(conf, th, capacity=8)
    want = ref.triage_fleet_ref(conf, th, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("E,N,capacity", [
    (8, 8, 3),            # narrow tick, one block
    (16, 1024, 5),        # two lane blocks: the count carries across them
    (2048, 128, 40),      # two row blocks of 1024
    (4, 1536, 600),       # three lane blocks, clamp far into the row
])
def test_cumsum_free_compaction_matches_ref(E, N, capacity):
    """The gridded kernel's matmul prefix sum is bit-exact against the
    cumsum oracle: slots across lane blocks, the ``capacity`` clamp, and
    rows with no escalations (all-accept, all-reject, all-pad)."""
    rng = np.random.default_rng(E * 7 + N)
    conf = rng.uniform(0, 1, (E, N)).astype(np.float32)
    th = np.stack([rng.uniform(0.5, 1.0, E), rng.uniform(0.0, 0.45, E)],
                  axis=1).astype(np.float32)
    conf[0] = 0.99                          # every lane accepts
    conf[1] = 0.01                          # every lane rejects
    conf[2, :] = -1.0                       # a pad row
    th[3] = (1.0, 0.0)                      # every lane escalates
    got = TR.triage_fleet_pallas(conf, th, capacity=capacity, interpret=True)
    want = ref.triage_fleet_ref(conf, th, capacity)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    counts, slots = np.asarray(got[2]), np.asarray(got[1])
    assert counts[0] == counts[1] == counts[2] == 0
    assert counts[3] == N and slots[3].max() == min(N, capacity) - 1


@pytest.mark.slow
def test_triage_fleet_property_matches_independent_calls():
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        st.lists(st.integers(0, 24), min_size=1, max_size=6),
        st.integers(1, 16),
        st.integers(0, 2 ** 31 - 1),
    )
    def prop(lengths, capacity, seed):
        rng = np.random.default_rng(seed)
        batches = [list(rng.uniform(0, 1, n)) for n in lengths]
        th = np.stack(
            [rng.uniform(0.5, 1.0, len(lengths)),
             rng.uniform(0.0, 0.5, len(lengths))], axis=1).astype(np.float32)
        routes, slots, counts = ops.triage_fleet(
            _pack(batches), th, capacity=capacity)
        routes, slots, counts = (np.asarray(routes), np.asarray(slots),
                                 np.asarray(counts))
        for e, b in enumerate(batches):
            if b:
                rb, sb, cb = ref.triage_ref(
                    np.asarray(b, np.float32), float(th[e, 0]),
                    float(th[e, 1]), capacity)
                np.testing.assert_array_equal(routes[e, :len(b)],
                                              np.asarray(rb))
                np.testing.assert_array_equal(slots[e, :len(b)],
                                              np.asarray(sb))
                assert int(counts[e]) == int(cb)
            else:
                assert int(counts[e]) == 0
            # pad lanes never claim escalation slots (or routes != reject)
            assert np.all(routes[e, len(b):] == 1)
            assert np.all(slots[e, len(b):] == -1)

    prop()


# --- one fused launch per tick on a multi-edge fleet --------------------------


def test_multi_edge_fleet_is_one_launch_per_tick():
    sc = homogeneous_multi_edge(num_cameras=6, duration_s=30.0, seed=2)
    stream = synthetic_confidence_stream(sc)
    ticks_with_arrivals = {int(it.t_arrival // sc.interval_s)
                           for it in stream}
    assert sc.num_edges == 3
    r = run_query(sc, items=stream)
    assert len(r.latencies) == len(stream)
    # ONE launch per tick-with-arrivals for the whole fleet, not per edge
    assert r.kernel_launches == len(ticks_with_arrivals)
    assert r.kernel_launches < len(ticks_with_arrivals) * sc.num_edges
    # the frozen-threshold cascade fleet-launches identically
    rf = run_query(sc.with_scheme("surveiledge_fixed"), items=stream)
    assert rf.kernel_launches == len(ticks_with_arrivals)


# --- per-edge adaptive thresholds ---------------------------------------------


def test_per_edge_thresholds_diverge_under_asymmetric_load():
    """One drowning edge and one idle edge in the same run: the loaded
    edge's Eqs. 8-9 state tightens its [beta, alpha] escalation bracket
    (alpha falls, beta rises) while the idle edge's widens past its start,
    which a single fleet-global threshold pair cannot do."""
    sc = Scenario(name="asym", edge_speeds=(1.0, 1.0), num_cameras=2,
                  duration_s=60.0, offload_drain_s=1e9, seed=1)
    items = []
    for k in range(60):
        for i in range(20):      # edge 1: ~1.6s of service arriving per 1s
            items.append(Item(t_arrival=k + i / 25.0, camera=0,
                              edge_device=1, conf=0.95, is_query=True))
        items.append(Item(t_arrival=k + 0.5, camera=1, edge_device=2,
                          conf=0.95, is_query=True))
    items.sort(key=lambda it: it.t_arrival)
    r = run_query(sc, items=items)
    a_loaded, b_loaded = r.thresholds[1]
    a_idle, b_idle = r.thresholds[2]
    assert a_loaded < 0.8 < a_idle       # 0.8 is the shared starting alpha
    assert b_loaded > b_idle
    # and both still satisfy the Eqs. 8-9 clamps
    for a, b in r.thresholds.values():
        assert 0.5 <= a <= 1.0
        assert 0.0 <= b < 0.5


# --- city_scale smoke ---------------------------------------------------------


def test_city_scale_smoke_invariants():
    sc = city_scale(duration_s=10.0, seed=0)
    assert sc.num_edges >= 64
    assert sc.num_cameras >= 512
    assert len({e for _, e in sc.failures}) == len(sc.failures) >= 2
    stream = synthetic_confidence_stream(sc)
    assert len(stream) > 1000
    r = run_query(sc, items=stream)
    # every item is answered exactly once despite rolling edge failures
    assert len(r.latencies) == len(stream)
    assert len(r.decisions) == len(stream)
    assert np.all(r.latencies >= 0)
    assert np.all(np.diff(r.finish_times) >= -1e-9)
    # the whole 64-edge fleet still costs ONE kernel launch per tick
    ticks_with_arrivals = {int(it.t_arrival // sc.interval_s)
                           for it in stream}
    assert r.kernel_launches == len(ticks_with_arrivals)
    assert r.kernel_launches == r.ticks      # 512 cameras: every tick busy
    # per-edge threshold state exists for the whole fleet
    assert sorted(r.thresholds) == list(sc.edge_ids)
