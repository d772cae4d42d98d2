"""Cross-camera TrackQuery tests: fused similarity/association kernel
parity (fixed + property shapes), greedy one-to-one and query-mask
invariants, the kinded QuerySpec surface, keyword-only run_query, the
one-fused-launch-per-tick budget, hand-off determinism across reruns and
drivers, the predictive-handoff-beats-ablation acceptance, and the
edge_health snapshot on QueryReport."""
import dataclasses

import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import similarity as SIM
from repro.serving.simulator import Item
from repro.system import (
    QuerySpec,
    crowd_flow,
    homogeneous_multi_edge,
    run_query,
    single_edge,
    straggler_edge,
    vehicle_pursuit,
)

# --- ops.associate_tracks: Pallas vs ref parity -------------------------------


def _rand_problem(rng, m, k, d, nq=2):
    emb = rng.normal(size=(m, d)).astype(np.float32)
    emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)
    trk = rng.normal(size=(k, d)).astype(np.float32)
    trk /= np.maximum(np.linalg.norm(trk, axis=1, keepdims=True), 1e-12)
    cq = rng.integers(0, nq, m).astype(np.int32)
    tq = rng.integers(0, nq, k).astype(np.int32)
    thr = rng.uniform(-0.5, 0.9, m).astype(np.float32)
    return emb, trk, cq, tq, thr


@pytest.mark.parametrize("m,k,d", [(5, 7, 16), (1, 1, 4), (16, 16, 32),
                                   (9, 30, 20), (33, 3, 8)])
def test_associate_pallas_matches_ref(m, k, d):
    rng = np.random.default_rng(m * 100 + k)
    emb, trk, cq, tq, thr = _rand_problem(rng, m, k, d)
    ap, sp = ops.associate_tracks(emb, trk, cq, tq, thr)
    ar, sr = ops.associate_tracks(emb, trk, cq, tq, thr, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ap), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)


def test_associate_2d_layout_matches_ref():
    """The kernel's 2-D operand layout — (M, 1) crop ids and floors, a
    (1, K) track-id row, an int32 claimed row — is bit-exact against the
    oracle, including tied best scores (first index wins, like argmax),
    crops whose query has no unclaimed track left, and pad ids."""
    rng = np.random.default_rng(5)
    emb, trk, cq, tq, thr = _rand_problem(rng, 24, 16, 32, nq=3)
    trk[9] = trk[4]                         # a tie: row 4 must win it
    emb[0] = trk[4]
    cq[0], tq[4], tq[9] = 2, 2, 2
    tq[tq == 1] = 0                         # query 1 has no tracks at all
    cq[-3:], tq[-2:] = -1, -2               # pad crops and pad tracks
    thr[:4] = -2.0                          # early crops claim greedily
    a, s = SIM.associate_pallas(emb, trk, cq, tq, thr, interpret=True)
    ar, sr = ref.associate_tracks_ref(emb, trk, cq, tq, thr)
    np.testing.assert_array_equal(np.asarray(a), ar)
    np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-5, atol=1e-5)
    assert ar[0] == 4
    assert np.all(ar[cq == 1] == -1) and np.all(ar[-3:] == -1)
    assert np.all(sr[cq == 1] == np.float32(-1e30))


def test_associate_empty_table_and_empty_crops():
    rng = np.random.default_rng(0)
    emb, trk, cq, tq, thr = _rand_problem(rng, 4, 6, 8)
    a, s = ops.associate_tracks(emb, trk[:0], cq, tq[:0], thr)
    assert np.all(np.asarray(a) == -1)
    a2, _ = ops.associate_tracks(emb[:0], trk, cq[:0], tq, thr[:0])
    assert np.asarray(a2).shape == (0,)


def test_associate_greedy_one_to_one_and_query_mask():
    rng = np.random.default_rng(7)
    emb, trk, cq, tq, thr = _rand_problem(rng, 24, 10, 16, nq=3)
    a, s = ops.associate_tracks(emb, trk, cq, tq, thr)
    a = np.asarray(a)
    claimed = a[a >= 0]
    assert len(claimed) == len(set(claimed)), "a track claimed twice"
    for i, j in enumerate(a):
        if j >= 0:
            assert cq[i] == tq[j], "association crossed query boundaries"
            assert np.asarray(s)[i] >= thr[i] - 1e-6


def test_associate_ref_prefers_best_available():
    # two crops chase the same track: the earlier crop wins it, the later
    # one falls to its next-best (greedy in crop order)
    trk = np.eye(3, dtype=np.float32)
    emb = np.stack([trk[0], 0.9 * trk[0] + 0.1 * trk[1]]).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    q = np.zeros(2, np.int32)
    thr = np.full(2, 0.05, np.float32)
    a, _ = ref.associate_tracks_ref(emb, trk, q, np.zeros(3, np.int32), thr)
    assert a[0] == 0 and a[1] == 1


@pytest.mark.slow
def test_associate_bucket_padding_invisible_property():
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    del hypothesis
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(m=st.integers(1, 21), k=st.integers(1, 19),
           d=st.integers(2, 24), seed=st.integers(0, 2**16))
    def check(m, k, d, seed):
        rng = np.random.default_rng(seed)
        emb, trk, cq, tq, thr = _rand_problem(rng, m, k, d)
        # wrapper (bucket-pads M, K, D internally) vs the ref oracle on
        # the UNPADDED inputs: padding must be invisible in the outputs
        a, s = ops.associate_tracks(emb, trk, cq, tq, thr)
        ar, sr = ref.associate_tracks_ref(emb, trk, cq, tq, thr)
        np.testing.assert_array_equal(np.asarray(a), ar)
        matched = np.asarray(a) >= 0
        np.testing.assert_allclose(np.asarray(s)[matched], sr[matched],
                                   rtol=1e-5, atol=1e-5)

    check()


# --- the kinded QuerySpec surface ---------------------------------------------


def test_queryspec_kind_validation():
    QuerySpec(0, kind="classify")
    QuerySpec(0, kind="track")
    with pytest.raises(ValueError, match="unknown kind"):
        QuerySpec(0, kind="reid")


def test_track_kind_rejects_superstep():
    with pytest.raises(ValueError, match="superstep"):
        dataclasses.replace(
            vehicle_pursuit(), superstep=4).__post_init__()


def test_existing_presets_bit_identical_under_kinded_spec():
    # satellite regression: classify-only presets produce the same
    # summary as before the kind field / track plumbing landed, and emit
    # NO track columns
    for preset in (single_edge, homogeneous_multi_edge):
        sc = preset(duration_s=15.0)
        s = run_query(sc).summary()
        assert not any(k.startswith(("track", "id_switch", "prewarm"))
                       for k in s), s
        assert s == run_query(sc).summary()


def test_run_query_knobs_keyword_only():
    sc = single_edge(duration_s=5.0)
    with pytest.raises(TypeError):
        run_query(sc, None)          # noqa: too many positional args
    with pytest.raises(ValueError, match="unknown frontend"):
        run_query(sc, frontend="cnn")
    r = run_query(sc, frontend="confidence")
    assert r.n_items > 0


# --- end-to-end track runs ----------------------------------------------------


def _pursuit(duration_s=25.0, **kw):
    return vehicle_pursuit(duration_s=duration_s, **kw)


def test_vehicle_pursuit_tracks_end_to_end():
    r = run_query(_pursuit())
    s = r.summary()
    assert s["track_items"] > 0
    assert s["tracks_born"] > 0
    assert s["track_matches"] > 0
    assert 0.0 <= s["track_continuity"] <= 1.0
    assert s["track_launches_per_tick"] <= 1.0 + 1e-9


def test_track_association_one_fused_launch_per_tick(monkeypatch):
    calls = {"n": 0}
    orig = ops.associate_tracks

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    import repro.system.tracks as TK
    monkeypatch.setattr(TK.ops, "associate_tracks", counting)
    r = run_query(_pursuit())
    assert calls["n"] == r.track_launches
    assert r.track_launches <= r.ticks


def test_handoff_beats_no_handoff_ablation():
    sc = vehicle_pursuit()
    on = run_query(sc)
    off = run_query(dataclasses.replace(sc, predictive_handoff=False))
    assert on.prewarms_shipped > 0 and on.track_handoffs > 0
    assert on.prewarm_hits > 0
    assert off.prewarms_shipped == 0
    # the acceptance criterion: predictive hand-off strictly reduces
    # identity switches on the pursuit scenario
    assert on.id_switches < off.id_switches
    assert on.track_continuity > off.track_continuity


def test_handoff_decisions_deterministic_across_reruns_and_drivers():
    from repro.serving.engine import AsyncDriver, VirtualClock
    sc = _pursuit()
    a = run_query(sc)
    b = run_query(sc)
    c = run_query(sc, driver=AsyncDriver(VirtualClock()))
    for other in (b, c):
        assert a.summary() == other.summary()
        assert a.prewarms_shipped == other.prewarms_shipped
        assert a.id_switches == other.id_switches
        np.testing.assert_array_equal(a.latencies, other.latencies)


def test_crowd_flow_mixes_track_and_classify():
    r = run_query(crowd_flow(duration_s=20.0))
    s = r.summary()
    assert s["n_queries"] == 2
    assert s["track_items"] > 0
    # the classify query's items never enter the track registry
    assert s["track_items"] < r.n_items


def test_track_table_dies_with_query_retire():
    sc = crowd_flow(duration_s=20.0)
    specs = tuple(dataclasses.replace(sp, t_retire_s=8.0)
                  if sp.kind == "track" else sp for sp in sc.queries)
    r = run_query(dataclasses.replace(sc, queries=specs))
    # association stops at retire: far fewer track items than the full run
    assert 0 < r.track_items < run_query(sc).track_items


def test_edge_health_snapshot_on_report():
    r = run_query(straggler_edge(duration_s=20.0))
    assert set(r.edge_health) == set(straggler_edge().edge_ids)
    snap = r.edge_health[1]
    assert set(snap) == {"alerts", "recent", "total"}
    # straggler_edge kills edge 1 mid-run: its failover must be visible
    assert snap["alerts"].get("failover", 0) >= 1
    assert snap["total"] == sum(snap["alerts"].values())
    assert any(a["topic"].startswith("alerts/edge1/") for a in snap["recent"])


@pytest.mark.slow
def test_pixel_frontend_emits_embeddings_for_track_queries():
    from repro.system import PixelFrontend
    sc = vehicle_pursuit(num_cameras=4, num_edges=2, duration_s=3.0)
    items = PixelFrontend(seed=0).stream(sc)
    assert items, "pixel path produced no detections"
    assert all(it.emb is not None for it in items)
    for it in items[:5]:
        assert it.emb.shape == (sc.embedding_dim,)
        assert abs(float(np.linalg.norm(it.emb)) - 1.0) < 1e-5
    # no trajectory ground truth on the pixel path
    assert all(it.gt_track == -1 for it in items)


def test_confidence_stream_embeddings_and_gt():
    from repro.system import synthetic_confidence_stream
    sc = _pursuit(duration_s=10.0)
    items = synthetic_confidence_stream(sc)
    tracked = [it for it in items if it.emb is not None]
    assert tracked
    for it in tracked[:10]:
        assert it.gt_track >= 0
        assert abs(float(np.linalg.norm(it.emb)) - 1.0) < 1e-5


def test_item_defaults_inert():
    it = Item(0.0, 0, 1, 0.5, False)
    assert it.emb is None and it.gt_track == -1
