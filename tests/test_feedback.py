"""Feedback-loop tests: calibrate-kernel Pallas/ref parity, padding
invisibility, Platt-fit recovery of a known logistic map, scenario
validation (ValueError, never assert), the drifting_city closed loop
beating the update_period_s=None ablation with exactly one fused
calibrate launch per update event, and report-loader consistency
rejection."""
import dataclasses
import json

import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.system import (
    Scenario,
    apply_calibration,
    drifting_city,
    run_query,
    synthetic_confidence_stream,
)

# --- ops.calibrate_fleet vs the independent NumPy oracle ----------------------


def _label_fleet(seed, lengths, n=None, a=2.0, b=0.5):
    """Per-edge (scores, truths) from a known logistic: y ~ Bernoulli of
    sigmoid(a * logit(s) + b).  Pad lanes score -1.0, truth 0."""
    rng = np.random.default_rng(seed)
    n = n if n is not None else max(lengths) if lengths else 1
    scores = np.full((len(lengths), max(n, 1)), -1.0, np.float32)
    truths = np.zeros((len(lengths), max(n, 1)), np.float32)
    for e, length in enumerate(lengths):
        s = rng.uniform(0.02, 0.98, length)
        p = 1.0 / (1.0 + np.exp(-(a * np.log(s / (1 - s)) + b)))
        scores[e, :length] = s
        truths[e, :length] = rng.uniform(0, 1, length) < p
    return scores, truths


def test_calibrate_fleet_pallas_matches_numpy_ref():
    scores, truths = _label_fleet(0, [200, 150, 7, 40, 0])
    truths[3, :40] = 1.0                     # single-class row -> identity
    got_p, got_c = ops.calibrate_fleet(scores, truths)
    want_p, want_c = ops.calibrate_fleet(scores, truths, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


def test_calibrate_fleet_padding_is_invisible():
    scores, truths = _label_fleet(1, [64, 33, 90])
    base, _ = ops.calibrate_fleet(scores, truths)
    wide = np.full((6, scores.shape[1] + 55), -1.0, np.float32)
    wide_t = np.zeros_like(wide)
    wide[:3, :scores.shape[1]] = scores
    wide_t[:3, :scores.shape[1]] = truths
    padded, counts = ops.calibrate_fleet(wide, wide_t)
    padded = np.asarray(padded)
    np.testing.assert_allclose(padded[:3], np.asarray(base), atol=1e-5)
    # pad edge rows are fully masked: identity params, zero counts
    np.testing.assert_allclose(padded[3:], [[1.0, 0.0]] * 3)
    assert np.all(np.asarray(counts)[3:] == 0)


def test_calibrate_fleet_degenerate_rows_fall_back_to_identity():
    scores, truths = _label_fleet(2, [40, 4, 40, 0])
    truths[2, :40] = 0.0                     # all-negative labels
    params, counts = ops.calibrate_fleet(scores, truths, min_count=8)
    params = np.asarray(params)
    assert not np.allclose(params[0], [1.0, 0.0])   # healthy row did fit
    np.testing.assert_allclose(params[1:], [[1.0, 0.0]] * 3)
    np.testing.assert_array_equal(np.asarray(counts), [40, 4, 40, 0])


def test_calibrate_fleet_recovers_known_logistic():
    scores, truths = _label_fleet(3, [4000], a=2.0, b=0.5)
    params, _ = ops.calibrate_fleet(scores, truths)
    a, b = np.asarray(params)[0]
    # Platt target smoothing + the MAP prior bias the fit slightly toward
    # the identity; with 4000 labels the pull is small
    assert abs(a - 2.0) < 0.3
    assert abs(b - 0.5) < 0.3


@pytest.mark.slow
def test_calibrate_fleet_padding_property():
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 48), min_size=1, max_size=5),
           st.integers(0, 4), st.integers(0, 60),
           st.integers(0, 2 ** 31 - 1))
    def prop(lengths, extra_rows, extra_cols, seed):
        scores, truths = _label_fleet(seed, lengths)
        base, base_c = ops.calibrate_fleet(scores, truths)
        E, N = scores.shape
        wide = np.full((E + extra_rows, N + extra_cols), -1.0, np.float32)
        wide_t = np.zeros_like(wide)
        wide[:E, :N] = scores
        wide_t[:E, :N] = truths
        padded, padded_c = ops.calibrate_fleet(wide, wide_t)
        np.testing.assert_allclose(np.asarray(padded)[:E],
                                   np.asarray(base), atol=1e-4)
        np.testing.assert_array_equal(np.asarray(padded_c)[:E],
                                      np.asarray(base_c))
        np.testing.assert_allclose(np.asarray(padded)[E:],
                                   np.tile([[1.0, 0.0]], (extra_rows, 1)))

    prop()


def test_apply_calibration_identity_is_bit_exact():
    conf = np.linspace(0.0, 1.0, 33, dtype=np.float32)
    assert apply_calibration(conf, 1.0, 0.0) is conf
    # a real map is monotone and stays in (0, 1) without overflow warnings
    with np.errstate(over="raise"):
        out = apply_calibration(conf, 6.0, -8.0)
    assert np.all(np.diff(out) >= 0)
    assert np.all((out >= 0) & (out <= 1))


# --- scenario validation (ValueError, never assert) ---------------------------


def test_with_scheme_unknown_raises_value_error():
    sc = drifting_city()
    with pytest.raises(ValueError, match="unknown scheme"):
        sc.with_scheme("bogus")


def test_fixed_thresholds_validated_at_construction():
    with pytest.raises(ValueError, match="alpha"):
        Scenario(name="bad", fixed_thresholds=(0.3, 0.1))
    with pytest.raises(ValueError, match="beta"):
        Scenario(name="bad", fixed_thresholds=(0.8, 0.6))
    with pytest.raises(ValueError, match="update_period_s"):
        Scenario(name="bad", update_period_s=0.0)
    # the valid corner is accepted
    Scenario(name="ok", fixed_thresholds=(0.5, 0.0))


# --- the closed loop on drifting_city -----------------------------------------


@pytest.fixture(scope="module")
def drift_runs():
    sc = drifting_city(num_cameras=8, duration_s=60.0, seed=0)
    stream = synthetic_confidence_stream(sc)
    closed = run_query(sc, items=stream)
    ablation = run_query(
        dataclasses.replace(sc, update_period_s=None), items=stream)
    return sc, stream, closed, ablation


def test_drift_stream_actually_drifts(drift_runs):
    sc, stream, _, _ = drift_runs
    pre_q = [it.conf for it in stream
             if it.is_query and it.t_arrival < sc.drift_at_s]
    post_q = [it.conf for it in stream
              if it.is_query and it.t_arrival >= sc.drift_at_s]
    assert np.mean(pre_q) > 0.7 > np.mean(post_q)


def test_closed_loop_beats_open_loop_on_drift(drift_runs):
    _, _, closed, ablation = drift_runs
    assert closed.model_updates > 0
    assert closed.downloaded_bytes > 0
    assert ablation.model_updates == 0
    assert ablation.downloaded_bytes == 0
    assert closed.f_score() > ablation.f_score()


def test_closed_loop_recovers_after_drift(drift_runs):
    sc, _, closed, ablation = drift_runs
    # windows fully past the drift: the recalibrated system climbs back,
    # the frozen one stays down
    def post_drift_mean(r):
        wins = [w["f2"] for w in r.accuracy_timeline(window_s=10.0)
                if w["t_start"] >= sc.drift_at_s + 10.0]
        assert wins
        return float(np.mean(wins))
    assert post_drift_mean(closed) > post_drift_mean(ablation)


def test_one_fused_calibrate_launch_per_update_event(drift_runs, monkeypatch):
    sc, stream, _, _ = drift_runs
    calls = {"n": 0}
    real = ops.calibrate_fleet

    def counting(*args, **kw):
        calls["n"] += 1
        return real(*args, **kw)

    monkeypatch.setattr(ops, "calibrate_fleet", counting)
    r = run_query(sc, items=stream)
    # fleet-wide recalibration is ONE ops.calibrate_fleet call per update
    # event — never one per edge
    assert r.model_updates > 0
    assert calls["n"] == r.model_updates
    assert calls["n"] < r.model_updates * sc.num_edges


def test_feedback_loop_off_by_default(drift_runs):
    _, stream, _, _ = drift_runs
    sc = Scenario(name="plain", edge_speeds=(1.0, 1.0), num_cameras=4,
                  duration_s=20.0)
    r = run_query(sc, items=[it for it in stream if it.t_arrival < 20.0])
    assert r.model_updates == 0
    assert r.downloaded_bytes == 0


# --- report loader consistency ------------------------------------------------


def test_load_report_rejects_updates_without_downlink(tmp_path):
    import importlib.util
    import pathlib
    script = pathlib.Path(__file__).resolve().parents[1] \
        / "examples" / "run_scenarios.py"
    spec = importlib.util.spec_from_file_location("run_scenarios", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    good = {"scenario": "drifting_city", "frontend": "confidence",
            "schemes": {"surveiledge": {"model_updates": 3,
                                        "downloaded_MB": 0.0,
                                        "downloaded_bytes": 24}}}
    path = tmp_path / "ok.json"
    path.write_text(json.dumps(good))
    # tiny payloads round to 0.0 MB but the raw byte gate sees them
    assert mod.load_report(str(path))["scenario"] == "drifting_city"
    bad = {"scenario": "drifting_city", "frontend": "confidence",
           "schemes": {"surveiledge": {"model_updates": 3,
                                       "downloaded_MB": 0.0,
                                       "downloaded_bytes": 0}}}
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="downlink"):
        mod.load_report(str(path))
