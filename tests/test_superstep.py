"""Scan-superstep differential harness: ``superstep=K`` must be
bit-exact against the ``superstep=1`` per-tick reference driver (and,
for the fixed scheme, against the true pre-superstep legacy loop),
boundary events must SPLIT supersteps rather than be absorbed by them,
sharded execution must match single-device, and the metropolis preset
must actually buy the >= 10x host-loop reduction it exists for."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.kernels.buckets import MAX_FLEET_ROWS
from repro.system import (
    QuerySpec,
    Scenario,
    city_scale,
    drifting_city,
    metropolis,
    multi_query_city,
    run_query,
)

# summary keys that legitimately differ between segmentations of the same
# run: one fused launch replaces many per-tick launches
_LAUNCH_KEYS = ("kernel_launches", "launches_per_tick", "supersteps")


def _strip_launch_keys(summary):
    return {k: v for k, v in summary.items() if k not in _LAUNCH_KEYS}


def _assert_bit_exact(ra, rb):
    """Everything observable except the launch accounting must be
    IDENTICAL — latencies, decisions, truths, query ids, final per-edge
    thresholds, per-query lifecycle facts, and the summary row."""
    np.testing.assert_array_equal(ra.latencies, rb.latencies)
    np.testing.assert_array_equal(ra.decisions, rb.decisions)
    np.testing.assert_array_equal(ra.truths, rb.truths)
    np.testing.assert_array_equal(ra.finish_times, rb.finish_times)
    np.testing.assert_array_equal(ra.query_ids, rb.query_ids)
    assert ra.thresholds == rb.thresholds
    assert ra.queries == rb.queries
    assert _strip_launch_keys(ra.summary()) == _strip_launch_keys(
        rb.summary())


def _pair(base: Scenario, ka, kb):
    ra = run_query(dataclasses.replace(base, superstep=ka))
    rb = run_query(dataclasses.replace(base, superstep=kb))
    return ra, rb


# --- differential: K=1 reference vs K=N fused, per preset ---------------------


def test_city_scale_superstep_bit_exact():
    base = city_scale(duration_s=6.0, num_failures=2, interval_s=0.25)
    ra, rb = _pair(base, 1, 16)
    _assert_bit_exact(ra, rb)
    assert rb.supersteps < ra.supersteps  # fusion actually happened


def test_multi_query_city_superstep_bit_exact():
    base = multi_query_city(duration_s=30.0)
    ra, rb = _pair(base, 1, 25)
    _assert_bit_exact(ra, rb)
    assert rb.supersteps < ra.supersteps


def test_drifting_city_superstep_bit_exact():
    """Calibration deliveries (ModelUpdate) are boundaries: the fused run
    must split at each one so rows see exactly the calibration the
    per-tick driver would have applied."""
    base = drifting_city(duration_s=30.0)
    ra, rb = _pair(base, 1, 10)
    _assert_bit_exact(ra, rb)
    assert rb.model_updates == ra.model_updates > 0


def test_fixed_scheme_superstep_matches_true_legacy():
    """``surveiledge_fixed`` never refreshes thresholds and never sheds,
    so ``superstep=K`` must be bit-exact against ``superstep=None`` —
    the UNTOUCHED pre-superstep per-tick live-signal loop, not just the
    K=1 reference."""
    base = multi_query_city(duration_s=30.0).with_scheme(
        "surveiledge_fixed")
    ra = run_query(dataclasses.replace(base, superstep=None))
    rb = run_query(dataclasses.replace(base, superstep=16))
    _assert_bit_exact(ra, rb)


# --- boundary events split supersteps, never get absorbed ---------------------


@pytest.mark.slow
def test_random_boundaries_split_supersteps_property():
    """Hypothesis property: for random K and random boundary placements
    (edge failures + a query retire landing anywhere in the run, i.e.
    mid-superstep almost surely), ``superstep=K`` stays bit-exact vs the
    K=1 reference.  A superstep that absorbed a boundary instead of
    splitting at it would triage post-boundary ticks with stale
    liveness/calibration state and diverge."""
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    duration = 12.0

    @settings(max_examples=10, deadline=None)
    @given(k=st.integers(min_value=2, max_value=25),
           fail_frac=st.floats(min_value=0.05, max_value=0.95),
           # the retire lands after query 1's arrival at 0.1 of the run
           retire_frac=st.floats(min_value=0.15, max_value=0.95),
           seed=st.integers(min_value=0, max_value=3))
    def prop(k, fail_frac, retire_frac, seed):
        base = Scenario(
            name="boundary_prop", num_cameras=8, duration_s=duration,
            interval_s=0.25, edge_speeds=(1.0, 0.5, 1.0),
            edge_service_s=0.04, escalation_capacity=4,
            failures=((duration * fail_frac, 2),),
            queries=(QuerySpec(0, 0.0, None, "surveiledge"),
                     QuerySpec(1, duration * 0.1,
                               duration * retire_frac, "no_finetune")),
            train_step_s=duration / 2000.0, seed=seed)
        _assert_bit_exact(*_pair(base, 1, k))

    prop()


# --- metropolis: scale smoke + determinism + sharding -------------------------


@pytest.fixture(scope="module")
def metro_report():
    """One shrunken metropolis run shared by the scale assertions (the
    full preset is a minutes-long benchmark; 1024 cameras over 12 s keeps
    the >= 1024-edge fleet and the boundary structure)."""
    return run_query(metropolis(num_cameras=1024, duration_s=12.0))


def test_metropolis_host_loop_reduction(metro_report):
    """The acceptance bar: one fused launch per boundary-free run must
    replace >= 10 per-tick host-loop iterations, while the
    one-launch-per-triaged-tick budget stays intact (launches can only
    ever be FEWER than ticks, never more)."""
    r = metro_report
    assert r.supersteps > 0
    assert r.triaged_ticks / r.supersteps >= 10.0
    assert r.kernel_launches <= r.triaged_ticks
    assert r.summary()["launches_per_tick"] <= 1.0


def test_metropolis_streams_report_aggregates(metro_report):
    """Streaming aggregates replace the per-item arrays: O(window)
    report memory with the item count still legible via ``n_items``."""
    r = metro_report
    assert len(r.latencies) == 0 and len(r.decisions) == 0
    assert r.stream is not None and r.n_items == r.stream.n > 0
    assert 0.0 < r.summary()["accuracy_F2"] <= 1.0
    rows = r.accuracy_timeline()
    assert rows and sum(row["n"] for row in rows) == r.n_items
    per_q = r.per_query_summary()
    assert len(per_q) >= 12  # dozens of concurrent CQs is the point
    assert sum(row["n_items"] for row in per_q.values()) == r.n_items


def test_metropolis_determinism_same_seed(metro_report):
    """Two same-seed runs produce byte-identical reports — the fused
    scan + shard_map path must not introduce any run-to-run jitter."""
    again = run_query(metropolis(num_cameras=1024, duration_s=12.0))
    assert again.summary() == metro_report.summary()
    assert again.per_query_summary() == metro_report.per_query_summary()
    assert again.accuracy_timeline() == metro_report.accuracy_timeline()
    assert again.thresholds == metro_report.thresholds


@pytest.mark.skipif(
    jax.device_count() < 8,
    reason="sharded-vs-single-device equivalence needs >= 8 devices "
           "(run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
           ", see `make test-sharded`)")
def test_metropolis_sharded_matches_single_device(metro_report):
    """With >= 8 host devices, the ``shard_fleet`` row-axis shard_map
    must be bit-exact vs the single-device program (rows are independent
    — shard-local execution IS the semantics)."""
    solo = run_query(metropolis(num_cameras=1024, duration_s=12.0,
                                shard_fleet=False))
    assert solo.summary() == metro_report.summary()
    assert solo.per_query_summary() == metro_report.per_query_summary()
    assert solo.thresholds == metro_report.thresholds


# --- config validation against the kernel bucket table ------------------------


def test_scenario_rejects_empty_edge_fleet():
    with pytest.raises(ValueError, match="at least one edge"):
        Scenario(name="bad", edge_speeds=())


def test_scenario_rejects_zero_escalation_capacity():
    with pytest.raises(ValueError, match="escalation_capacity"):
        Scenario(name="bad", escalation_capacity=0)


def test_scenario_rejects_fleet_over_bucket_table():
    queries = tuple(QuerySpec(q, 0.0, None, "no_finetune")
                    for q in range(64))
    with pytest.raises(ValueError, match="bucket table"):
        Scenario(name="bad", edge_speeds=(1.0,) * (MAX_FLEET_ROWS // 16),
                 queries=queries)


def test_scenario_rejects_bad_superstep():
    with pytest.raises(ValueError, match="superstep"):
        Scenario(name="bad", superstep=0)


def test_scenario_rejects_bad_metrics_window():
    with pytest.raises(ValueError, match="metrics_window_s"):
        Scenario(name="bad", metrics_window_s=0.0)
