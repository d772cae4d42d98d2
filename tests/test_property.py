"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis-heavy: runs hundreds of examples per property, so the module
# lives in the slow tier (`make test-slow` / the non-blocking CI job)
pytestmark = pytest.mark.slow

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r "
           "requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import cascade as C
from repro.core import latency as LT
from repro.core.thresholds import ThresholdState
from repro.kernels import ref

fin = dict(allow_nan=False, allow_infinity=False)


@given(st.floats(1e-3, 1e3, **fin), st.floats(1e-3, 1e3, **fin))
def test_adaptive_mean_convex(a, b):
    m = LT.adaptive_mean(a, b)
    assert min(a, b) - 1e-9 <= m <= max(a, b) + 1e-9


@given(st.lists(st.tuples(st.integers(0, 100), st.floats(0, 10, **fin)),
                min_size=1, max_size=60))
def test_threshold_invariants_under_any_load_sequence(seq):
    th = ThresholdState()
    for q, t in seq:
        th = th.update(q, t, 1.0)
        assert 0.5 <= th.alpha <= 1.0
        assert 0.0 <= th.beta < 0.5
        # triage is total: every confidence maps to exactly one region
        for c in (0.0, th.beta, (th.alpha + th.beta) / 2, th.alpha, 1.0):
            assert th.triage(c) in ("accept", "reject", "escalate")


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 300), st.floats(0, 1, **fin), st.floats(0, 1, **fin),
       st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_triage_compaction_properties(n, a, b, cap, seed):
    alpha, beta = max(a, b), min(a, b)
    conf = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    routes, slots, count = ref.triage_ref(conf, alpha, beta, cap)
    routes, slots = np.asarray(routes), np.asarray(slots)
    esc_idx = np.flatnonzero(routes == 2)
    # count is exact
    assert int(count) == len(esc_idx)
    # slots are a stable, dense prefix of [0, cap)
    got = slots[slots >= 0]
    assert list(got) == list(range(min(len(esc_idx), cap)))
    # non-escalated items never get a slot
    assert np.all(slots[routes != 2] == -1)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2), st.integers(8, 48), st.integers(8, 48),
       st.integers(0, 2 ** 31 - 1))
def test_morphology_order_properties(b, h, w, seed):
    x = (jax.random.uniform(jax.random.PRNGKey(seed), (b, h, w)) > 0.6
         ).astype(jnp.int32) * 255
    d = ref.dilate3x3_ref(x)
    e = ref.erode3x3_ref(x)
    # extensivity / anti-extensivity
    assert bool(jnp.all(d >= x))
    assert bool(jnp.all(e <= x))
    # duality on binary masks: erode(x) == 255 - dilate(255 - x)
    dual = 255 - np.asarray(ref.dilate3x3_ref(255 - x))
    np.testing.assert_array_equal(np.asarray(e), dual)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 250))
def test_framediff_static_scene_is_silent(seed, thresh):
    """No motion => empty mask regardless of threshold (property: the
    detector never hallucinates on identical frames)."""
    f = jax.random.randint(jax.random.PRNGKey(seed), (1, 32, 128, 3), 0, 256)
    mask = ref.framediff_ref(f, f, f, thresh)
    assert int(jnp.sum(mask)) == 0


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 40), st.integers(0, 2 ** 31 - 1))
def test_compact_escalated_is_injective(n, seed):
    conf = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    routes = C.triage(conf, jnp.float32(0.7), jnp.float32(0.2))
    idx, valid, cnt = C.compact_escalated(routes, capacity=n)
    idx, valid = np.asarray(idx), np.asarray(valid)
    taken = idx[valid]
    assert len(np.unique(taken)) == len(taken)          # no duplicates
    assert all(routes[i] == C.ESCALATE for i in taken)  # only escalated
