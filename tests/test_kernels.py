"""Pallas kernels vs their references: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

#: (B, H, W, threshold): lane-aligned, ragged and odd frames at three
#: thresholds, then sparse-mask frames at the default threshold
_CASCADE_CASES = [
    (B, H, W, t) for B, H, W in [(1, 32, 128), (2, 50, 200), (3, 96, 128),
                                 (1, 33, 129), (2, 64, 256)]
    for t in (10, 40, 128)
] + [(B, H, W, 40) for B, H, W in [(1, 32, 64), (2, 50, 100), (1, 96, 128),
                                   (2, 33, 65)]]


@pytest.mark.parametrize("B,H,W,threshold", _CASCADE_CASES)
def test_pixel_cascade_matches_np(B, H, W, threshold):
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    f = [jax.random.randint(k, (B, H, W, 3), 0, 256) for k in keys]
    mask, counts = ops.pixel_cascade(*f, threshold=threshold)
    want_mask, want_counts = ref.pixel_cascade_np(*f, threshold)
    assert mask.shape == (B, H, W)
    np.testing.assert_array_equal(np.asarray(mask), want_mask)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)


def test_dilate_then_erode_is_closing():
    """Morphological closing fills single-pixel holes and keeps blobs."""
    x = np.zeros((1, 32, 32), np.int32)
    x[0, 10:20, 10:20] = 255
    x[0, 14, 14] = 0                      # hole
    y = ref.erode3x3_ref(ref.dilate3x3_ref(jnp.asarray(x)))
    y = np.asarray(y)
    assert y[0, 14, 14] == 255            # hole filled
    assert y[0, 0, 0] == 0                # background untouched


def _triage_row(conf, alpha, beta, capacity):
    """One edge's batch through the fleet kernel as a (1, N) matrix."""
    routes, slots, counts = ops.triage_fleet(
        np.asarray(conf, np.float32)[None], [[alpha, beta]],
        capacity=capacity)
    return routes[0], slots[0], counts[0]


@pytest.mark.parametrize("N", [8, 100, 1000, 4096])
@pytest.mark.parametrize("alpha,beta", [(0.8, 0.1), (0.55, 0.3), (1.0, 0.0)])
def test_triage_matches_ref(N, alpha, beta):
    conf = jax.random.uniform(jax.random.PRNGKey(N), (N,))
    cap = max(N // 4, 4)
    got = _triage_row(conf, alpha, beta, cap)
    want = ref.triage_ref(conf, alpha, beta, cap)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_triage_compaction_is_stable_and_dense():
    conf = [0.9, 0.5, 0.2, 0.05, 0.5, 0.6]
    routes, slots, count = _triage_row(conf, 0.8, 0.1, 8)
    # escalated = indices 1,2,4,5 -> slots 0,1,2,3 in order
    assert int(count) == 4
    np.testing.assert_array_equal(np.asarray(slots), [-1, 0, 1, -1, 2, 3])
    np.testing.assert_array_equal(np.asarray(routes), [0, 2, 2, 1, 2, 2])


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int32, jnp.int16])
def test_framediff_input_dtypes(dtype):
    B, H, W = 1, 32, 128
    f = [jax.random.randint(jax.random.PRNGKey(i), (B, H, W, 3), 0, 255
                            ).astype(dtype) for i in range(3)]
    mask, counts = ops.pixel_cascade(*f, threshold=30)
    want_mask, want_counts = ref.pixel_cascade_np(*f, 30)
    np.testing.assert_array_equal(np.asarray(mask), want_mask)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
