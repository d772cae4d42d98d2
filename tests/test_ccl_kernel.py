"""The VMEM-resident label fixpoint (``kernels/ccl.py``) against the jnp one.

``ops.label_components`` labels each camera in one Pallas launch; its
labels must equal the jnp fixpoint's (``use_pallas=False``) bit for bit,
and each camera's step count must equal the one the jnp loop counts for
that camera alone.  The masks cover the edges a lane or sublane roll
could wrap across, the ``max_iters`` cap and a long serpentine.  The
kernel is interpreted here (CPU); ``tests/test_tpu_compile.py`` compiles
it for a v5e.  Also here: the counters ``detect`` keeps from the steps,
and the benchmark's reader of them.
"""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest
from scipy import ndimage

from repro.detection import pipeline as DP
from repro.kernels import ccl, ops
from repro.system.pixel_frontend import COUNTERS

SHAPES = [(3, 64, 96), (2, 96, 128), (3, 112, 300)]


def _blobs(rng, H, W, n=14):
    m = np.zeros((H, W), np.int32)
    for _ in range(n):
        y, x = rng.integers(0, H - 4), rng.integers(0, W - 4)
        h, w = rng.integers(1, 12, 2)
        m[y:y + h, x:x + w] = 255
    return m


def _edges(rng, H, W):
    """Components on every edge row and column, and in the corners."""
    m = np.zeros((H, W), np.int32)
    m[0, 5:20] = m[H - 1, 30:50] = 255
    m[10:20, 0] = m[25:40, W - 1] = 255
    m[0, 0] = m[0, W - 1] = m[H - 1, 0] = m[H - 1, W - 1] = 255
    return m


def _wrap(rng, H, W):
    """Pairs that touch only through a wrap-around: column 0 and column
    W-1 on the same rows, row 0 and row H-1 in the same columns."""
    m = np.zeros((H, W), np.int32)
    m[20:30, 0] = m[20:30, W - 1] = 255
    m[0, 40:60] = m[H - 1, 40:60] = 255
    return m


def _full(rng, H, W):
    """One component over the whole frame: max(H, W) - 1 steps, past the
    ``max_iters`` cap on the widest frame."""
    return np.full((H, W), 255, np.int32)


def _serpentine(rng, H, W, length=200):
    """One snake of about ``length`` pixels from the top-left corner, so
    the min label has to walk all of it."""
    m = np.zeros((H, W), np.int32)
    run = min(W - 2, 40)
    y, left, walked = 0, True, 0
    while walked < length and y < H:
        n = min(run, length - walked)
        cols = slice(0, n) if left else slice(run - n, run)
        m[y, cols] = 255
        walked += n
        if walked < length and y + 2 < H:
            m[y + 1, run - 1 if left else 0] = 255
            walked += 1
        y, left = y + 2, not left
    return m


KINDS = {"blobs": _blobs, "edges": _edges, "wrap": _wrap, "full": _full,
         "serpentine": _serpentine}


def _fleet(kind, shape, seed=0):
    """Camera 0 is empty and the rest hold ``kind``: an empty camera
    beside busy ones in every case."""
    rng = np.random.default_rng(seed)
    m = np.zeros(shape, np.int32)
    for b in range(1, shape[0]):
        m[b] = KINDS[kind](rng, *shape[1:])
    return m


def _scipy_labels(m):
    """Independent labels: 8-connected components, each named by its
    smallest linear index."""
    lab, n = ndimage.label(m > 0, structure=np.ones((3, 3)))
    out = np.full(m.shape, -1, np.int64)
    lin = np.arange(m.size).reshape(m.shape)
    for k in range(1, n + 1):
        sel = lab == k
        out[sel] = lin[sel].min()
    return out


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_kernel_labels_and_steps_match_jnp(kind, shape):
    m = _fleet(kind, shape)
    B, H, W = shape
    assert ccl.fits(H, W)
    lab_k, steps_k = (np.asarray(a) for a in ops.label_components(
        jnp.asarray(m)))
    lab_j, steps_j = (np.asarray(a) for a in ops.label_components(
        jnp.asarray(m), use_pallas=False))
    np.testing.assert_array_equal(lab_k, lab_j)
    np.testing.assert_array_equal(steps_k, steps_j)
    for b in range(B):
        lab_1, steps_1 = ccl.label_components(jnp.asarray(m[b:b + 1]))
        assert int(steps_1[0]) == int(steps_k[b])
        np.testing.assert_array_equal(np.asarray(lab_1[0]), lab_k[b])
        if int(steps_k[b]) < 256:            # converged: the true labels
            np.testing.assert_array_equal(lab_k[b], _scipy_labels(m[b]))
    assert int(steps_k[0]) == ccl.STEPS       # empty: one check block
    if kind == "wrap":
        for b in range(1, B):
            assert len(np.unique(lab_k[b][lab_k[b] >= 0])) == 4
    if kind == "serpentine":
        assert 180 <= int(steps_k[1]) < 256
    if kind == "full":
        assert int(steps_k[1]) == min(
            256, ccl.STEPS * -(-(max(H, W) - 1) // ccl.STEPS) + ccl.STEPS)


def test_kernel_stops_at_the_cap_like_the_jnp_loop():
    """A fixpoint longer than ``max_iters`` stops at it in both, with the
    same partial labels."""
    m = jnp.asarray(_fleet("full", (2, 40, 300)))
    lab_k, steps_k = ccl.label_components_pallas(m, max_iters=24)
    lab_j, steps_j = ccl.label_components(m, max_iters=24)
    np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_j))
    assert np.asarray(steps_k).tolist() == np.asarray(steps_j).tolist() \
        == [ccl.STEPS, 24]


def test_plane_shape_leaves_a_background_column_and_whole_strips():
    for h, w in [(540, 960), (1080, 1920), (96, 128), (61, 133)]:
        hp, wp, strip = ccl.plane_shape(h, w)
        assert wp % 128 == 0 and wp > w
        assert strip % 8 == 0 and strip <= ccl.MAX_STRIP and hp % strip == 0
        assert h <= hp < h + 8 * (hp // strip)


def test_kernel_or_jnp_by_frame_shape(monkeypatch):
    """The cell's frame sizes run in the kernel; a frame whose resident
    planes would overflow the VMEM budget keeps the jnp fixpoint, with no
    Pallas launch."""
    assert ccl.fits(540, 960)
    assert ccl.fits(1080, 1920)
    assert not ccl.fits(2048, 2048)
    assert ccl.vmem_bytes(1080, 1920) <= ccl.VMEM_BUDGET \
        < ccl.vmem_bytes(2048, 2048)

    launches = []
    real = ccl.pl.pallas_call
    monkeypatch.setattr(ccl.pl, "pallas_call",
                        lambda *a, **kw: launches.append(1) or real(*a, **kw))
    m = jnp.asarray(_fleet("blobs", (2, 41, 53)))     # never traced before
    monkeypatch.setattr(ccl, "VMEM_BUDGET", 0)
    lab, steps = ops.label_components(m)
    assert not launches
    want = ccl.label_components(m)
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(steps), np.asarray(want[1]))


# --- counters and their reader -------------------------------------------------


def _moving(seed, B=2, H=64, W=96):
    rng = np.random.default_rng(seed)
    frames = np.full((B, 3, H, W, 3), 55, np.int32)
    for b in range(B):
        for t in range(3):
            y, x = 8 + 6 * t, 10 + 8 * t + 20 * b
            frames[b, t, y:y + 8, x:x + 8] = rng.integers(180, 255)
    return frames


def test_detect_counts_ccl_steps_only_on_ticks_that_label():
    counters = {}
    DP.detect(np.full((2, 3, 64, 96, 3), 55, np.int32), counters=counters)
    assert counters.get("ccl_steps", 0) == counters.get("ccl_cameras", 0) \
        == 0
    assert counters["motionless_camera_ticks"] == 2

    frames = _moving(1)
    DP.detect(frames, counters=counters)
    mask, _ = ops.pixel_cascade(*(jnp.asarray(frames[:, i])
                                  for i in range(3)))
    _, steps = ops.label_components(mask)
    assert counters["ccl_cameras"] == 2
    assert counters["ccl_steps"] == int(np.asarray(steps).sum()) > 0


def test_pixel_summary_carries_the_ccl_counters():
    from repro.system import run_query
    from repro.system.pixel_frontend import PixelFrontend
    from repro.system.scenario import pixel_city

    assert {"ccl_steps", "ccl_cameras"} <= set(COUNTERS)
    sc = pixel_city(num_cameras=2, num_edges=1, duration_s=1.0, seed=4)
    row = run_query(sc, frontend=PixelFrontend(seed=4)).summary()
    labelled = row["ccl_cameras"]
    assert labelled % 2 == 0 and labelled > 0
    assert row["ccl_steps"] >= ccl.STEPS * labelled


def _reader():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", "metrics",
        "ccl_steps_per_camera.py")
    spec = importlib.util.spec_from_file_location("ccl_steps_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_ccl_steps_reader():
    read = _reader()
    calls = [{"report": {"ccl_steps": 96, "ccl_cameras": 4}},
             {"report": {"ccl_steps": 40, "ccl_cameras": 2}}]
    assert read({"calls": calls}) == pytest.approx(136 / 6)
    # a program without the counters, or with no tick labelled, reads none
    assert read({"calls": [{"report": {"crops_scored": 3}}]}) is None
    assert read({"calls": [{"report": {"ccl_steps": 0,
                                       "ccl_cameras": 0}}]}) is None
