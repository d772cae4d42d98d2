"""Frame-difference detection pipeline: planted objects are found."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.data import synthetic_video as SV
from repro.detection import components, pipeline
from repro.kernels import ccl


def test_motion_mask_finds_moving_object():
    cam = SV.make_cameras(1, seed=3)[0]
    rng = np.random.default_rng(0)
    # force exactly one object
    cam.class_mix = np.eye(SV.NUM_CLASSES)[1]
    cam.base_rate, cam.busy_boost = 1.0, 0.0
    for _ in range(5):
        frames, truth = SV.render_triple(cam, 0.0, rng)
        if len(truth.classes) == 1:
            break
    assert len(truth.classes) >= 1
    mask = pipeline.motion_mask(*(jnp.asarray(frames[i][None]) for i in range(3)))
    assert int((np.asarray(mask) > 0).sum()) > 20   # something moved


def test_label_components_two_blobs():
    m = np.zeros((1, 40, 40), np.int32)
    m[0, 2:8, 2:8] = 255
    m[0, 20:30, 25:35] = 255
    lab = np.asarray(ccl.label_components(jnp.asarray(m))[0])
    fg = lab[0][lab[0] >= 0]
    assert len(np.unique(fg)) == 2


def test_extract_boxes_filters_small_and_elongated():
    """The device box table keeps the big blob and drops a single pixel
    (min_area) and a 1x28 line (aspect), in raster order of the blobs."""
    m = np.zeros((1, 40, 40), np.int32)
    m[0, 5:20, 5:20] = 255      # big blob -> kept
    m[0, 30, 30] = 255          # single pixel -> dropped (min_area)
    m[0, 35, 2:30] = 255        # 1x28 line -> dropped (aspect)
    lab, _ = ccl.label_components(jnp.asarray(m))
    boxes, keep, comps = (np.asarray(a) for a in components.box_table(
        lab, 8, min_area=12, max_aspect=6.0))
    assert comps.tolist() == [3]
    assert keep[0].tolist() == [True, False, False] + [False] * 5
    assert boxes[0, 0].tolist() == [5, 5, 19, 19, 225]


def test_detect_end_to_end_crop_shapes():
    cam = SV.make_cameras(1, seed=5)[0]
    cam.base_rate, cam.busy_boost = 2.0, 0.0
    rng = np.random.default_rng(1)
    frames, truth = SV.render_triple(cam, 0.0, rng)
    dets = pipeline.detect(frames, crop=32)
    for d in dets[0]:
        assert d.crop.shape == (32, 32, 3)


def test_detection_recall_on_planted_objects():
    """Most planted sprites should produce a detection (recall-oriented,
    as the paper emphasizes)."""
    cam = SV.make_cameras(1, seed=7)[0]
    cam.base_rate, cam.busy_boost = 1.5, 0.0
    rng = np.random.default_rng(2)
    found, total = 0, 0
    for _ in range(8):
        frames, truth = SV.render_triple(cam, 0.0, rng)
        dets = pipeline.detect(frames)[0]
        total += len(truth.classes)
        for (y, x) in truth.boxes:
            hit = any(abs((d.box.y0 + d.box.y1) / 2 - (y + SV.SPRITE / 2)) < 16
                      and abs((d.box.x0 + d.box.x1) / 2 - (x + SV.SPRITE / 2)) < 16
                      for d in dets)
            found += bool(hit)
    if total == 0:
        pytest.skip("no objects sampled")
    assert found / total > 0.6, (found, total)
