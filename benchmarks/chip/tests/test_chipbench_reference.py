"""The chip benchmark's plain reference agrees with the program at small
sizes on the CPU, and its bfloat16 control does not."""
import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from chipbench import cells as CL  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench import weights as WT  # noqa: E402


def _frames(rng, b=3, h=48, w=64, objects=4):
    f = np.repeat(rng.integers(60, 120, (b, 1, h, w, 3)), 3, axis=1)
    for cam in range(b):
        for _ in range(objects):
            y, x = rng.integers(0, h - 12), rng.integers(4, w - 16)
            for k in range(3):
                f[cam, k, y:y + 8, x + 3 * k:x + 3 * k + 8] = \
                    rng.integers(150, 255, 3)
    return np.clip(f + rng.normal(0, 2, f.shape), 0, 255).astype(np.uint8)


def test_pixel_cascade_boxes_and_tokens_match_the_program():
    from repro.data import synthetic_video as SV
    from repro.detection import pipeline as DP
    from repro.kernels import ops
    fr = _frames(np.random.default_rng(0))
    mask, counts = ops.pixel_cascade(fr[:, 0], fr[:, 1], fr[:, 2],
                                     threshold=40)
    m_ref, c_ref = R.pixel_cascade(fr[:, 0], fr[:, 1], fr[:, 2], 40)
    np.testing.assert_array_equal(np.asarray(mask), m_ref)
    np.testing.assert_array_equal(np.asarray(counts), c_ref)
    assert c_ref.sum() > 0
    dets = DP.detect(fr, threshold=40, crop=16, min_area=12)
    crops = []
    for b, per in enumerate(dets):
        ref = R.boxes(m_ref[b], 12)
        assert [(d.box.y0, d.box.x0, d.box.y1, d.box.x1, d.box.area)
                for d in per] == ref
        for d, box in zip(per, ref):
            np.testing.assert_array_equal(d.crop, R.crop(fr[b, 1], box, 16))
            crops.append(d.crop)
    crops = np.stack(crops)
    np.testing.assert_array_equal(R.crop_tokens(crops, 4096),
                                  SV.crops_to_tokens(crops, 4096))


def _classifier():
    from repro.system.pixel_frontend import _conf_apply
    from repro.configs import get_config
    spec = CL.load_config("ua_detrac_24cam")["classifier"]
    full = get_config(spec["arch"])
    cfg = dataclasses.replace(full.edge_variant(), num_query_classes=2,
                              vocab_size=full.vocab_size)
    return spec, cfg, functools.partial(_conf_apply, cfg)


def test_weights_have_the_programs_layout():
    import jax
    from repro.models import meta as M
    spec, cfg, _ = _classifier()
    got = jax.eval_shape(lambda: WT.make(spec, 1))
    want = M.abstract_params(cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(got)] == \
        [b.shape for b in jax.tree.leaves(want)]


def test_classifier_reference_matches_and_control_does_not():
    import jax
    spec, cfg, apply = _classifier()
    w = WT.make(spec, 2**31 + 5)
    tokens = np.random.default_rng(1).integers(0, 4096, (24, 16),
                                               dtype=np.int32)
    got = np.asarray(apply(w, tokens), np.float64)
    hw = jax.tree.map(np.asarray, w)
    ref = R.classifier(spec, hw, tokens)
    ctl = R.classifier(spec, hw, tokens, control=True)
    assert np.max(np.abs(got - ref)) < 1e-5
    assert np.max(np.abs(ctl - ref)) > 1e-3
    assert 0.05 < np.std(ref)


def test_triage_matches_the_programs_kernel():
    from repro.kernels import ops
    rng = np.random.default_rng(2)
    conf = rng.uniform(-0.2, 1.0, (5, 24)).astype(np.float32)
    th = np.stack([rng.uniform(0.5, 1.0, 5), rng.uniform(0.0, 0.5, 5)],
                  -1).astype(np.float32)
    routes, slots, _ = ops.triage_fleet(conf, th, capacity=3)
    r, s = R.triage(conf, th, 3)
    np.testing.assert_array_equal(np.asarray(routes), r)
    np.testing.assert_array_equal(np.asarray(slots), s)
    assert (s >= 0).sum() > 0 and ((r == 2) & (s < 0)).sum() > 0


@pytest.mark.parametrize("control", [False, True])
def test_threshold_scan_against_the_programs_superstep(control):
    from repro.system import superstep
    rng = np.random.default_rng(3)
    S, Rr, N = 8, 16, 8
    conf = rng.uniform(0, 1, (S, Rr, N)).astype(np.float32)
    th0 = np.stack([rng.uniform(0.6, 1.0, Rr),
                    rng.uniform(0.0, 0.2, Rr)], -1).astype(np.float32)
    mask = rng.uniform(size=(S, Rr)) < 0.7
    drain = rng.uniform(0.0, 0.3, Rr).astype(np.float32)
    gains = np.asarray([0.1, 0.005, 0.25, 0.1], np.float32)
    routes, slots, ths = (np.asarray(a) for a in superstep._superstep_fn(
        4, 1)(conf, th0, mask, drain, gains))
    gap = np.max(np.abs(ths - R.threshold_scan(th0, mask, drain, gains,
                                               control=control)))
    if control:
        assert gap > 1e-3
    else:
        assert gap < 1e-6
