"""The device-resident pixel tick at a small size, against its references.

Four cameras at 64x96 with seeded weights: the device painting against
``render_triple``'s NumPy painting, ``detect`` on device frames against
``kernels/ref.py`` and the chip benchmark's plain reference, the CQ
classifier at the highest matmul precision against a float64 forward,
compilation of a second tick, and the box table's overflow count.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic_video as SV
from repro.detection import pipeline as DP
from repro.kernels import ccl, ops
from repro.kernels import ref as KR
from repro.system import PixelFrontend, pixel_city, run_query

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")


def _bench(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(BENCH, "chipbench", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _bench("reference")
WT = _bench("weights")


def _fleet(n=4, h=64, w=96, rate=6.0, seed=3):
    return [dataclasses.replace(c, height=h, width=w, base_rate=rate,
                                busy_boost=0.0)
            for c in SV.make_cameras(n, seed=seed)]


def _tick(cams, seed=0, noise_seed=5):
    rng = np.random.default_rng(seed)
    truths = [SV.draw_scene(c, 0.0, rng) for c in cams]
    table, counts = SV.scene_table(truths, 16)
    bg = SV.backgrounds(cams)
    frames = SV.paint_fleet(bg, table, counts, SV.TEXTURES,
                            np.uint32(noise_seed))
    return truths, (bg, table, counts), frames


# --- camera source --------------------------------------------------------------


def test_device_painting_matches_render_triple():
    """Noiseless painting bit for bit; the noise by its mean and deviation
    against render_triple's on the same scene."""
    cams = _fleet()
    truths, (bg, table, counts), frames = _tick(cams)
    assert counts.sum() > 8
    clean = np.stack([SV.paint_triple(c, t) for c, t in zip(cams, truths)])
    got = np.asarray(jax.jit(SV.paint_scenes)(bg, table, counts,
                                              SV.TEXTURES))
    np.testing.assert_array_equal(got, clean)
    # render_triple: the same draws, then NumPy noise
    rng = np.random.default_rng(0)
    ref = []
    for c, t in zip(cams, truths):
        f, truth = SV.render_triple(c, 0.0, np.random.default_rng(
            rng.integers(1 << 30)))
        ref.append(f.astype(np.int64) - SV.paint_triple(c, truth))
    dev = np.asarray(frames).astype(np.int64) - clean
    ref = np.concatenate([r.ravel() for r in ref])
    assert abs(dev.mean() - ref.mean()) < 0.02
    assert abs(dev.std() - ref.std()) < 0.02
    assert np.abs(dev).max() < 16


def test_scene_draws_match_render_triple():
    """draw_scene consumes the generator as render_triple always has: the
    same objects, and the noise drawn after them."""
    cam = _fleet(1)[0]
    a, b = np.random.default_rng(7), np.random.default_rng(7)
    truth = SV.draw_scene(cam, 0.0, a)
    frames, t2 = SV.render_triple(cam, 0.0, b)
    assert (truth.classes, truth.boxes, truth.velocities) == \
        (t2.classes, t2.boxes, t2.velocities)
    noise = a.normal(0, 2.0, frames.shape)
    want = np.clip(SV.paint_triple(cam, truth) + noise, 0, 255)
    np.testing.assert_array_equal(frames, want.astype(np.uint8))


# --- detection ------------------------------------------------------------------


def test_detect_on_device_frames_matches_both_references():
    cams = _fleet()
    _, _, frames = _tick(cams, seed=1)
    fr = np.asarray(frames)
    vocab = 4096
    counters = {}
    dets = DP.detect(frames, threshold=40, crop=32, min_area=12,
                     vocab_size=vocab, counters=counters)
    mask, counts = ops.pixel_cascade(*(frames[:, i] for i in range(3)),
                                     threshold=40)
    want, want_counts = KR.pixel_cascade_np(fr[:, 0], fr[:, 1], fr[:, 2],
                                            40)
    np.testing.assert_array_equal(np.asarray(mask), want)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    m_ref, c_ref = R.pixel_cascade(fr[:, 0], fr[:, 1], fr[:, 2], 40)
    np.testing.assert_array_equal(np.asarray(mask), m_ref)
    np.testing.assert_array_equal(np.asarray(counts), c_ref)
    _, steps = ops.label_components(mask)
    assert counters == {"motionless_camera_ticks": 0, "boxes_dropped": 0,
                        "ccl_cameras": len(cams),
                        "ccl_steps": int(np.asarray(steps).sum())}
    n = 0
    for b, per in enumerate(dets):
        ref_boxes = R.boxes(m_ref[b], 12)
        assert [(d.box.y0, d.box.x0, d.box.y1, d.box.x1, d.box.area)
                for d in per] == ref_boxes
        for d, box in zip(per, ref_boxes):
            crop = R.crop(fr[b, 1], box, 32)
            np.testing.assert_array_equal(d.crop, crop)
            np.testing.assert_array_equal(
                d.tokens, R.crop_tokens(crop[None], vocab)[0])
            n += 1
    assert n > 10


def test_device_tokens_match_float64_or_flag_a_tie():
    """The device tokenizer agrees with the float64 one on every crop it
    does not flag as a near tie."""
    rng = np.random.default_rng(4)
    crops = np.stack([SV.object_crop(c % SV.NUM_CLASSES, rng)
                      for c in range(600)])
    crops[0] = 77                        # a flat crop: every sign is a tie
    want = SV.crops_to_tokens(crops, 4096)
    got, tie = (np.asarray(a) for a in jax.jit(functools.partial(
        SV.crops_to_tokens_device, vocab_size=4096))(crops))
    assert tie[0]
    assert ((got == want).all(axis=1) | tie).all()
    assert 0 < tie.mean() < 0.1


def test_boxes_dropped_counts_a_forced_overflow(monkeypatch):
    """A table of two boxes a camera keeps the first two components in
    raster order and counts the rest as dropped; the benchmark's check
    would read them as wrong boxes."""
    m = np.zeros((1, 3, 48, 64, 3), np.uint8)
    m[0, 1, 4:10, 4:10] = 200          # three blobs that differ in the
    m[0, 1, 20:26, 30:36] = 200        # middle frame only
    m[0, 1, 36:42, 10:16] = 200
    monkeypatch.setattr(DP, "MAX_BOXES", 2)
    counters = {}
    dets = DP.detect(m, threshold=40, crop=16, min_area=12,
                     counters=counters)
    mask, _ = R.pixel_cascade(m[:, 0], m[:, 1], m[:, 2], 40)
    ref = R.boxes(mask[0], 12)
    assert len(ref) == 3
    assert counters["boxes_dropped"] == 1
    assert [(d.box.y0, d.box.x0, d.box.y1, d.box.x1, d.box.area)
            for d in dets[0]] == ref[:2]


@pytest.mark.parametrize("big", [False, True])
def test_box_table_matches_reference_in_window_and_whole_frame(big):
    """Boxes measured in each root's window, and over the whole frame once
    a component spills past its window, equal the reference's."""
    from repro.detection import components
    rng = np.random.default_rng(8)
    m = np.zeros((3, 112, 300), np.int32)
    for b in range(3):
        for _ in range(14):
            y, x = rng.integers(0, 100), rng.integers(0, 288)
            h, w = rng.integers(1, 12, 2)
            m[b, y:y + h, x:x + w] = 255
    if big:
        m[1, 10:90, 100:120] = 255       # taller than the window
        m[2, 50:54, 20:240] = 255        # wider than the window
    lab, _ = ccl.label_components(jnp.asarray(m))
    boxes, keep, comps = (np.asarray(a) for a in jax.jit(
        functools.partial(components.box_table, max_boxes=32,
                          min_area=12))(lab))
    for b in range(3):
        got = [tuple(int(v) for v in boxes[b, k])
               for k in np.flatnonzero(keep[b])]
        assert got == R.boxes(m[b], 12)
    if big:
        assert any(r[2] - r[0] >= components.WINDOW_H
                   for r in R.boxes(m[1], 12))


# --- CQ classifier ----------------------------------------------------------------


def _spec():
    import json
    with open(os.path.join(BENCH, "configs", "ua_detrac_960p.json")) as fh:
        return json.load(fh)["classifier"]


def test_score_crops_highest_precision_matches_float64():
    spec = _spec()
    weights = WT.make(spec, 11)
    fe = PixelFrontend(params=weights, cache=False)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, spec["vocab_size"], (37, spec["tokens"]))
    score = functools.partial(fe._conf_fn, fe.params)
    got = ops.score_crops(score, tokens)
    host = jax.tree.map(np.asarray, weights)
    want = R.classifier(spec, host, tokens)
    bf16 = R.classifier(spec, host, tokens, control=True)
    assert np.max(np.abs(got - want)) < 1e-5
    assert np.max(np.abs(bf16 - want)) > 1e-5
    # every matmul of the classifier asks for the highest precision (a
    # CPU computes float32 either way; a TPU's default is one bf16 pass)
    text = str(jax.make_jaxpr(fe._conf_fn)(fe.params, jnp.asarray(tokens)))
    assert text.count("dot_general[") == text.count(
        "precision=(Precision.HIGHEST, Precision.HIGHEST)") > 0


# --- compilation ------------------------------------------------------------------


@pytest.fixture
def compiles():
    names = []

    def on(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(on)
    yield names
    jax.monitoring.unregister_event_duration_listener(on)


def test_second_tick_with_the_same_buckets_compiles_nothing(compiles):
    """Painting, detection, scoring and the per-tick triage compile once:
    a second call on the same buckets compiles nothing."""
    sc = pixel_city(num_cameras=4, num_edges=4, duration_s=0.2,
                    interval_s=0.1, frame_hw=(64, 96), seed=4)
    fe = PixelFrontend(seed=0, cache=False)
    run_query(sc, frontend=fe)
    jax.jit(lambda x: x + 1)(np.zeros(3))     # the listener hears compiles
    assert compiles
    del compiles[:]
    rep = run_query(sc, frontend=fe)
    assert compiles == []
    assert rep.n_items > 0 and rep.frontend_counters["crops_scored"] > 0


def test_triage_fleet_second_shape_in_a_bucket_compiles_nothing(compiles):
    rng = np.random.default_rng(0)
    for n in (9, 13):
        conf = rng.uniform(size=(2, 5, n)).astype(np.float32)
        th = np.tile(np.asarray([0.8, 0.2], np.float32), (2, 5, 1))
        if n == 13:
            del compiles[:]
        routes, slots, counts = ops.triage_fleet(conf, th, capacity=4)
        assert routes.shape == (2, 5, n) and counts.shape == (2, 5)
    assert compiles == []
