"""Fused pixel-cascade kernel: bit-exactness, launch budget, planar layout.

The fused kernel's contract is strict equality: kernel == the jnp
reference (``use_pallas=False``) == the independent NumPy oracle, over
every frame size / threshold / bucket-padding placement.  On top of that, the
launch-budget acceptance — a pixel_city tick's whole framediff ->
morphology -> score chain in <= 2 Pallas launches — is asserted with a
monkeypatched launch counter.  The kernels are interpreted here (CPU);
``tests/test_tpu_compile.py`` compiles the same launcher for a v5e.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pallas_mod

from repro.data import synthetic_video as SV
from repro.detection import pipeline as DP
from repro.kernels import ops, ref
from repro.kernels import pixel_cascade as PC
from repro.kernels.buckets import (MAX_FRAME_ELEMS, MIN_FRAME_SIDE,
                                   validate_frame_hw)
from repro.system.scenario import Scenario, pixel_city


def _frames(rng, B, H, W):
    return rng.integers(0, 256, (3, B, H, W, 3)).astype(np.int32)


def _assert_cascade_exact(fs, threshold=40):
    f0, f1, f2 = (jnp.asarray(fs[i]) for i in range(3))
    mask_f, cnt_f = ops.pixel_cascade(f0, f1, f2, threshold=threshold)
    mask_r, cnt_r = ops.pixel_cascade(f0, f1, f2, threshold=threshold,
                                      use_pallas=False)
    mask_np, cnt_np = ref.pixel_cascade_np(fs[0], fs[1], fs[2], threshold)
    np.testing.assert_array_equal(np.asarray(mask_f), np.asarray(mask_r))
    np.testing.assert_array_equal(np.asarray(mask_f), mask_np)
    np.testing.assert_array_equal(np.asarray(cnt_f), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(cnt_f), cnt_np)


# --- bit-exactness: kernel == jnp reference == independent NumPy oracle ------


def test_fused_matches_staged_and_oracle_fixed_shapes():
    """Default camera frame, band-exact, sub-band, and non-lane widths."""
    rng = np.random.default_rng(0)
    for (B, H, W) in [(2, 96, 128), (1, 32, 128), (1, 33, 40),
                      (3, 16, 300), (2, 100, 96), (1, 64, 129)]:
        _assert_cascade_exact(_frames(rng, B, H, W))


def test_fused_seeded_shape_sweep():
    """Seeded sweep over bucket-padding placements: H straddling band
    multiples, W straddling lane multiples, thresholds across the range.
    Always runs (no hypothesis dependency)."""
    rng = np.random.default_rng(7)
    for _ in range(12):
        H = int(rng.integers(16, 140))
        W = int(rng.integers(16, 280))
        B = int(rng.integers(1, 4))
        thr = int(rng.integers(0, 250))
        _assert_cascade_exact(_frames(rng, B, H, W), threshold=thr)


def test_fused_property_hypothesis():
    """Hypothesis property over random frame sizes, thresholds, and
    padding placements (skips where hypothesis isn't installed — the
    seeded sweep above keeps the coverage)."""
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(16, 130), st.integers(16, 260),
           st.integers(1, 3), st.integers(0, 254), st.integers(0, 2**31 - 1))
    def prop(H, W, B, thr, seed):
        rng = np.random.default_rng(seed)
        _assert_cascade_exact(_frames(rng, B, H, W), threshold=thr)

    prop()


def test_sparse_motion_counts():
    """Counts equal the true foreground population on a nearly-static
    scene (one moving block), including a camera with zero motion."""
    B, H, W = 2, 96, 128
    base = np.full((B, H, W, 3), 30, np.int32)
    f0, f1, f2 = base.copy(), base.copy(), base.copy()
    # camera 0: a block whose framediff survives the AND of both diffs
    f1[0, 40:56, 60:76] = 200
    mask_f, cnt_f = ops.pixel_cascade(*(jnp.asarray(x)
                                        for x in (f0, f1, f2)))
    mask_np, cnt_np = ref.pixel_cascade_np(f0, f1, f2, 40)
    np.testing.assert_array_equal(np.asarray(mask_f), mask_np)
    np.testing.assert_array_equal(np.asarray(cnt_f), cnt_np)
    assert int(cnt_f[1]) == 0


# --- planar frame layout -----------------------------------------------------


def test_planar_layout_matches_oracle():
    """The launcher reads planar (B, 3, H', W') frames: channel c of pixel
    (y, x) sits at [b, c, y, x], the pad is zero, and the launch on that
    layout is bit-exact against the NumPy oracle, counts included."""
    rng = np.random.default_rng(3)
    B, H, W = 2, 70, 150
    fs = _frames(rng, B, H, W)
    planes = [PC.planar_frames(jnp.asarray(fs[i])) for i in range(3)]
    p0 = np.asarray(planes[0])
    assert p0.shape == (B, 3, 96, 256) and p0.dtype == np.int32
    np.testing.assert_array_equal(p0[:, :, :H, :W],
                                  fs[0].transpose(0, 3, 1, 2))
    assert not p0[:, :, H:].any() and not p0[:, :, :, W:].any()
    mask, counts = PC.pixel_cascade_pallas(
        *planes, threshold=40, maxval=255, true_hw=(H, W), interpret=True)
    mask_np, cnt_np = ref.pixel_cascade_np(fs[0], fs[1], fs[2], 40)
    mask = np.asarray(mask)
    np.testing.assert_array_equal(mask[:, :H, :W], mask_np)
    assert not mask[:, H:].any() and not mask[:, :, W:].any()
    np.testing.assert_array_equal(np.asarray(counts), cnt_np)


# --- launch budget -----------------------------------------------------------


def test_pixel_tick_launch_budget(monkeypatch):
    """A pixel tick's framediff->morphology chain is ONE fused Pallas
    launch (<= 2 is the acceptance bar; score_crops is a jit'd model
    apply, not a Pallas program), and none on the jnp reference.

    Counted at trace time by monkeypatching ``pallas_call`` on the shared
    pallas module — so the frame shape must be FRESH (never traced in
    this process); jit caches replay traced launches without re-entering
    ``pallas_call``.
    """
    launches = {"n": 0}
    real = pallas_mod.pallas_call

    def counting(*a, **kw):
        launches["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pallas_mod, "pallas_call", counting)
    rng = np.random.default_rng(5)
    # fresh, never-traced frame shape (prime-ish H/W)
    fs = _frames(rng, 2, 67, 131)
    f = tuple(jnp.asarray(fs[i]) for i in range(3))

    launches["n"] = 0
    ops.pixel_cascade(*f, threshold=41)
    assert launches["n"] == 1
    assert launches["n"] <= 2          # the acceptance bar

    launches["n"] = 0
    ops.pixel_cascade(*f, threshold=41, use_pallas=False)
    assert launches["n"] == 0          # the jnp reference: no launch


def test_pixel_city_tick_detect_launch_budget(monkeypatch):
    """End-to-end: a pixel_city-style fleet tick through ``detect`` stays
    within the <= 2 Pallas-launch budget: the cascade and the label
    fixpoint."""
    launches = {"n": 0}
    real = pallas_mod.pallas_call

    def counting(*a, **kw):
        launches["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pallas_mod, "pallas_call", counting)
    sc = pixel_city(num_cameras=3)
    cam = SV.make_cameras(sc.num_cameras, seed=sc.seed)[0]
    rng = np.random.default_rng(9)
    # fresh batch shape: 3 cameras at a never-traced 61x133 frame
    batch = rng.integers(0, 256, (3, 3, 61, 133, 3)).astype(np.int32)
    assert (cam.height, cam.width) == (96, 128)   # city preset sanity
    launches["n"] = 0
    DP.detect(batch, threshold=40)
    assert launches["n"] == 2


# --- detect integration ------------------------------------------------------


def test_detect_fused_matches_staged_end_to_end():
    """Boxes and crops identical under the kernels and the jnp
    references (``use_pallas=False``)."""
    rng = np.random.default_rng(0)
    cam = SV.make_cameras(1, seed=11)[0]
    cam.base_rate, cam.busy_boost = 2.0, 0.0
    frames, _ = SV.render_triple(cam, 0.0, rng)
    dets_f = DP.detect(frames)[0]
    dets_s = DP.detect(frames, use_pallas=False)[0]
    assert len(dets_f) == len(dets_s) > 0
    for df, ds in zip(dets_f, dets_s):
        assert df.box == ds.box
        np.testing.assert_array_equal(df.crop, ds.crop)


def test_static_scene_skips_ccl(monkeypatch):
    """A motionless tick returns empties WITHOUT running the CCL
    fixpoint — the fused kernel's counts short-circuit it.  A moving tick
    at a never-traced shape does reach the patched labeller, so the patch
    sits where the labelling happens."""
    called = {"n": 0}
    real = ops.label_components

    def counting(mask, **kw):
        called["n"] += 1
        return real(mask, **kw)

    monkeypatch.setattr(ops, "label_components", counting)
    static = np.full((2, 3, 96, 128, 3), 55, np.int32)
    out = DP.detect(static)
    assert out == [[], []]
    assert called["n"] == 0
    moving = np.random.default_rng(3).integers(
        0, 256, (2, 3, 59, 127, 3)).astype(np.int32)
    DP.detect(moving)
    assert called["n"] == 1


# --- Scenario.frame_hw validation -------------------------------------------


def test_frame_hw_validation_rejects_tiny_and_huge():
    with pytest.raises(ValueError, match="minimum frame side"):
        validate_frame_hw("t", MIN_FRAME_SIDE - 1, 128)
    with pytest.raises(ValueError, match="tile table's limit"):
        validate_frame_hw("t", 4096, 4096)
    validate_frame_hw("t", 96, 128)          # default camera frame: fine


def test_scenario_rejects_bad_frame_hw():
    sc = pixel_city(num_cameras=2)
    with pytest.raises(ValueError, match="minimum frame side"):
        dataclasses.replace(sc, frame_hw=(8, 128))
    big_hw = (2048, int(MAX_FRAME_ELEMS / 2048) + 129)
    with pytest.raises(ValueError, match="tile table's limit"):
        dataclasses.replace(sc, frame_hw=big_hw)
    ok = dataclasses.replace(sc, frame_hw=(48, 64))   # validates cleanly
    assert ok.frame_hw == (48, 64)
