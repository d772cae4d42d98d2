"""Pallas TPU kernel: the fused pixel-cascade frontend (paper Eqs. 1-6).

One launch per tick runs the whole chain, with no full-frame HBM
round-trip between its stages:

    framediff (Eqs. 1-4) -> 3x3 dilate (Eq. 5) -> 3x3 erode (Eq. 6)
                         -> per-band foreground reduction

The kernel walks each camera's frame in (BAND_H, W) row bands with a
double-buffered software pipeline: grid step ``i`` frame-differences band
``i`` into a rolling three-slot VMEM scratch while the 3x3 stencil chain
and writeback run for band ``i - 1``, whose halo rows (the last two of
band ``i - 2``, the first two of band ``i``) are already resident.  The
framediff and dilated masks never leave VMEM/registers — only the input
frames stream in and the final eroded mask streams out, so a compiled
tick is bounded by frame bandwidth, not launch count or intermediate
traffic.  On TPU the grid's block DMAs double-buffer automatically on top
of the software pipeline; the one-band writeback delay is expressed with
revisited output blocks (steps ``i`` and ``i + 1`` map to the same output
band exactly once at the boundary, so copy-out happens after the real
write).

Band layout per grid step ``(b, i)`` of the ``(B, nb + 1)`` grid::

      fd scratch (3, BAND_H, W)            output band i-1
      ┌────────────┐                       ┌──────────────┐
      │ band i-2   │─ last 2 rows ─┐       │              │
      ├────────────┤               ▼       │   erode ∘    │
      │ band i-1   │──────────▶ (BAND_H+4, │   dilate     │
      ├────────────┤               ▲  W)   │   window     │
      │ band i     │─ first 2 rows ┘       │              │
      └────────────┘ ◀─ framediff(band i)  └──────────────┘

Frames arrive planar, (B, 3, H', W'), so a block's lane axis is the
padded frame width and never the 3 colour channels.

The second output is the per-camera foreground count — the mask
reduction the host needs to skip connected-component labeling for
motionless cameras (and the whole CCL fixpoint for motionless ticks)
without paying another device pass over the mask.  Its (1, 1, 1) block
is the whole trailing extent of a (B, 1, 1) array, which keeps it
lane-legal, and it stays resident while the camera's bands accumulate.

Boundary semantics match the separate framediff, dilate and erode of
``ref.pixel_cascade_ref`` bit-exactly: framediff outside the true (H, W)
image is 0 (dilate's fill), dilated values outside it are ``maxval``
(erode's fill), and the final mask is zeroed outside the true image so
the pad region can never contribute to a count.  Both stencils are
``stencil3x3``, a nine-shift reduction in registers.

Compiled on TPU, interpreted on CPU (``runtime.resolve_interpret``);
validated against the independent NumPy oracle ``ref.pixel_cascade_np``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.buckets import FRAME_BAND_H, FRAME_LANE_W, frame_pad
from repro.kernels.runtime import resolve_interpret

BAND_H = FRAME_BAND_H

_OPS = {"max": jnp.maximum, "min": jnp.minimum}


def stencil3x3(win: jax.Array, *, op: str, fill: int,
               out_h: int, out_w: int) -> jax.Array:
    """Nine-shift 3x3 stencil reduce over a row window, in registers.

    ``win`` is an (out_h + 2, out_w) window that already carries the
    1-row halo above and below; the 1-column halo is filled here with
    ``fill`` (a concatenate, not a host pad), so callers never pad W.
    Returns the (out_h, out_w) reduced block.
    """
    red = _OPS[op]
    fc = jnp.full((win.shape[0], 1), fill, win.dtype)
    xp = jnp.concatenate([fc, win, fc], axis=1)       # (out_h+2, out_w+2)
    acc = None
    for dy in range(3):
        for dx in range(3):
            sl = xp[dy:dy + out_h, dx:dx + out_w]
            acc = sl if acc is None else red(acc, sl)
    return acc


def _framediff_band(f0, f1, f2, *, threshold: int, maxval: int) -> jax.Array:
    """Eqs. 1-4 on one planar (3, bh, W) frame band -> (bh, W) binary mask."""
    d1 = jnp.abs(f1 - f0)                        # Eq. 1
    d2 = jnp.abs(f2 - f1)                        # Eq. 2
    da = jnp.bitwise_and(d1, d2)                 # Eq. 3 (uint8 bits in i32)
    gray = (da[0] * 299 + da[1] * 587 + da[2] * 114) // 1000
    return jnp.where(gray > threshold, maxval, 0).astype(jnp.int32)


def _cascade_kernel(f0_ref, f1_ref, f2_ref, mask_ref, count_ref, fd, *,
                    nb: int, true_h: int, true_w: int,
                    threshold: int, maxval: int):
    """One grid step of the band pipeline (see module docstring)."""
    i = pl.program_id(1)
    bh, Wp = mask_ref.shape[1], mask_ref.shape[2]

    # stage 1 — framediff band i into its rolling scratch slot.  Skipped on
    # the flush step (i == nb), which only drains the pipeline.
    @pl.when(i < nb)
    def _():
        fd[jax.lax.rem(i, 3)] = _framediff_band(
            f0_ref[0], f1_ref[0], f2_ref[0],
            threshold=threshold, maxval=maxval)

    # stage 2 — dilate + erode + reduce band i-1, whose halo is resident:
    # rows above come from band i-2's slot, rows below from the slot stage 1
    # just wrote.  Out-of-image halos reduce to each stencil's fill.
    @pl.when(i >= 1)
    def _():
        cur = fd[jax.lax.rem(i + 2, 3)]                  # band i-1
        above = fd[jax.lax.rem(i + 1, 3)][bh - 2:, :]    # band i-2, last 2
        below = fd[jax.lax.rem(i, 3)][:2, :]             # band i,   first 2
        above = jnp.where(i >= 2, above, 0)              # no band above 0
        below = jnp.where(i <= nb - 1, below, 0)         # flush: none below
        win = jnp.concatenate([above, cur, below], axis=0)   # (bh+4, Wp)

        # Eq. 5: 3x3 max, fill 0 — framediff is already 0 outside (H, W)
        dil = stencil3x3(win, op="max", fill=0, out_h=bh + 2, out_w=Wp)

        # Eq. 6: 3x3 min, fill maxval — mask the pad region to maxval so
        # the erode boundary matches the reference's fill bit-exactly
        g0 = (i - 1) * bh - 1                    # global row of dil row 0
        rows = g0 + jax.lax.broadcasted_iota(jnp.int32, (bh + 2, Wp), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bh + 2, Wp), 1)
        dil = jnp.where((rows >= 0) & (rows < true_h) & (cols < true_w),
                        dil, maxval)
        ero = stencil3x3(dil, op="min", fill=maxval, out_h=bh, out_w=Wp)

        # zero the pad region so counts see only true pixels, then reduce
        orows = (i - 1) * bh + jax.lax.broadcasted_iota(
            jnp.int32, (bh, Wp), 0)
        ocols = jax.lax.broadcasted_iota(jnp.int32, (bh, Wp), 1)
        out = jnp.where((orows < true_h) & (ocols < true_w), ero, 0)
        mask_ref[0] = out.astype(mask_ref.dtype)

        # the camera's count block stays resident across its bands: zero it
        # on the first drained band, accumulate on every band after
        @pl.when(i == 1)
        def _():
            count_ref[...] = jnp.zeros_like(count_ref)

        count_ref[...] += jnp.sum((out > 0).astype(jnp.int32),
                                  keepdims=True)[None]


def pixel_cascade_pallas(f0: jax.Array, f1: jax.Array, f2: jax.Array, *,
                         threshold: int, maxval: int,
                         true_hw: Tuple[int, int],
                         interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Planar (B, 3, H', W') int32 frame triple (``planar_frames``) ->
    ((B, H', W') mask, (B,) foreground counts).

    H' must be a multiple of BAND_H and W' of FRAME_LANE_W; the true
    (H, W) comes in through ``true_hw``.  The mask is zero outside the
    true image and the counts cover true pixels only.
    """
    interpret = resolve_interpret(interpret)
    B, C, Hp, Wp = f0.shape
    true_h, true_w = true_hw
    assert C == 3 and Hp % BAND_H == 0 and Wp % FRAME_LANE_W == 0, (f0.shape,)
    nb = Hp // BAND_H
    kernel = functools.partial(_cascade_kernel, nb=nb, true_h=true_h,
                               true_w=true_w, threshold=threshold,
                               maxval=maxval)
    in_spec = pl.BlockSpec((1, 3, BAND_H, Wp),
                           lambda b, i: (b, 0, jnp.minimum(i, nb - 1), 0))
    mask, counts = pl.pallas_call(
        kernel,
        grid=(B, nb + 1),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[
            pl.BlockSpec((1, BAND_H, Wp),
                         lambda b, i: (b, jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hp, Wp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((3, BAND_H, Wp), jnp.int32)],
        interpret=interpret,
    )(f0, f1, f2)
    return mask, counts[:, 0, 0]


def planar_frames(x: jax.Array) -> jax.Array:
    """(B, H, W, 3) frames -> zero-padded planar (B, 3, H', W') int32.

    The colour planes lead so the lane axis is the padded width, never the
    3 channels (a channels-last block would pad 3 lanes out to 128).  Zero
    is the correct frame fill: framediff of identical zeros is 0, which is
    exactly dilate's out-of-image fill — the kernel handles the erode fill
    itself via the true (H, W) mask.
    """
    B, H, W, _ = x.shape
    hp, wp = frame_pad(H, W)
    x = jnp.moveaxis(x.astype(jnp.int32), -1, 1)
    if hp == H and wp == W:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, hp - H), (0, wp - W)))
