"""Connected-component label fixpoint, per camera: a Pallas TPU kernel
and the jnp fixpoint it falls back to.

``label_components`` is the jnp fixpoint, an XLA ``while_loop`` over the
whole fleet's (B, H, W) label plane: every step streams the plane through
HBM, and the loop runs until the slowest camera has converged.  It runs
where a frame's planes do not fit in VMEM (``fits``) and is the oracle
the kernel is tested bit-exact against.  The kernel
runs the same fixpoint with one camera's whole (padded) frame resident in
VMEM from its initial labels to convergence: the grid walks the cameras,
the mask is read once and the labels are written once, and each camera
stops at its own convergence.

Per camera the labels live in two VMEM planes, ``A`` and ``B``, each with
``HALO`` rows of background above and below the frame, so a row's upper
and lower neighbours are always in the plane.  One step reads ``A`` and
writes ``B`` (or back) in row strips of ``strip`` rows: a strip's window
is the strip plus ``HALO`` rows on each side, all tile-aligned, and the
separable 3x3 min runs on it as lane rolls (the row's three) then sublane
rolls (three rows).  The padded width always leaves at least one
background column, so a lane roll never lets column 0 see column W-1.

Background holds ``BIG``, which no min can lower, so a pixel is
foreground exactly when its label is below ``BIG``, and a step keeps
background at ``BIG`` without reading the mask again.

The loop checks convergence every ``STEPS`` steps and stops at
``max_iters``, as the jnp fixpoint does, and counts its steps the same
way.  Labels only fall, so a block of ``STEPS`` steps changes something
exactly when its first step does: the change is checked on that first
step, and a block whose first step changes nothing computes no more.
Min is associative and commutative, so each camera's own fixpoint is
the one the fleet-wide loop reaches: the labels are bit-exact with
``label_components``.

Compiled on TPU, interpreted on CPU (``runtime.resolve_interpret``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

#: background label, above any pixel's linear index, which no min lowers
BIG = 1 << 30

#: neighbourhood-min steps between two convergence checks of the fixpoint
STEPS = 4

#: background rows above and below the frame in each label plane: one
#: sublane tile, so every strip window starts on a tile
HALO = 8

#: most rows a strip holds; the frame's rows are split into equal strips
#: of at most this many, rounded up to a tile
MAX_STRIP = 128

#: v5e VMEM a launch may claim (of 128 MiB a core); a frame whose
#: resident planes need more keeps the jnp fixpoint
VMEM_BUDGET = 100 << 20


def label_components(mask: jax.Array, max_iters: int = 256
                     ) -> Tuple[jax.Array, jax.Array]:
    """mask (B,H,W) {0, nonzero} -> (labels (B,H,W) int32 (-1 background),
    steps (B,) int32).

    Label of a component = min linear index of its pixels.  Each step
    takes the min over a pixel's 3x3 neighbourhood, as a min over each
    row's three then over three rows (slices of BIG-padded copies);
    convergence is checked every ``STEPS`` steps, and at most
    ``max_iters`` steps run.  Each camera stops at its own convergence
    (the loop is vmapped over cameras), and ``steps`` counts its steps,
    ``STEPS`` to a check, the final check included.
    """
    _, H, W = mask.shape
    lin = jnp.arange(H * W, dtype=jnp.int32).reshape(H, W)
    big = jnp.int32(BIG)

    def one(m):
        fg = m > 0

        def nb_min(lab):
            pad = jnp.pad(lab, ((0, 0), (1, 1)), constant_values=big)
            row = jnp.minimum(jnp.minimum(pad[:, :W], pad[:, 2:]), lab)
            pad = jnp.pad(row, ((1, 1), (0, 0)), constant_values=big)
            m = jnp.minimum(jnp.minimum(pad[:H], pad[2:]), row)
            return jnp.where(fg, m, big)

        def cond(state):
            lab, changed, it = state
            return changed & (it < max_iters)

        def body(state):
            lab, _, it = state
            new = lab
            for _ in range(STEPS):
                new = nb_min(new)
            return new, jnp.any(new != lab), it + STEPS

        lab, _, it = jax.lax.while_loop(
            cond, body, (jnp.where(fg, lin, big), jnp.bool_(True),
                         jnp.int32(0)))
        return jnp.where(fg, lab, -1), it

    return jax.vmap(one)(mask)


def plane_shape(h: int, w: int) -> Tuple[int, int, int]:
    """(padded rows, padded lanes, strip rows) of an (h, w) frame.

    Rows pad up to a whole number of equal strips, each a multiple of 8;
    lanes pad up to a multiple of 128 with at least one background column
    (a 128-lane-aligned width gains 128 more)."""
    n = pl.cdiv(h, MAX_STRIP)
    strip = pl.cdiv(pl.cdiv(h, n), 8) * 8
    return n * strip, (w // 128 + 1) * 128, strip


def vmem_bytes(h: int, w: int) -> int:
    """VMEM the kernel claims for an (h, w) frame: the mask and label
    blocks double-buffered, the two haloed label planes, and room for a
    strip window's working copies."""
    hp, wp, strip = plane_shape(h, w)
    plane = hp * wp * 4
    window = (strip + 2 * HALO) * wp * 4
    return 4 * plane + 2 * (plane + 2 * HALO * wp * 4) + 8 * window + (2 << 20)


def fits(h: int, w: int) -> bool:
    """Whether an (h, w) frame's fixpoint runs in the kernel; a larger
    one keeps the jnp fixpoint, ``label_components``."""
    return vmem_bytes(h, w) <= VMEM_BUDGET


def _ccl_kernel(mask_ref, lab_ref, steps_ref, a, b, *, true_w: int,
                strip: int, max_iters: int):
    """One camera: initial labels, the fixpoint, the labels out."""
    hp, wp = mask_ref.shape[1], mask_ref.shape[2]
    n_strips = hp // strip
    big = jnp.int32(BIG)
    edge = jnp.full((HALO, wp), BIG, jnp.int32)
    for plane in (a, b):
        plane[pl.ds(0, HALO), :] = edge
        plane[pl.ds(HALO + hp, HALO), :] = edge

    def rows(i):
        return pl.multiple_of(i * strip, 8)

    def init(i, carry):
        r0 = rows(i)
        y = r0 + jax.lax.broadcasted_iota(jnp.int32, (strip, wp), 0)
        x = jax.lax.broadcasted_iota(jnp.int32, (strip, wp), 1)
        fg = mask_ref[0, pl.ds(r0, strip), :] > 0
        a[pl.ds(r0 + HALO, strip), :] = jnp.where(fg, y * true_w + x, big)
        return carry

    jax.lax.fori_loop(0, n_strips, init, 0)

    def step(src, dst, check: bool):
        """One 3x3 min step src -> dst; whether it changed a label."""
        def one(i, changed):
            r0 = rows(i)
            win = src[pl.ds(r0, strip + 2 * HALO), :]
            row = jnp.minimum(jnp.minimum(pltpu.roll(win, 1, 1),
                                          pltpu.roll(win, wp - 1, 1)), win)
            col = jnp.minimum(
                jnp.minimum(pltpu.roll(row, 1, 0),
                            pltpu.roll(row, strip + 2 * HALO - 1, 0)), row)
            old = win[HALO:HALO + strip]
            new = jnp.where(old == big, big, col[HALO:HALO + strip])
            dst[pl.ds(r0 + HALO, strip), :] = new
            if check:
                changed = jnp.maximum(changed, jnp.max(
                    jnp.where(new != old, 1.0, 0.0)).astype(jnp.int32))
            return changed

        return jax.lax.fori_loop(0, n_strips, one, jnp.int32(0))

    def block(carry):
        """``STEPS`` steps from ``a`` back into ``a``; the labels are in
        ``a`` again when it returns, changed or not."""
        it, _ = carry
        changed = step(a, b, True)

        @pl.when(changed > 0)
        def _():
            step(b, a, False)
            step(a, b, False)
            step(b, a, False)

        return it + STEPS, changed

    steps, _ = jax.lax.while_loop(
        lambda c: (c[1] > 0) & (c[0] < max_iters), block,
        (jnp.int32(0), jnp.int32(1)))
    steps_ref[...] = jnp.full(steps_ref.shape, steps, jnp.int32)

    def out(i, carry):
        r0 = rows(i)
        lab = a[pl.ds(r0 + HALO, strip), :]
        lab_ref[0, pl.ds(r0, strip), :] = jnp.where(lab == big, -1, lab)
        return carry

    jax.lax.fori_loop(0, n_strips, out, 0)


def label_components_pallas(mask: jax.Array, *, max_iters: int = 256,
                            interpret: Optional[bool] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """mask (B, H, W) {0, nonzero} -> (labels (B, H, W) int32, -1 for
    background and the min linear index ``y * W + x`` of its component
    for foreground; steps (B,) int32, each camera's fixpoint steps as the
    jnp loop counts them)."""
    interpret = resolve_interpret(interpret)
    B, H, W = mask.shape
    hp, wp, strip = plane_shape(H, W)
    mask = jnp.pad(mask.astype(jnp.int32), ((0, 0), (0, hp - H), (0, wp - W)))
    kernel = functools.partial(_ccl_kernel, true_w=W, strip=strip,
                               max_iters=max_iters)
    labels, steps = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, hp, wp), lambda b: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, hp, wp), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, hp, wp), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((hp + 2 * HALO, wp), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(H, W)),
        interpret=interpret,
    )(mask)
    return labels[:, :H, :W], steps[:, 0, 0]
