"""Connected-component labeling + bounding boxes (TPU-native contour substitute).

The paper retrieves contours with Suzuki border-following — sequential
pointer-chasing with no TPU analogue.  We use iterative min-label propagation
(a data-parallel fixpoint: every foreground pixel takes the min label of its
8-neighbourhood until convergence), which yields identical bounding boxes for
the pipeline's purpose; see the detection stage in ``docs/ARCHITECTURE.md``.
The labels come from ``ops.label_components`` (the Pallas kernel of
``kernels/ccl.py``, or the jnp fixpoint beside it); ``box_table`` here
turns them into a fixed-size table of filtered boxes per camera, also on
the device.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Box:
    y0: int
    x0: int
    y1: int
    x1: int
    area: int

    @property
    def h(self) -> int:
        return self.y1 - self.y0 + 1

    @property
    def w(self) -> int:
        return self.x1 - self.x0 + 1


#: rows and columns of the window around each root that ``box_table``
#: reads first, and the columns it keeps left of the root
WINDOW_H, WINDOW_W, WINDOW_LEFT = 64, 128, 48


def _stats(same, y0, x0):
    """(y1, x0, x1, area) of the pixels ``same`` (..., h, w) marks, in a
    window whose corner is (y0, x0)."""
    h, w = same.shape[-2:]
    in_row = jnp.sum(same, axis=-1, dtype=jnp.int32)
    in_col = jnp.any(same, axis=-2)
    ys = jnp.arange(h, dtype=jnp.int32)
    xs = jnp.arange(w, dtype=jnp.int32)
    return (y0 + jnp.max(jnp.where(in_row > 0, ys, -1), axis=-1),
            x0 + jnp.min(jnp.where(in_col, xs, w), axis=-1),
            x0 + jnp.max(jnp.where(in_col, xs, -1), axis=-1),
            jnp.sum(in_row, axis=-1), in_row, in_col)


def box_table(labels: jax.Array, max_boxes: int, *, min_area: int = 12,
              max_aspect: float = 6.0
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fixed-shape bounding boxes of ``ops.label_components``' labels, on
    the device, with the paper's size/aspect filter (§IV-C).

    labels (B, H, W) -> (boxes (B, K, 5) int32 rows of (y0, x0, y1, x1,
    area), keep (B, K) bool, components (B,) int32), K = ``max_boxes``.
    Slot k holds camera b's k-th component in raster order of its first
    pixel (a component's label is that pixel's linear index, so its row
    is the box's y0); ``keep`` marks the slots that hold a component and
    pass the filter.  Components past the K-th get no slot:
    ``components - K`` of them, where positive, are dropped.

    Each component is measured in a ``WINDOW_H`` x ``WINDOW_W`` window
    from its first row; if any component reaches an edge of its window
    that is not an edge of the frame, it may extend past it, and every
    slot is measured over the whole frame instead.
    """
    B, H, W = labels.shape
    K = max_boxes
    lin = jnp.arange(H * W, dtype=jnp.int32).reshape(1, H, W)
    root = labels == lin                      # background (-1) never is
    per_row = jnp.sum(root, axis=2, dtype=jnp.int32)               # (B, H)
    cum = jnp.cumsum(per_row, axis=1)
    k = jnp.arange(K, dtype=jnp.int32)
    # the row holding each slot's root, and the root's rank in that row
    row = jnp.minimum(jax.vmap(
        lambda c: jnp.searchsorted(c, k, side="right"))(cum), H - 1)
    rank = k[None] - (jnp.take_along_axis(cum, row, 1)
                      - jnp.take_along_axis(per_row, row, 1))
    line = jnp.take_along_axis(root, row[:, :, None], 1)       # (B, K, W)
    col = jnp.argmax(jnp.cumsum(line, axis=2, dtype=jnp.int32)
                     > rank[:, :, None], axis=2).astype(jnp.int32)
    live = k[None] < cum[:, -1:]
    ident = jnp.where(live, row * W + col, -2)                    # (B, K)

    wh, ww = min(WINDOW_H, H), min(WINDOW_W, W)
    wy = jnp.minimum(row, H - wh)
    wx = jnp.clip(col - WINDOW_LEFT, 0, W - ww)
    win = jax.vmap(jax.vmap(
        lambda lab, y, x: jax.lax.dynamic_slice(lab, (y, x), (wh, ww)),
        in_axes=(None, 0, 0)))(labels, wy, wx)             # (B, K, wh, ww)
    *near, in_row, in_col = _stats(win == ident[:, :, None, None],
                                   wy, wx)
    spills = live & ((in_row[..., -1] > 0) & (wy + wh < H)
                     | in_col[..., 0] & (wx > 0)
                     | in_col[..., -1] & (wx + ww < W))

    def whole():
        return _stats(labels[:, None] == ident[:, :, None, None],
                      0, 0)[:4]

    y1, x0, x1, area = jax.lax.cond(jnp.any(spills), whole,
                                    lambda: tuple(near))
    h, w = y1 - row + 1, x1 - x0 + 1
    long_side = jnp.maximum(h, w).astype(jnp.float32)
    short_side = jnp.maximum(jnp.minimum(h, w), 1).astype(jnp.float32)
    keep = live & (area >= min_area) & (long_side <= max_aspect * short_side)
    boxes = jnp.stack([row, x0, y1, x1, area], axis=-1)
    return boxes, keep, cum[:, -1]
