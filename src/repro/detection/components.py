"""Connected-component labeling + bounding boxes (TPU-native contour substitute).

The paper retrieves contours with Suzuki border-following — sequential
pointer-chasing with no TPU analogue.  We use iterative min-label propagation
(a data-parallel fixpoint: every foreground pixel takes the min label of its
8-neighbourhood until convergence), which yields identical bounding boxes for
the pipeline's purpose.  See DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

BIG = jnp.int32(1 << 30)


def label_components(mask: jax.Array, max_iters: int = 256) -> jax.Array:
    """mask (B,H,W) {0, nonzero} -> labels (B,H,W) int32 (-1 background).

    Label of a component = min linear index of its pixels.
    """
    B, H, W = mask.shape
    fg = mask > 0
    init = jnp.where(fg, jnp.arange(H * W, dtype=jnp.int32).reshape(1, H, W),
                     BIG)

    def nb_min(lab):
        m = lab
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                if dy == 0 and dx == 0:
                    continue
                sh = jnp.roll(lab, (dy, dx), axis=(1, 2))
                if dy > 0:
                    sh = sh.at[:, :dy, :].set(BIG)
                elif dy < 0:
                    sh = sh.at[:, dy:, :].set(BIG)
                if dx > 0:
                    sh = sh.at[:, :, :dx].set(BIG)
                elif dx < 0:
                    sh = sh.at[:, :, dx:].set(BIG)
                m = jnp.minimum(m, sh)
        return jnp.where(fg, m, BIG)

    def cond(state):
        lab, changed, it = state
        return changed & (it < max_iters)

    def body(state):
        lab, _, it = state
        new = nb_min(lab)
        return new, jnp.any(new != lab), it + 1

    with jax.named_scope("ccl"):
        lab, _, _ = jax.lax.while_loop(cond, body,
                                       (init, jnp.bool_(True), 0))
    return jnp.where(fg, lab, -1)


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


def extract_boxes(labels: np.ndarray, *, min_area: int = 12,
                  max_aspect: float = 6.0) -> List[Box]:
    """Host-side bbox extraction + the paper's size/aspect filtering.

    Discards detections that are too small or too elongated (disturbance /
    noise), per §IV-C.
    """
    out: List[Box] = []
    lab = np.asarray(labels)
    fg = lab >= 0
    if not fg.any():
        return out
    for lid in np.unique(lab[fg]):
        ys, xs = np.nonzero(lab == lid)
        b = Box(int(ys.min()), int(xs.min()), int(ys.max()), int(xs.max()),
                int(len(ys)))
        if b.area < min_area:
            continue
        aspect = max(b.h, b.w) / max(min(b.h, b.w), 1)
        if aspect > max_aspect:
            continue
        out.append(b)
    return out
