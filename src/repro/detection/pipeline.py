"""The full moving-object detection stage (paper §IV-C), end to end.

frames -> fused pixel cascade (ONE Pallas launch: framediff + dilate +
erode + foreground count) -> one jitted program: CCL (ONE Pallas launch
of the label fixpoint, ``kernels/ccl.py``), a fixed table of
filtered bounding boxes per camera, their crops of the middle frame and,
for a classifier's vocabulary, the crops' patch tokens -> the host gets
the table.

Frames may live on the device; nothing but the cascade's per-camera
counts, the box table and its crops (and tokens) comes back.  The counts
skip the CCL program on motionless ticks, and motionless cameras yield no
boxes.  ``use_pallas=False`` drops to the jnp references
(``ref.pixel_cascade_ref`` and the jnp label fixpoint).

Under a call's ``Spans`` the stage splits into ``detect.cascade`` (the
cascade launch and its counts), ``detect.ccl`` (the CCL program and the
table's readback) and ``detect.boxes`` (the host's detections).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import synthetic_video as SV
from repro.detection import components
from repro.kernels import ops
from repro.system.spans import Spans

#: boxes a camera's table holds per tick (components past it are dropped
#: and counted); a power of two that the traffic of a 960x540 fleet at 25
#: fps never fills
MAX_BOXES = 128


@dataclasses.dataclass(frozen=True)
class Detection:
    box: components.Box
    crop: np.ndarray          # (ch, cw, 3) uint8-valued
    #: the crop's patch tokens, when ``detect`` was given a vocabulary
    tokens: Optional[np.ndarray] = None


def motion_mask(f0: jax.Array, f1: jax.Array, f2: jax.Array, *,
                threshold: int = 40, use_pallas: bool = True) -> jax.Array:
    """Eqs. 1-6: framediff + dilate + erode.  (B,H,W,3)x3 -> (B,H,W)."""
    mask, _ = ops.pixel_cascade(f0, f1, f2, threshold=threshold,
                                use_pallas=use_pallas)
    return mask


@jax.jit
def _split(frames: jax.Array):
    return frames[:, 0], frames[:, 1], frames[:, 2]


@functools.partial(jax.jit, static_argnames=("min_area", "crop",
                                             "max_boxes", "vocab_size",
                                             "use_pallas"))
def _boxes_and_crops(mask: jax.Array, middle: jax.Array, *, min_area: int,
                     crop: int, max_boxes: int, vocab_size: Optional[int],
                     use_pallas: bool):
    """One tick's device work after the cascade: labels (and each
    camera's fixpoint steps), the box table, the crops centred on each
    box (shifted inside the frame) and their tokens."""
    with jax.named_scope("ccl"):
        labels, steps = ops.label_components(mask, use_pallas=use_pallas)
    boxes, keep, comps = components.box_table(labels, max_boxes,
                                              min_area=min_area)
    H, W = middle.shape[1], middle.shape[2]
    top = jnp.clip((boxes[..., 0] + boxes[..., 2]) // 2 - crop // 2,
                   0, H - crop)
    left = jnp.clip((boxes[..., 1] + boxes[..., 3]) // 2 - crop // 2,
                    0, W - crop)

    def cut(frame, y, x):
        return jax.lax.dynamic_slice(frame, (y, x, 0), (crop, crop, 3))

    with jax.named_scope("crops"):
        crops = jax.vmap(jax.vmap(cut, in_axes=(None, 0, 0)))(
            middle, top, left).astype(jnp.uint8)      # (B, K, crop, crop, 3)
    out = {"boxes": boxes, "keep": keep, "components": comps,
           "crops": crops, "steps": steps}
    if vocab_size is not None:
        B, K = keep.shape
        tokens, tie = SV.crops_to_tokens_device(
            crops.reshape(B * K, crop, crop, 3), vocab_size)
        out["tokens"] = tokens.reshape(B, K, -1)
        out["tie"] = tie.reshape(B, K)
    return out


def detect(frames, *, threshold: int = 40, crop: int = 32,
           min_area: int = 12, use_pallas: bool = True,
           vocab_size: Optional[int] = None, spans: Optional[Spans] = None,
           counters: Optional[Dict[str, int]] = None
           ) -> List[List[Detection]]:
    """frames: (3, H, W, 3) consecutive triple (or (B,3,H,W,3)), on the
    host or the device.

    Returns, per batch item, the filtered detections of the middle frame,
    in raster order of each component's first pixel.  With
    ``vocab_size`` each detection carries its crop's patch tokens
    (``crops_to_tokens``: made on the device, near ties redone on the host
    in float64).  ``counters``, when given, gains ``boxes_dropped``
    (components past a camera's ``MAX_BOXES``),
    ``motionless_camera_ticks`` (cameras with an empty mask), and, on
    ticks that run the CCL program, ``ccl_cameras`` (the cameras it
    labels) and ``ccl_steps`` (their label fixpoint steps, summed).
    """
    spans = spans if spans is not None else Spans()
    with spans.span("detect.cascade"):
        frames = jnp.asarray(frames)
        if frames.ndim == 4:
            frames = frames[None]
        B = frames.shape[0]
        f0, f1, f2 = _split(frames)
        mask, counts = ops.pixel_cascade(f0, f1, f2, threshold=threshold,
                                         use_pallas=use_pallas)
        moving = np.asarray(counts) > 0
        if counters is not None:
            counters["motionless_camera_ticks"] = counters.get(
                "motionless_camera_ticks", 0) + int(B - moving.sum())
    if not moving.any():
        # motionless tick: no foreground anywhere — skip the CCL program
        return [[] for _ in range(B)]
    with spans.span("detect.ccl"):
        table = jax.device_get(_boxes_and_crops(
            mask, f1, min_area=min_area, crop=crop, max_boxes=MAX_BOXES,
            vocab_size=vocab_size, use_pallas=use_pallas))
    with spans.span("detect.boxes"):
        if counters is not None:
            dropped = np.maximum(table["components"] - MAX_BOXES, 0).sum()
            for key, n in (("boxes_dropped", dropped),
                           ("ccl_steps", table["steps"].sum()),
                           ("ccl_cameras", B)):
                counters[key] = counters.get(key, 0) + int(n)
        tokens = table.get("tokens")
        if tokens is not None:
            tie = table["tie"] & table["keep"]
            if tie.any():
                tokens = tokens.copy()
                tokens[tie] = SV.crops_to_tokens(table["crops"][tie],
                                                 vocab_size)
        out: List[List[Detection]] = []
        for b in range(B):
            slots = np.flatnonzero(table["keep"][b])
            out.append([Detection(
                components.Box(*(int(v) for v in table["boxes"][b, k])),
                table["crops"][b, k],
                None if tokens is None else tokens[b, k]) for k in slots])
    return out
