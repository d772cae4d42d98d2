"""Pixel-path frontend: frames -> motion crops -> CQ scores -> Items.

The paper's query pipeline starts from pixels (§IV): frame differencing
(Eqs. 1-6) finds moving objects, their crops go through the fine-tuned CQ
classifier, and only the classifier's confidences enter the cascade.  This
module runs that path over the procedural camera fleet, one scheduler
tick at a time, with the fleet's frames kept on the device:

  1. render — the host draws every camera's scene for the tick
     (``scenario.frame_schedule`` staggers captures within the tick) and
     ONE jitted program paints the fleet's (C, 3, H, W, 3) uint8 frame
     triples on the device (``synthetic_video.paint_fleet``), from
     backgrounds uploaded once per fleet.
  2. framediff — the FUSED pixel cascade (ONE Pallas launch per tick:
     framediff + dilate + erode + foreground count, see
     ``kernels/pixel_cascade.py``), then ONE jitted program for
     connected components, the fixed box table, the crops and their patch
     tokens (``repro.detection.pipeline.detect``); only the counts, boxes,
     crops and tokens come back to the host, and the counts skip the
     second program on motionless ticks.
  3. classify — all of the tick's crops, across every camera, are scored
     by the CQ classifier in ONE bucket-padded jit launch
     (``kernels.ops.score_crops``) — launches per tick stay O(1) in fleet
     size, exactly like the fused triage kernel downstream.  The
     classifier runs in float32 at the highest matmul precision.

The output is the same ``Item`` stream the engine's event loop consumes,
so ``run_query(sc, frontend=PixelFrontend())`` is the paper's full
frames -> triage -> allocation -> metrics loop.  Ground truth comes from
the drawn scene: each detection is matched to the nearest planted sprite
(unmatched detections are disturbance and count as non-query).

Each tick's render, detect and classify phases run in spans of the call's
``Spans`` (``render``, ``detect``, ``classify``, each with ``tick=k``);
their totals surface in ``QueryReport.stage_timings`` as ``render_s``,
``framediff_s`` and ``classify_s``, and ``detect``'s three parts as
``detect_cascade_s``, ``detect_ccl_s`` and ``detect_boxes_s``.  The
frontend's counters (``crops_scored``, ``crop_pad_rows``,
``motionless_camera_ticks``, ``boxes_dropped``, ``ccl_steps``,
``ccl_cameras``) join the report's summary.

By default the classifier is a freshly initialized (untrained) CQ edge
model — the full compute path with no training in the loop, for tests and
smoke runs.  Pass ``params=`` (e.g. from ``repro.serving.workload.
build_workload`` or ``repro.core.finetune``) to score with a fine-tuned
model and get paper-meaningful accuracy numbers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core.cascade import confidence_from_logits
from repro.data import synthetic_video as SV
from repro.detection import pipeline as DP
from repro.detection.components import Box
from repro.kernels import ops
from repro.kernels.buckets import bucket
from repro.models import meta as M
from repro.models import transformer as T
from repro.serving.simulator import Item
from repro.system.frontend import Frontend
from repro.system.scenario import Scenario, frame_schedule, scenario_cameras
from repro.system.spans import Spans

#: object slots of the painter's scene table: enough for any camera of
#: the shipped fleets in one tick (a busier tick takes the next power of
#: two, and compiles once for it)
SCENE_SLOTS = 128

#: ``detect``'s parts, each a span ``detect.<part>`` and a
#: ``stage_timings`` key ``detect_<part>_s`` (self seconds)
DETECT_PARTS = ("cascade", "ccl", "boxes")

#: the frontend's counters, summed over a stream's ticks
COUNTERS = ("crops_scored", "crop_pad_rows", "motionless_camera_ticks",
            "boxes_dropped", "ccl_steps", "ccl_cameras")


def match_truth(box: Box, truth: SV.FrameTruth,
                radius: float = SV.SPRITE) -> Optional[int]:
    """Class of the planted sprite a detection box corresponds to.

    Nearest truth object whose center lies within ``radius`` of the box
    center on both axes (the renderer's sprites are SPRITE x SPRITE);
    ``None`` when the detection matches nothing — disturbance/noise."""
    cy = (box.y0 + box.y1) / 2
    cx = (box.x0 + box.x1) / 2
    best, best_d = None, float("inf")
    for cls, (y, x) in zip(truth.classes, truth.boxes):
        dy = abs(cy - (y + SV.SPRITE / 2))
        dx = abs(cx - (x + SV.SPRITE / 2))
        if dy < radius and dx < radius and dy + dx < best_d:
            best, best_d = cls, dy + dx
    return best


def _conf_apply(cfg, params, tokens: jax.Array) -> jax.Array:
    """(N, T) patch tokens -> (N,) P(query object) under the CQ model, in
    float32 at the highest matmul precision (a TPU's default is one
    bfloat16 pass)."""
    with jax.named_scope("score_crops"), \
            jax.default_matmul_precision("highest"):
        h, _ = T.forward(cfg, params, tokens, remat=False)
        return confidence_from_logits(T.classify(cfg, params, h), 1)


class PixelFrontend(Frontend):
    """Frames-to-items frontend over the procedural camera fleet.

    One instance owns one CQ classifier (config + params) and caches the
    last scenario's stream, so sweeping the four schemes over one scenario
    renders and scores the fleet's frames once, not four times.
    """

    def __init__(self, *, arch: str = "surveiledge-cls",
                 params=None, seed: int = 0,
                 query_class: int = SV.QUERY_CLASS,
                 threshold: int = 40, crop: int = 32, min_area: int = 12,
                 cache: bool = True):
        super().__init__()
        assert crop % 8 == 0, "crop side must be patch-aligned (8 px)"
        full = get_config(arch)
        self.cfg = dataclasses.replace(
            full.edge_variant(), num_query_classes=2,
            vocab_size=full.vocab_size)
        self.params = params if params is not None \
            else M.init_params(self.cfg, jax.random.PRNGKey(seed))
        self.query_class = query_class
        self.threshold = threshold
        self.crop = crop
        self.min_area = min_area
        self.launches = 0            # classifier launches (one per tick)
        self._conf_fn = jax.jit(functools.partial(_conf_apply, self.cfg))
        self._cache_enabled = cache
        self._cache: Optional[tuple] = None
        self._bg: Optional[Tuple[tuple, jax.Array]] = None
        self._textures = jax.device_put(SV.TEXTURES)

    # stream identity: every scenario field the rendered stream depends on
    # (scheme, links and topology speeds don't change what the cameras see)
    @staticmethod
    def _stream_key(sc: Scenario) -> tuple:
        return (sc.name, sc.seed, sc.num_cameras, sc.num_edges,
                sc.duration_s, sc.interval_s, sc.burst_boost, sc.burst_rate,
                sc.frame_hw, sc.track_query_ids, sc.embedding_dim)

    def stream(self, sc: Scenario, spans: Optional[Spans] = None
               ) -> List[Item]:
        key = self._stream_key(sc)
        if self._cache is not None and self._cache[0] == key:
            _, items, timings, counters = self._cache
        else:
            items, timings, counters = self._build(
                sc, spans if spans is not None else Spans())
            if self._cache_enabled:
                self._cache = (key, list(items), timings, counters)
        self._timings = dict(timings)
        self._counters = dict(counters)
        return list(items)

    def _backgrounds(self, cams: List[SV.CameraSpec]) -> jax.Array:
        """The fleet's backgrounds on the device, uploaded once per fleet
        (a background depends on the camera id and frame size only)."""
        key = tuple((c.cam_id, c.height, c.width) for c in cams)
        if self._bg is None or self._bg[0] != key:
            self._bg = (key, jax.device_put(SV.backgrounds(cams)))
        return self._bg[1]

    def _build(self, sc: Scenario, spans: Spans
               ) -> Tuple[List[Item], Dict[str, float], Dict[str, int]]:
        cams = scenario_cameras(sc)
        schedule = frame_schedule(sc)                        # (T, C)
        rng = np.random.default_rng(sc.seed + 31)
        bg = self._backgrounds(cams)
        score = functools.partial(self._conf_fn, self.params)
        counters = dict.fromkeys(COUNTERS, 0)
        items: List[Item] = []
        for k in range(schedule.shape[0]):
            with spans.span("render", tick=k):
                truths = [SV.draw_scene(cam, schedule[k, j], rng)
                          for j, cam in enumerate(cams)]
                most = max(len(t.classes) for t in truths)
                table, n_obj = SV.scene_table(
                    truths, bucket(most, SCENE_SLOTS))
                seed = np.random.SeedSequence(
                    [sc.seed % (1 << 63), k]).generate_state(1)[0]
                frames = SV.paint_fleet(bg, table, n_obj, self._textures,
                                        seed)

            with spans.span("detect", tick=k):
                dets = DP.detect(frames, threshold=self.threshold,
                                 crop=self.crop, min_area=self.min_area,
                                 vocab_size=self.cfg.vocab_size,
                                 spans=spans, counters=counters)

            flat = [(j, d) for j, per in enumerate(dets) for d in per]
            if not flat:
                continue
            with spans.span("classify", tick=k):
                conf = ops.score_crops(
                    score, np.stack([d.tokens for _, d in flat]))
            self.launches += 1
            counters["crops_scored"] += len(flat)
            counters["crop_pad_rows"] += bucket(len(flat)) - len(flat)

            nbytes = self.crop * self.crop * 3
            # track queries declared -> every detection carries a pixel-
            # derived re-ID embedding (appearance hash of the crop); no
            # trajectory ground truth on this path, so gt_track stays -1
            # (ID-switch accounting needs the synthetic-trajectory stream)
            embed = bool(sc.track_query_ids)
            for (j, det), cf in zip(flat, conf):
                cls = match_truth(det.box, truths[j])
                items.append(Item(
                    t_arrival=float(schedule[k, j]),
                    camera=cams[j].cam_id,
                    edge_device=cams[j].cam_id % sc.num_edges + 1,
                    conf=float(cf),
                    is_query=cls == self.query_class,
                    nbytes=nbytes,
                    emb=SV.crop_embedding(det.crop, sc.embedding_dim)
                    if embed else None))
        items.sort(key=lambda it: it.t_arrival)
        timings = {"render_s": spans.total("render"),
                   "framediff_s": spans.total("detect"),
                   "classify_s": spans.total("classify")}
        for part in DETECT_PARTS:
            timings[f"detect_{part}_s"] = spans.self_s(f"detect.{part}")
        return items, timings, counters
