"""Pixel-path frontend: rendered frames -> motion crops -> CQ scores -> Items.

The paper's query pipeline starts from pixels (§IV): frame differencing
(Eqs. 1-6) finds moving objects, their crops go through the fine-tuned CQ
classifier, and only the classifier's confidences enter the cascade.  This
module runs that path over the procedural camera fleet:

  1. render — every camera produces one synthetic frame triple per
     scheduler tick (``scenario.frame_schedule`` staggers captures within
     the tick), batched fleet-wide into one (C, 3, H, W, 3) array.
  2. framediff — the FUSED pixel cascade (ONE Pallas launch per tick:
     framediff + dilate + erode + foreground count, see
     ``kernels/pixel_cascade.py``) and the connected-component labeler
     (``repro.detection.pipeline.detect``) turn the tick's frames into
     filtered moving-object crops; the counts skip CCL on motionless
     ticks.  ``fused=False`` keeps the original staged three-launch
     chain as the differential reference.
  3. classify — all of the tick's crops, across every camera, are scored
     by the CQ classifier in ONE bucket-padded jit launch
     (``kernels.ops.score_crops``) — launches per tick stay O(1) in fleet
     size, exactly like the fused triage kernel downstream.

The output is the same ``Item`` stream the engine's event loop consumes,
so ``run_query(sc, frontend=PixelFrontend())`` is the paper's full
frames -> triage -> allocation -> metrics loop.  Ground truth comes from
the renderer: each detection is matched to the nearest planted sprite
(unmatched detections are disturbance and count as non-query).

Each tick's render, detect and classify phases run in spans of the call's
``Spans`` (``render``, ``detect``, ``classify``, each with ``tick=k``);
their totals surface in ``QueryReport.stage_timings`` as ``render_s``,
``framediff_s`` and ``classify_s``.

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
from repro.models import meta as M
from repro.models import transformer as T
from repro.serving.simulator import Item
from repro.system.frontend import Frontend
from repro.system.scenario import Scenario, frame_schedule, scenario_cameras
from repro.system.spans import Spans


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
    """(N, T) patch tokens -> (N,) P(query object) under the CQ model."""
    with jax.named_scope("score_crops"):
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
                 use_pallas: bool = True, fused: bool = True,
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
        self.use_pallas = use_pallas
        self.fused = fused           # ONE fused pixel launch vs staged three
        self.launches = 0            # classifier launches (one per tick)
        self._conf_fn = jax.jit(functools.partial(_conf_apply, self.cfg))
        self._cache_enabled = cache
        self._cache: Optional[Tuple[tuple, List[Item], Dict[str, float]]] \
            = None

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
            _, items, timings = self._cache
            self._timings = dict(timings)
            return list(items)
        items, timings = self._build(sc, spans if spans is not None
                                     else Spans())
        self._timings = dict(timings)
        if self._cache_enabled:
            self._cache = (key, list(items), timings)
        return items

    def _build(self, sc: Scenario, spans: Spans
               ) -> Tuple[List[Item], Dict[str, float]]:
        cams = scenario_cameras(sc)
        schedule = frame_schedule(sc)                        # (T, C)
        rng = np.random.default_rng(sc.seed + 31)
        items: List[Item] = []
        for k in range(schedule.shape[0]):
            with spans.span("render", tick=k):
                triples, truths = [], []
                for j, cam in enumerate(cams):
                    frames, truth = SV.render_triple(cam, schedule[k, j],
                                                     rng)
                    triples.append(frames)
                    truths.append(truth)
                batch = np.stack(triples)                # (C, 3, H, W, 3)

            with spans.span("detect", tick=k):
                dets = DP.detect(batch, threshold=self.threshold,
                                 crop=self.crop, min_area=self.min_area,
                                 use_pallas=self.use_pallas,
                                 fused=self.fused)

            flat = [(j, d) for j, per in enumerate(dets) for d in per]
            if not flat:
                continue
            with spans.span("classify", tick=k):
                tokens = SV.crops_to_tokens(
                    np.stack([d.crop for _, d in flat]), self.cfg.vocab_size)
                conf = np.asarray(ops.score_crops(
                    functools.partial(self._conf_fn, self.params), tokens))
            self.launches += 1

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
        return items, {"render_s": spans.total("render"),
                       "framediff_s": spans.total("detect"),
                       "classify_s": spans.total("classify")}
