"""The comparison that decides ``correct``: what the timed path produced,
sampled from the seed in the window, against the plain reference.

Each number compared has its own limit, stated in the cell's file under
``limits``; a number without one is an error.  Counts of wrong answers
(mask pixels, foreground counts, boxes, tokens, routes, slots, the
engine's decisions, uplink bytes) are held to 0; the classifier's scores
and the superstep's thresholds to the widest gap the cell's limit allows;
the report's F2 and mean latency to the rounding of the arithmetic.

The event engine is held to every answer of every call in the window:
each triaged item's route and slot against the reference's triage on the
row's thresholds, each finished item's decision against what its route
and the node that answered it call for, exactly one answer per triaged
item, the report's F2 against the reference's F2 of the expected
decisions, its mean latency against the items' own, and its uplink bytes
against the bytes of the items the cloud answered.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from chipbench import reference as R
from chipbench.cells import BenchError

#: classifier fields the configuration states and the program must run
_SPEC_FIELDS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
                "head_dim", "d_ff", "vocab_size", "num_query_classes",
                "norm_eps", "rope_theta")


def require_classifier(spec: Dict, cfg) -> None:
    """The program's classifier has the configuration's stated sizes and
    the layer kinds the reference implements."""
    diff = {k: (spec[k], getattr(cfg, k)) for k in _SPEC_FIELDS
            if spec[k] != getattr(cfg, k)}
    kinds = {"norm_type": "rmsnorm", "mlp_act": "silu", "rope_style": "neox",
             "attn_bias": False, "qk_norm": False, "logit_softcap": 0.0,
             "parallel_block": False, "sliding_window": None}
    diff.update({k: (v, getattr(cfg, k)) for k, v in kinds.items()
                 if getattr(cfg, k) != v})
    if diff:
        raise BenchError(f"classifier differs from the configuration "
                         f"(stated, program): {diff}")


def _pixel(cell: Dict, config: Dict, taps, weights) -> Dict[str, float]:
    spec = config["classifier"]
    out = {"mask_px_wrong": 0, "count_wrong": 0, "box_wrong": 0,
           "token_wrong": 0, "score_gap": 0.0}
    scored = 0
    ticks = taps.ticks.sample()
    for rec in ticks:
        fr = np.asarray(rec["frames"])
        m_ref, c_ref = R.pixel_cascade(fr[:, 0], fr[:, 1], fr[:, 2],
                                       rec["threshold"])
        out["mask_px_wrong"] += int((np.asarray(rec["mask"]) != m_ref).sum())
        out["count_wrong"] += int((np.asarray(rec["counts"]) != c_ref).sum())
        ref_boxes = [R.boxes(m_ref[b], rec["min_area"]) if c_ref[b] else []
                     for b in range(fr.shape[0])]
        for per, ref in zip(rec["dets"], ref_boxes):
            got = [(d.box.y0, d.box.x0, d.box.y1, d.box.x1, d.box.area)
                   for d in per]
            out["box_wrong"] += sum(a != b for a, b in zip(got, ref)) \
                + abs(len(got) - len(ref))
        crops = [R.crop(fr[b, 1], box, rec["crop"])
                 for b in range(fr.shape[0]) for box in ref_boxes[b]]
        got_tokens = rec.get("tokens", np.zeros((0, spec["tokens"]), int))
        if not crops:
            out["token_wrong"] += len(got_tokens)
            continue
        tokens = R.crop_tokens(np.stack(crops), spec["vocab_size"])
        if got_tokens.shape != tokens.shape:
            out["token_wrong"] += max(len(got_tokens), len(tokens))
            out["score_gap"] = float("inf")
            continue
        out["token_wrong"] += int((got_tokens != tokens).sum())
        ref = R.classifier(spec, weights, tokens)
        out["score_gap"] = max(out["score_gap"], float(
            np.max(np.abs(np.asarray(rec["scores"], np.float64) - ref))))
        scored += len(tokens)
    missing = int(not ticks) + int(scored == 0)
    return {**out, "missing_samples": missing}


def _superstep(taps) -> Dict[str, float]:
    out = {"route_wrong": 0, "slot_wrong": 0, "threshold_gap": 0.0}
    launches = taps.supersteps.sample()
    for rec in launches:
        conf, th0, mask, drain, gains = rec["args"]
        routes, slots, ths = rec["out"]
        S, Rr, N = conf.shape
        ref_ths = R.threshold_scan(th0, mask, drain, gains)
        out["threshold_gap"] = max(out["threshold_gap"], float(
            np.max(np.abs(np.asarray(ths, np.float64) - ref_ths))))
        r, s = R.triage(conf.reshape(S * Rr, N), ths.reshape(S * Rr, 2),
                        rec["capacity"])
        out["route_wrong"] += int((routes.reshape(S * Rr, N) != r).sum())
        out["slot_wrong"] += int((slots.reshape(S * Rr, N) != s).sum())
    return {**out, "missing_samples": int(not launches)}


def _engine(taps, calls) -> Dict[str, float]:
    out = {"route_wrong": 0, "slot_wrong": 0, "decision_wrong": 0,
           "answers_not_one": 0, "f2_gap": 0.0, "latency_gap": 0.0,
           "uplink_bytes_wrong": 0}
    for routed, finished, call in zip(taps.routed, taps.finished, calls):
        route_of = {}
        for home, items, routes, slots, th, cap in routed:
            conf = np.asarray([it.conf for it in items], np.float32)
            r, s = R.triage(conf, np.asarray(th, np.float32), cap)
            out["route_wrong"] += int((np.asarray(routes) != r).sum())
            out["slot_wrong"] += int((np.asarray(slots) != s).sum())
            for it, ri, si, ci in zip(items, r, s, conf):
                route_of[id(it)] = (int(ri), int(si), float(ci), home)
        if not finished:
            continue
        answers = {}
        for it, *_ in finished:
            answers[id(it)] = answers.get(id(it), 0) + 1
        out["answers_not_one"] += sum(abs(answers.get(k, 0) - 1)
                                      for k in route_of)
        none = (-1, -1, None, None)
        rec = [route_of.get(id(it), none) for it, *_ in finished]
        truth = np.asarray([it.is_query for it, *_ in finished], bool)
        expected = R.expected_decisions(
            route=[r[0] for r in rec], slot=[r[1] for r in rec],
            conf=[it.conf if r[2] is None else r[2]
                  for r, (it, *_) in zip(rec, finished)],
            truth=truth,
            home=[it.edge_device if r[3] is None else r[3]
                  for r, (it, *_) in zip(rec, finished)],
            node=[f[1] for f in finished])
        got = np.asarray([bool(f[2]) for f in finished])
        out["decision_wrong"] += int((got != expected).sum())
        out["f2_gap"] = max(out["f2_gap"], abs(
            call["f2"] - R.f_score(expected, truth)))
        lat = np.mean([f[3] - f[0].t_arrival for f in finished])
        out["latency_gap"] = max(out["latency_gap"],
                                 abs(call["latency_mean"] - float(lat)))
        cloud = sum(f[0].nbytes for f in finished if f[1] == R.CLOUD)
        out["uplink_bytes_wrong"] += abs(call["uploaded_bytes"] - cloud)
    return out


def control_numbers(config: Dict, taps, weights) -> Dict[str, float]:
    """The compared gaps of the control: the reference computed in
    bfloat16, put in the program's place, on the inputs of the same
    sampled calls (the classifier's crops, the superstep's slabs)."""
    out: Dict[str, float] = {}
    if config["frontend"] == "pixel":
        spec, gap = config["classifier"], 0.0
        for rec in taps.ticks.sample():
            fr = np.asarray(rec["frames"])
            m_ref, c_ref = R.pixel_cascade(fr[:, 0], fr[:, 1], fr[:, 2],
                                           rec["threshold"])
            crops = [R.crop(fr[b, 1], box, rec["crop"])
                     for b in range(fr.shape[0]) if c_ref[b]
                     for box in R.boxes(m_ref[b], rec["min_area"])]
            if crops:
                t = R.crop_tokens(np.stack(crops), spec["vocab_size"])
                gap = max(gap, float(np.max(np.abs(
                    R.classifier(spec, weights, t, control=True)
                    - R.classifier(spec, weights, t)))))
        out["score_gap"] = gap
    for rec in taps.supersteps.sample():
        conf, th0, mask, drain, gains = rec["args"]
        gap = float(np.max(np.abs(
            R.threshold_scan(th0, mask, drain, gains, control=True)
            - R.threshold_scan(th0, mask, drain, gains))))
        out["threshold_gap"] = max(out.get("threshold_gap", 0.0), gap)
    return out


def numbers(cell: Dict, config: Dict, taps, weights, calls
            ) -> Dict[str, float]:
    """Every number this cell compares, by name."""
    if config["frontend"] == "pixel":
        a = _pixel(cell, config, taps, weights)
    elif config["scenario"].get("superstep"):
        a = _superstep(taps)
    else:
        a = {"missing_samples": 0}
    b = _engine(taps, calls)
    for k in ("route_wrong", "slot_wrong"):
        b[k] += a.pop(k, 0)
    return {**a, **b}


def compare(cell: Dict, config: Dict, taps, weights, calls
            ) -> Dict[str, Tuple[float, float]]:
    """name -> (number, limit).  Besides the sampled answers, every
    detection each call was due to answer has to be answered:
    ``unanswered`` sums, over the window's calls, the gap between the
    detections a call was given (or, on the pixel path, produced) and the
    items its report finished."""
    limits = cell["limits"]
    got = numbers(cell, config, taps, weights, calls)
    got["unanswered"] = sum(abs(c["due"] - c["items"]) for c in calls)
    missing = sorted(set(got) - set(limits))
    if missing:
        raise BenchError(f"cell {cell['name']!r} states no limit for "
                         f"{missing}")
    return {k: (v, float(limits[k])) for k, v in got.items()}
