"""Model-free confidence stream: a copy of the program's synthetic camera
traffic, kept here so that the yardstick cannot change with the program.

Copied from ``repro.data.synthetic_video.make_cameras`` and
``repro.system.scenario._query_substream`` /
``synthetic_confidence_stream`` (classify queries only).  The program's
versions stay the system under test; ``tests/test_chipbench_traffic.py``
holds the two to the same items at a small size.

The stream is returned as plain arrays, sorted by arrival time:
``t`` (s), ``camera``, ``edge`` (1..E), ``conf``, ``is_query``, ``query``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NUM_CLASSES = 12
QUERY_CLASS = 3

#: Fig. 5 training scheme -> class-conditional Beta parameters
#: ((query_a, query_b), (other_a, other_b))
SCHEME_BETAS: Dict[str, Tuple[Tuple[float, float], Tuple[float, float]]] = {
    "surveiledge": ((8.0, 2.0), (2.0, 8.0)),
    "all_finetune": ((9.0, 1.5), (1.5, 9.0)),
    "no_finetune": ((4.0, 2.5), (2.5, 4.0)),
}


@dataclasses.dataclass
class Camera:
    cam_id: int
    class_mix: np.ndarray
    busy_period_s: float
    busy_phase: float
    base_rate: float
    busy_boost: float = 3.0


def make_cameras(n: int, seed: int, contexts: int = 2) -> List[Camera]:
    """``n`` cameras over ``contexts`` scene types (road-like, plaza-like)
    with per-camera jitter."""
    rng = np.random.default_rng(seed)
    cams = []
    for i in range(n):
        mix = np.full(NUM_CLASSES, 0.02)
        if i % contexts == 0:                 # road: vehicles dominate
            mix[[1, 3, 4, 6, 9]] += [0.30, 0.16, 0.08, 0.10, 0.08]
        else:                                 # plaza: people dominate
            mix[[2, 5, 7, 10]] += [0.38, 0.12, 0.10, 0.12]
        mix += rng.uniform(0, 0.03, NUM_CLASSES)
        mix /= mix.sum()
        cams.append(Camera(cam_id=i, class_mix=mix,
                           busy_period_s=rng.uniform(90, 180),
                           busy_phase=rng.uniform(0, 2 * np.pi),
                           base_rate=rng.uniform(0.5, 1.2)))
    return cams


def query_substream(cams: Sequence[Camera], num_edges: int, duration_s: float,
                    interval_s: float, rng: np.random.Generator,
                    betas, t0: float, t1: float,
                    arrivals: Optional[np.random.Generator] = None
                    ) -> Dict[str, np.ndarray]:
    """One query's detections: Poisson arrivals per (tick, camera) from
    each camera's busy profile, confidence from the query's Betas, kept
    inside the query's [t0, t1) lifetime (a mask after the draws, so the
    draws do not depend on the lifetime).  ``arrivals``, when given, draws
    the counts, the arrival times and each camera's classes, and ``rng``
    only the order in which a camera's classes are dealt and the
    confidences; absent, ``rng`` draws all, as the program does."""
    arrivals = rng if arrivals is None else arrivals
    (qa, qb), (oa, ob) = betas
    ts = np.arange(0.0, duration_s, interval_s)
    period = np.asarray([c.busy_period_s for c in cams])
    phase = 2 * np.pi * ts[:, None] / period[None, :] \
        + np.asarray([c.busy_phase for c in cams])[None, :]
    rates = np.asarray([c.base_rate for c in cams]) * (
        1.0 + np.asarray([c.busy_boost for c in cams])
        * np.maximum(0.0, np.sin(phase)) ** 2)
    counts = arrivals.poisson(rates * interval_s)
    parts = []
    for j, cam in enumerate(cams):
        n = int(counts[:, j].sum())
        if n == 0:
            continue
        cls = arrivals.choice(NUM_CLASSES, size=n, p=cam.class_mix)
        if arrivals is not rng:
            t = np.repeat(ts, counts[:, j]) + arrivals.uniform(0, interval_s, n)
            keep = (t >= t0) & (t < t1)
            # the same classes for every seed, dealt in the seed's order
            cls[keep] = rng.permutation(cls[keep])
        is_query = cls == QUERY_CLASS
        conf = np.where(is_query, rng.beta(qa, qb, n), rng.beta(oa, ob, n))
        if arrivals is rng:
            t = np.repeat(ts, counts[:, j]) + rng.uniform(0, interval_s, n)
            keep = (t >= t0) & (t < t1)
        parts.append((t[keep], np.full(int(keep.sum()), cam.cam_id),
                      np.full(int(keep.sum()), cam.cam_id % num_edges + 1),
                      conf[keep], is_query[keep]))
    if not parts:
        return _empty()
    t, cam, edge, conf, isq = (np.concatenate(p) for p in zip(*parts))
    return {"t": t, "camera": cam, "edge": edge, "conf": conf,
            "is_query": isq}


def _empty() -> Dict[str, np.ndarray]:
    return {"t": np.zeros(0), "camera": np.zeros(0, np.int64),
            "edge": np.zeros(0, np.int64), "conf": np.zeros(0),
            "is_query": np.zeros(0, bool)}


def stream(cams: Sequence[Camera], num_edges: int, duration_s: float,
           interval_s: float, seed: int,
           queries: Sequence[Tuple[int, float, Optional[float], str]],
           arrivals_seed: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Every query's substream, merged and sorted by arrival time.

    ``queries`` lists ``(query, t_arrive_s, t_retire_s, train_scheme)``;
    empty means the one implicit query 0, live all run.  Each query draws
    from its own generator, seeded ``(seed, 1001 + query)``; with
    ``arrivals_seed`` its counts and arrival times come from a second
    one, seeded ``(arrivals_seed, 1001 + query)``, which also fixes
    how many detections of each class a camera sees."""
    def arrivals(key):
        return None if arrivals_seed is None else \
            np.random.default_rng(key(arrivals_seed))

    if not queries:
        parts = [(0, query_substream(
            cams, num_edges, duration_s, interval_s,
            np.random.default_rng(seed), SCHEME_BETAS["surveiledge"],
            0.0, float("inf"), arrivals(lambda s: s)))]
    else:
        parts = [(q, query_substream(
            cams, num_edges, duration_s, interval_s,
            np.random.default_rng((seed, 1001 + q)), SCHEME_BETAS[scheme],
            t0, float("inf") if t1 is None else t1,
            arrivals(lambda s: (s, 1001 + q))))
            for q, t0, t1, scheme in sorted(queries)]
    out = {k: np.concatenate([p[k] for _, p in parts])
           for k in ("t", "camera", "edge", "conf", "is_query")}
    out["query"] = np.concatenate(
        [np.full(len(p["t"]), q, np.int64) for q, p in parts])
    order = np.argsort(out["t"], kind="stable")
    return {k: v[order] for k, v in out.items()}
