"""The one traffic generator: a cell's ``traffic`` block plus the run's
seed give every call its scenario arguments and, on the confidence
frontend, its detection stream.

Parameters a ``traffic`` block may hold:

``query_book``
    ``"opening"``: ``queries`` queries register over the first 2% of the
    span (an operations centre setting up its book at shift start); the
    last two retire at 95% of it.  ``"churn"``: ``queries`` queries are
    live from the start, and ``swaps`` times per span one retires and a
    fresh one registers, at the middle of each of ``swaps`` equal slices.
    Absent: the configuration's implicit single query.
``streams``
    How many distinct streams set-up draws; calls cycle through them, so
    each differs from the one before it.  Pixel cells render their own
    frames and draw none.
``failures``
    ``[[share, edge], ...]``: edge ``edge`` dies at ``share`` of the span.

Every seed gives the same work: the span, the fleet, the book and the
rates are the cell's; the cameras (rates, busy profiles, class mixes)
are drawn from the configuration's ``topology_seed``, and each stream's
arrivals (how many detections, when, on which camera, for which query)
from that seed and the stream's index.  The run's seed
draws what the detections show: which of a camera's detections is of
which class (each camera sees the same classes under every seed) and
each one's confidence.  So two seeds replay the same detections with
other contents, and a run's work does not change with its seed.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from traffic import confidence

#: (query, t_arrive_s, t_retire_s or None, train_scheme)
Query = Tuple[int, float, Optional[float], str]


def call_seed(seed: int, index: int) -> int:
    """32-bit seed of call ``index`` of a run started with ``seed``
    (index -1 is set-up's warm-up call)."""
    return int(np.random.SeedSequence(
        [seed % (1 << 64), index + 1]).generate_state(1)[0])


def _scheme(q: int) -> str:
    return "no_finetune" if q % 3 == 2 else "surveiledge"


def query_book(traffic: Dict, span_s: float) -> List[Query]:
    kind = traffic.get("query_book")
    if kind is None:
        return []
    n = int(traffic["queries"])
    if kind == "opening":
        return [(q, span_s * 0.02 * q / n,
                 span_s * 0.95 if q >= n - 2 else None, _scheme(q))
                for q in range(n)]
    if kind == "churn":
        swaps = int(traffic["swaps"])
        if swaps > n:
            raise ValueError(f"churn: {swaps} swaps retire more than the "
                             f"{n} queries live at the start")
        at = [(k + 0.5) * span_s / swaps for k in range(swaps)]
        book = [(q, span_s * 0.02 * q / n, at[q] if q < swaps else None,
                 _scheme(q)) for q in range(n)]
        book += [(n + k, at[k], None, _scheme(n + k)) for k in range(swaps)]
        return book
    raise ValueError(f"unknown query_book {kind!r}")


def scenario_args(config: Dict, cell: Dict, seed: int, index: int) -> Dict:
    """Keyword arguments of the configuration's preset for one call."""
    traffic = cell["traffic"]
    span = float(cell["span_s"])
    args = {k: tuple(v) if isinstance(v, list) else v
            for k, v in config["scenario"].items()}
    args["duration_s"] = span
    # settings the configuration states per second of span
    for key, per_s in config.get("per_span_s", {}).items():
        args[key] = per_s * span
    if "failures" in traffic:
        args["failures"] = tuple((share * span, int(edge))
                                 for share, edge in traffic["failures"])
    if config["frontend"] == "pixel":
        # the program renders each camera's frames from the scenario seed
        args["seed"] = call_seed(seed, index)
    else:
        args["seed"] = int(config["topology_seed"])
    return args


def confidence_stream(config: Dict, cell: Dict, seed: int, index: int
                      ) -> Dict[str, np.ndarray]:
    """Call ``index``'s detection stream on the confidence frontend."""
    args = scenario_args(config, cell, seed, index)
    if "burst_rate" in args or "burst_boost" in args:
        raise ValueError("the confidence stream keeps every camera's own "
                         "rates; it has no burst override")
    topology = int(config["topology_seed"])
    cams = confidence.make_cameras(int(args["num_cameras"]), seed=topology)
    num_edges = args.get("num_edges") or len(args["edge_speeds"])
    return confidence.stream(
        cams, int(num_edges), args["duration_s"],
        float(args["interval_s"]), call_seed(seed, index),
        query_book(cell["traffic"], args["duration_s"]),
        arrivals_seed=call_seed(topology, index))
