"""The chip benchmark's copied confidence generator yields the program's
items, and its query books keep their sizes under every seed."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from traffic import confidence, generator  # noqa: E402


def _program(sc):
    from repro.system import synthetic_confidence_stream
    items = synthetic_confidence_stream(sc)
    return {"t": np.asarray([it.t_arrival for it in items]),
            "camera": np.asarray([it.camera for it in items]),
            "edge": np.asarray([it.edge_device for it in items]),
            "conf": np.asarray([it.conf for it in items]),
            "is_query": np.asarray([it.is_query for it in items]),
            "query": np.asarray([it.query for it in items])}


@pytest.mark.parametrize("with_queries", [False, True])
def test_copied_generator_matches_the_program(with_queries):
    from repro.system import QuerySpec, Scenario
    book = [(0, 0.0, None, "surveiledge"), (1, 2.0, 7.5, "no_finetune"),
            (2, 1.0, None, "all_finetune")] if with_queries else []
    sc = Scenario(name="t", num_cameras=6, duration_s=10.0,
                  edge_speeds=(1.0, 0.5, 1.0), seed=5,
                  queries=tuple(QuerySpec(q, t_arrive_s=a, t_retire_s=r,
                                          train_scheme=s)
                                for q, a, r, s in book))
    cams = confidence.make_cameras(6, seed=5)
    ours = confidence.stream(cams, 3, 10.0, 1.0, 5, book)
    theirs = _program(sc)
    assert len(theirs["t"]) > 20
    for k in ours:
        np.testing.assert_array_equal(ours[k], theirs[k], err_msg=k)


def test_churn_book_swaps_inside_the_span():
    book = generator.query_book(
        {"query_book": "churn", "queries": 24, "swaps": 12}, 12.8)
    assert len(book) == 36
    live = [sum(1 for q, a, r, _ in book
                if a <= t and (r is None or t < r)) for t in
            np.linspace(0.3, 12.7, 50)]
    assert min(live) == 24 and max(live) == 24
    retires = sorted(r for _, _, r, _ in book if r is not None)
    assert len(retires) == 12 and retires[-1] < 12.8


def test_seeds_draw_arrivals_not_sizes():
    cfg = {"frontend": "confidence", "topology_seed": 0,
           "scenario": {"num_cameras": 64, "num_edges": 8,
                        "interval_s": 0.1}}
    cell = {"span_s": 2.0, "traffic": {"query_book": "opening",
                                       "queries": 4}}
    a = generator.confidence_stream(cfg, cell, 2**31 + 7, 0)
    b = generator.confidence_stream(cfg, cell, 2**31 + 7, 1)
    c = generator.confidence_stream(cfg, cell, 2**31 + 7, 0)
    np.testing.assert_array_equal(a["conf"], c["conf"])
    assert not np.array_equal(a["conf"][:50], b["conf"][:50])
    assert generator.scenario_args(cfg, cell, 1, 0) == \
        generator.scenario_args(cfg, cell, 2, 3)
    assert abs(len(a["t"]) - len(b["t"])) < 0.2 * len(a["t"])


def test_failures_and_per_span_settings_follow_the_span():
    cfg = {"frontend": "confidence", "topology_seed": 0,
           "scenario": {"name": "t", "num_cameras": 8, "interval_s": 0.1,
                        "edge_speeds": [1.0, 0.5]},
           "per_span_s": {"metrics_window_s": 0.25}}
    cell = {"span_s": 4.0, "traffic": {"failures": [[0.5, 2]]}}
    args = generator.scenario_args(cfg, cell, 7, 0)
    assert args["failures"] == ((2.0, 2),)
    assert args["metrics_window_s"] == 1.0
    assert args["edge_speeds"] == (1.0, 0.5)
    cfg["scenario"]["burst_rate"] = 0.1
    with pytest.raises(ValueError, match="burst"):
        generator.confidence_stream(cfg, cell, 7, 0)


@pytest.mark.parametrize("book", [
    {"query_book": "opening", "queries": 4},
    {"query_book": "churn", "queries": 4, "swaps": 2}])
def test_seeds_replay_the_same_arrivals(book):
    """Two seeds give a stream the same detections (times, cameras,
    edges, queries) and each camera the same classes, dealt in another
    order, with other confidences, so a run's work does not change with
    its seed."""
    cfg = {"frontend": "confidence", "topology_seed": 0,
           "scenario": {"num_cameras": 32, "num_edges": 4,
                        "interval_s": 0.1}}
    cell = {"span_s": 2.0, "traffic": book}
    a = generator.confidence_stream(cfg, cell, 2**31 + 7, 0)
    b = generator.confidence_stream(cfg, cell, 3 * 2**31 + 11, 0)
    for k in ("t", "camera", "edge", "query"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert len(a["t"]) > 100
    assert not np.array_equal(a["conf"], b["conf"])
    assert not np.array_equal(a["is_query"], b["is_query"])
    for cam in np.unique(a["camera"]):
        assert a["is_query"][a["camera"] == cam].sum() == \
            b["is_query"][b["camera"] == cam].sum()
    other = generator.confidence_stream(cfg, cell, 2**31 + 7, 1)
    assert not np.array_equal(a["t"][:50], other["t"][:50])
