"""Every cell, configuration and metric of the chip benchmark is a file of
its own, found by name, and BENCHMARK.json agrees with them."""
import glob
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from chipbench import cells as CL  # noqa: E402

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _names(sub, ext):
    return sorted(os.path.basename(p)[:-len(ext)]
                  for p in glob.glob(os.path.join(BENCH, sub, "*" + ext)))


@pytest.mark.parametrize("name", _names("workloads", ".json"))
def test_workload_loads_with_its_config(name):
    cell = CL.load_cell(name)
    config = CL.load_config(cell["config"])
    assert cell["chips"] in (1, 4)
    assert cell["span_s"] > 0 and cell["traffic"]["name"]
    assert set(cell["limits"]) >= {"missing_samples"}
    assert config["frontend"] in ("pixel", "confidence")


@pytest.mark.parametrize("name", _names("configs", ".json"))
def test_config_states_source_and_cuts(name):
    config = CL.load_config(name)
    assert config["source"] and isinstance(config["reduced"], list)
    assert config["guarantees"]
    for key in config["reduced"]:
        assert key in config["scenario"] and key in config["published"]


@pytest.mark.parametrize("name", _names("metrics", ".py"))
def test_metric_reader_loads(name):
    read = CL.reader(name)
    assert callable(read)


def test_benchmark_json_names_existing_files():
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for c in configs.values():
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == CL.load_config(c["name"])["reduced"]
    for w in BENCHMARK["workloads"]:
        cell = CL.load_cell(w["name"])
        assert cell["config"] == w["config"] in configs
        assert cell["chips"] == w["chips"]
        assert cell["traffic"]["name"] == w["traffic"]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        CL.reader(m["name"])
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for w in cells:
        assert CL.metrics_of(w, "per_layer", BENCHMARK)
        assert {m["name"] for m in CL.metrics_of(w, "end_to_end",
                                                  BENCHMARK)} >= {"setup_s"}


def test_unknown_names_and_devices_are_errors():
    with pytest.raises(CL.BenchError):
        CL.load_cell("no_such_cell")
    with pytest.raises(CL.BenchError):
        CL.reader("no_such_metric")
    with pytest.raises(CL.BenchError):
        CL.peaks("cpu")
    assert CL.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("b,h,w", [(12, 96, 128), (12, 540, 960),
                                   (4, 1080, 1920)])
def test_pixel_cascade_count_matches_the_program(b, h, w):
    """The program counts the same traffic at int32, four times the uint8
    bytes the operation needs, plus one int32 count per camera here."""
    from counts import pixel_cascade
    from repro.launch.roofline import pixel_cascade_roofline
    nbytes, ops = pixel_cascade.cost(b, h, w)
    theirs = pixel_cascade_roofline(b, h, w, fused=True)
    assert ops == theirs.flops
    assert nbytes == theirs.hbm_bytes / 4 + 4 * b


def test_classifier_flops_match_a_dense_count():
    from counts import classifier
    spec = CL.load_config("ua_detrac_24cam")["classifier"]
    D, F, hd, H = 256, 512, 64, 4
    T = 16
    per_layer = T * (2 * D * 3 * H * hd + 2 * H * hd * D + 2 * 3 * D * F) \
        + 2 * 2 * T * T * H * hd
    want = 2 * per_layer + 2 * D * 2
    assert classifier.forward_flops(spec, 1, T) == want
