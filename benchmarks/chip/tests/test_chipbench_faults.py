"""The chip benchmark's comparison fails a broken timed path.

Each test drives a whole run of a cell through ``harness.run`` on the CPU
at a small size (skipping only ``run.py``'s look for a chip), with the
program broken underneath in one way, and sees ``correct`` come out false;
the unbroken program comes out true.  The controls put the reference,
computed in bfloat16, in the program's place."""
import contextlib
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from chipbench import cells as CL  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench import reference as R  # noqa: E402

def _small(name):
    cell = CL.load_cell(name)
    config = CL.load_config(cell["config"])
    if config["frontend"] == "pixel":
        config["frame_hw"] = [96, 128]
        config["classifier"]["warm_buckets"] = [32, 64, 128, 256]
        config["classifier"]["warm_triage_lanes"] = [8, 16, 32]
        cell["span_s"] = 0.12
    else:
        cell["span_s"] = 1.6
        cell["traffic"]["streams"] = 1
    return cell, config


def _run(name):
    import jax
    cell, config = _small(name)
    res = harness.run(cell, config, seed=2**31 + 99, seconds=0.0,
                      trace=False, devices=jax.devices()[:1],
                      peaks=CL.peaks("TPU v5 lite"))
    return res


@contextlib.contextmanager
def _patched(owner, name, wrap):
    real = getattr(owner, name)
    setattr(owner, name, wrap(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def _superstep(change):
    """Break every superstep launch's outputs with ``change``."""
    from repro.system import superstep

    def wrap(real):
        def fn_for(capacity, n_shards):
            fn = real(capacity, n_shards)

            def launch(*args):
                out = [np.array(o) for o in fn(*args)]
                return tuple(change(args, *out))
            return launch
        return fn_for
    return _patched(superstep, "_superstep_fn", wrap)


def _ops(name, change):
    from repro.kernels import ops

    def wrap(real):
        def f(*args, **kw):
            return change(args, kw, real(*args, **kw))
        return f
    return _patched(ops, name, wrap)


def _flip_route(routes):
    routes = routes.copy()
    idx = np.argwhere(routes == 0)[0] if (routes == 0).any() \
        else np.argwhere(routes >= 0)[0]
    routes[tuple(idx)] = 1 if routes[tuple(idx)] != 1 else 0
    return routes


def _control_scan(args, routes, slots, ths):
    conf, th0, mask, drain, gains = (np.asarray(a) for a in args)
    return routes, slots, R.threshold_scan(th0, mask, drain, gains,
                                           control=True).astype(np.float32)


def _unchanged_state(args, routes, slots, ths):
    th0 = np.asarray(args[1])
    return routes, slots, np.broadcast_to(th0, ths.shape).copy()


def _half_batch(args, routes, slots, ths):
    half = routes.shape[1] // 2
    routes[:, half:] = 1
    slots[:, half:] = -1
    return routes, slots, ths


def _dropped_answers():
    """The engine finishes every tenth item without recording it."""
    from repro.system import pipeline

    def wrap(real):
        count = [0]

        def finish(self, *args, **kw):
            count[0] += 1
            if count[0] % 10:
                return real(self, *args, **kw)
        return finish
    return _patched(pipeline.QueryPipeline, "_finish", wrap)


def _inverted_decision():
    """In every call the engine answers the first item an edge decides
    with the opposite of its route's decision."""
    from repro.system import pipeline

    def wrap(real):
        done = set()

        def on_done(self, t, node, task, svc):
            if id(self) not in done and task.phase == "classify" \
                    and task.decision is not None:
                task.decision = not task.decision
                done.add(id(self))
            return real(self, t, node, task, svc)
        return on_done
    return _patched(pipeline.QueryPipeline, "_on_done", wrap)


def _uplink_miscounted():
    """The transport charges one byte more for every upload."""
    from repro.system import transport

    def wrap(real):
        def wan_send(self, t, nbytes):
            return real(self, t, nbytes + 1)
        return wan_send
    return _patched(transport.Transport, "wan_send", wrap)


ENGINE = {
    "answer_dropped": _dropped_answers,
    "decision_inverted": _inverted_decision,
    "uplink_miscounted": _uplink_miscounted,
}

METROPOLIS = {
    **ENGINE,
    "control_bf16_thresholds": lambda: _superstep(_control_scan),
    "state_unchanged": lambda: _superstep(_unchanged_state),
    "half_batch": lambda: _superstep(_half_batch),
    "route_altered": lambda: _superstep(
        lambda a, r, s, t: (_flip_route(r), s, t)),
}


def _control_scores(args, kw, out):
    spec = CL.load_config("ua_detrac_24cam")["classifier"]
    import jax
    score_fn, tokens = args
    weights = jax.tree.map(np.asarray, score_fn.args[0])
    ctl = R.classifier(spec, weights, np.asarray(tokens), control=True)
    return np.asarray(ctl, np.float32)


def _half_scores(args, kw, out):
    out = np.array(out)
    out[len(out) // 2:] = 0.0
    return out


def _altered_score(args, kw, out):
    out = np.array(out)
    out[0] = (out[0] + 0.5) % 1.0
    return out


def _altered_mask(args, kw, out):
    mask, counts = (np.array(o) for o in out)
    mask[0, 0, 0] = 255 - mask[0, 0, 0]
    return mask, counts


def _altered_triage(args, kw, out):
    routes, slots, counts = (np.array(o) for o in out)
    return _flip_route(routes), slots, counts


PIXEL = {
    **ENGINE,
    "control_bf16_classifier": lambda: _ops("score_crops", _control_scores),
    "half_batch": lambda: _ops("score_crops", _half_scores),
    "score_altered": lambda: _ops("score_crops", _altered_score),
    "mask_altered": lambda: _ops("pixel_cascade", _altered_mask),
    "route_altered": lambda: _ops("triage_fleet", _altered_triage),
}


@pytest.mark.parametrize("cell", ["cityflow.steady", "cityflow.churn",
                                  "ua_detrac.motion"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["missing_samples"] == (0, 0.0)


@pytest.mark.parametrize("fault", sorted(METROPOLIS))
def test_broken_superstep_is_not_correct(fault):
    with METROPOLIS[fault]():
        res = _run("cityflow.steady")
    assert not res["correct"], (fault, res["checks"])


@pytest.mark.parametrize("fault", sorted(PIXEL))
def test_broken_pixel_path_is_not_correct(fault):
    with PIXEL[fault]():
        res = _run("ua_detrac.motion")
    assert not res["correct"], (fault, res["checks"])
