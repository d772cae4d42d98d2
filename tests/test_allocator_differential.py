"""The Eq. 7 allocator against a plain copy of its earlier form.

The earlier ``LatencyEstimator`` recomputed its lognormal long-period term
on every ``predict()`` and always ran 80 bisection steps; the earlier
``Scheduler`` refreshed a single-edge Eqs. 8-9 ``ThresholdState`` on every
enqueue and completion.  Patched back in, both must give the same report
and the same serving-simulator threshold trajectory, bit for bit."""
import dataclasses
from typing import Collection, Dict, List, Optional

import numpy as np
import pytest

import repro.core.scheduler as S
import repro.serving.simulator as SIM
import repro.system.pipeline as P
from repro.core.thresholds import ThresholdState
from repro.serving.simulator import CloudEdgeSim, Item, LinkSpec, NodeSpec
from repro.system import QuerySpec, Scenario, run_query, straggler_edge
from test_latency_estimator import _PlainEstimator


@dataclasses.dataclass
class _PlainNodeInfo:
    node_id: int
    queue_len: int = 0
    up: bool = True
    estimator: _PlainEstimator = dataclasses.field(
        default_factory=_PlainEstimator)

    @property
    def t(self) -> float:
        return self.estimator.predict()

    @property
    def drain_time(self) -> float:
        return self.queue_len * self.t


class _PlainScheduler:
    def __init__(self, nodes: List[int], interval_s: float = 1.0,
                 thresholds: Optional[ThresholdState] = None):
        self.nodes: Dict[int, _PlainNodeInfo] = {
            n: _PlainNodeInfo(n) for n in nodes}
        self.thresholds = thresholds or ThresholdState()
        self.interval_s = interval_s

    def select_node(self, exclude_cloud: bool = False,
                    exclude: Collection[int] = (),
                    extra_cost: Optional[Dict[int, float]] = None) -> int:
        best, best_cost = None, float("inf")
        for nid in sorted(self.nodes):
            n = self.nodes[nid]
            if exclude_cloud and nid == S.CLOUD:
                continue
            if nid in exclude or not n.up:
                continue
            cost = n.queue_len * n.t
            if extra_cost:
                cost += extra_cost.get(nid, 0.0)
            if cost < best_cost:
                best, best_cost = nid, cost
        if best is None:
            raise ValueError("no eligible node (all excluded or down)")
        return best

    def slo_pressure(self, weight, slack_s, base_extra=None):
        base = base_extra or {}
        if weight <= 0.0:
            return base
        out = dict(base)
        for nid, n in self.nodes.items():
            if not n.up:
                continue
            over = n.drain_time + base.get(nid, 0.0) - slack_s
            if over > 0.0:
                out[nid] = out.get(nid, 0.0) + weight * over
        return out

    def mark_down(self, node_id: int) -> None:
        self.nodes[node_id].up = False

    def mark_up(self, node_id: int) -> None:
        self.nodes[node_id].up = True

    def on_enqueue(self, node_id: int) -> None:
        self.nodes[node_id].queue_len += 1
        self._refresh_thresholds(node_id)

    def on_complete(self, node_id: int, latency_s: float) -> None:
        n = self.nodes[node_id]
        n.queue_len = max(0, n.queue_len - 1)
        n.estimator.observe(latency_s)
        self._refresh_thresholds(node_id)

    def _refresh_thresholds(self, node_id: int) -> None:
        n = self.nodes[node_id]
        self.thresholds = self.thresholds.update(
            n.queue_len, n.t, self.interval_s)


@pytest.fixture
def plain_allocator(monkeypatch):
    """Swap the earlier estimator and scheduler in where they are built."""
    def apply():
        monkeypatch.setattr(S, "LatencyEstimator", _PlainEstimator)
        monkeypatch.setattr(S, "NodeInfo", _PlainNodeInfo)
        monkeypatch.setattr(S, "Scheduler", _PlainScheduler)
        monkeypatch.setattr(P, "Scheduler", _PlainScheduler)
        monkeypatch.setattr(SIM, "_CascadeScheduler", _PlainScheduler)
    return apply


def _cityflow_like(duration_s: float = 3.2) -> Scenario:
    """40 cameras on 10 edges at 10 Hz: 24 queries registering over the
    first 2% of the span, two retiring at 95%, edge 3 failing at 4%."""
    queries = tuple(
        QuerySpec(q, t_arrive_s=duration_s * 0.02 * q / 24,
                  t_retire_s=duration_s * 0.95 if q >= 22 else None,
                  train_scheme="no_finetune" if q % 3 == 2
                  else "surveiledge")
        for q in range(24))
    return Scenario(
        name="cityflow_like", num_cameras=40,
        edge_speeds=(1.0, 0.5, 1.0, 1.0, 1.0, 0.5, 1.0, 2.0, 0.5, 1.0),
        duration_s=duration_s, interval_s=0.1,
        failures=((duration_s * 0.04, 3),), queries=queries,
        escalation_capacity=8, edge_service_s=0.05, uplink_MBps=16.0,
        downlink_MBps=2000.0, lan_MBps=100.0, cloud_speedup=80.0,
        cq_nbytes=32 * 1024, train_step_s=duration_s / 4000.0,
        superstep=16)


def _assert_same_report(a, b):
    assert a.summary() == b.summary()
    assert a.per_node_served == b.per_node_served
    assert a.thresholds == b.thresholds
    assert a.escalated == b.escalated
    assert a.uploaded_bytes == b.uploaded_bytes
    for name in ("latencies", "decisions", "truths", "finish_times",
                 "query_ids"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    assert sorted(a.queue_timeline) == sorted(b.queue_timeline)
    for nid, q in a.queue_timeline.items():
        np.testing.assert_array_equal(q, b.queue_timeline[nid])
    assert a.per_node_busy == b.per_node_busy


@pytest.mark.parametrize("make, fused", [
    pytest.param(_cityflow_like, True, id="cityflow_like_supersteps"),
    pytest.param(lambda: straggler_edge(duration_s=45.0), False,
                 id="straggler_edge_per_tick"),
])
def test_run_query_matches_plain_allocator(make, fused, plain_allocator):
    sc = make()
    new = run_query(sc)
    assert new.n_items > 0 and new.estimator_refits > 0
    assert (new.supersteps > 0) == fused
    plain_allocator()
    _assert_same_report(new, run_query(sc))


def test_refit_counters_on_a_cityflow_like_run():
    r = run_query(_cityflow_like())
    assert r.estimator_refits > 0
    assert 0 < r.refit_bisect_steps < 80 * r.estimator_refits


def _sim_items(n=600, seed=11):
    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0.0, 90.0, n))
    conf = rng.uniform(0.0, 1.0, n)
    truth = conf + rng.normal(0.0, 0.2, n) > 0.5
    return [Item(t_arrival=float(t[i]), camera=int(i % 6),
                 edge_device=int(i % 6) // 2 + 1, conf=float(conf[i]),
                 is_query=bool(truth[i])) for i in range(n)]


def _sim_trajectory(scheme):
    """Every (alpha, beta) the simulator's thresholds take, in order."""
    sim = CloudEdgeSim([NodeSpec(i, service_s=0.30) for i in (1, 2, 3)],
                       NodeSpec(0, service_s=0.05),
                       LinkSpec(uplink_MBps=0.5), scheme=scheme, seed=2)
    seen = []
    for name in ("on_enqueue", "on_complete"):
        inner = getattr(sim.sched, name)

        def hook(*args, _inner=inner):
            _inner(*args)
            seen.append((sim.sched.thresholds.alpha,
                         sim.sched.thresholds.beta))
        setattr(sim.sched, name, hook)
    res = sim.run(_sim_items())
    return seen, res


@pytest.mark.parametrize("scheme", ["surveiledge", "surveiledge_fixed"])
def test_simulator_threshold_trajectory_unchanged(scheme, plain_allocator):
    new, rn = _sim_trajectory(scheme)
    assert len(new) > 600
    plain_allocator()
    old, ro = _sim_trajectory(scheme)
    assert new == old
    np.testing.assert_array_equal(rn.latencies, ro.latencies)
    np.testing.assert_array_equal(rn.decisions, ro.decisions)
    assert rn.uploaded_bytes == ro.uploaded_bytes
