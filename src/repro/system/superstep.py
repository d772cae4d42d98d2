"""Scan-superstep driver: K scheduler ticks fused into ONE jitted launch.

The per-tick driver (``pipeline._on_tick`` + ``triage.triage_tick``) pays
one host->device round trip per scheduler tick: pack the tick's
(query, edge) batches, launch the fused triage kernel, pull the routes
back.  At metropolis scale (>=1024 edges, ~10k cameras, dozens of live
queries, 10 Hz ticks) the host loop — not the kernel — is the bottleneck.

This module fuses runs of consecutive ticks into one device program:

  host (numpy)                      device (ONE jit per superstep)
  ------------                      ------------------------------
  segment the event queue into      lax.scan over the tick axis:
  boundary-free runs of ticks;        Eqs. 8-9 threshold update per
  pack a (S, R, N) confidence         (query, edge) row (masked to the
  slab over the run's ACTIVE          ticks where the row had items)
  (query, edge) keys; apply live    then ONE row-folded
  Platt calibration per row           ``triage_fleet_pallas`` launch
  (feedback.calibrate_row)            over all S*R rows
  fold routes/slots/thresholds      <- (S, R, N) routes/slots,
  back into per-tick plans             (S, R, 2) per-tick thresholds

Axes: S = ticks in the run (<= scenario.superstep), R = |union of
(query, edge) keys with >=1 ready item in the run| — the fleet's
(Q, E) grid is ~99.8% empty per tick at metropolis scale, so the slab
is packed over active keys, not the dense grid.  R is the axis
``distributed.sharding.fleet_specs`` shards across devices (rows are
mutually independent; the kernel runs shard-local with no collectives).

Correctness contract (the differential harness in
``tests/test_superstep.py`` enforces all of it bit-exactly):

* **Boundaries split supersteps, never the reverse.**  A superstep may
  only cover ticks that process strictly before the next queued
  ``events.BOUNDARY_EVENTS`` time — those events mutate state the fused
  math reads (query/node liveness, calibrations, control signals).  No
  boundary event is ever created by pure tick/DES flow, so
  ``EventQueue.next_boundary()`` is always known at plan time.
* **K-invariance.**  The run's control signals (Eq. 7 escalation-target
  drain, per-edge queue drains, the overload-shed set) are sampled once
  at the first triaged tick after each boundary and held until the next
  one — by the *pipeline*, independent of K — so any segmentation of a
  boundary-free run produces bit-identical decisions, thresholds and
  latencies.  ``superstep=1`` is therefore a per-tick reference driver
  for any ``superstep=K``, which is exactly what the differential tests
  compare.
* **Threshold arithmetic is f32 end to end.**  The scan carries (alpha,
  beta) in f32; the host write-back stores the f32 values (f32 -> f64
  -> f32 round trips are exact), so splitting a run at any point does
  not change the trajectory.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Dict, FrozenSet, List, Tuple

import numpy as np

from repro.kernels.buckets import MAX_SUPERSTEP_ELEMS, bucket
from repro.serving.simulator import Item
from repro.system.feedback import calibrate_row

#: a (query, edge) pair — the row key of the packed slab
Key = Tuple[int, int]
#: per-tick triage outputs: key -> (routes, slots, conf_used), trimmed
TickOuts = Dict[Key, Tuple[np.ndarray, np.ndarray, np.ndarray]]
#: per-tick post-update thresholds: key -> (alpha, beta)
TickThs = Dict[Key, Tuple[float, float]]


@dataclasses.dataclass(frozen=True)
class Ctrl:
    """Boundary-held control signals (sampled by ``pipeline._sample_ctrl``
    at the first triaged tick after each boundary event, constant until
    the next boundary).

    ``esc_drain`` is the Eq. 7 escalation-target drain (incl. WAN backlog
    when the target is the cloud); ``edge_drain`` each edge's own queue
    drain; ``overloaded`` the edges whose drain exceeds the shed gate."""
    esc_drain: float
    edge_drain: Dict[int, float]
    overloaded: FrozenSet[int]


@functools.lru_cache(maxsize=None)
def _superstep_fn(capacity: int, n_shards: int):
    """One compiled superstep program per (capacity, shard count).

    Shapes retrace inside the returned jit (bucket padding keeps the set
    small).  ``n_shards > 1`` wraps the body in a ``shard_map`` over the
    1-D fleet mesh — the row axis R splits across devices; each shard
    runs the scan and the triage kernel on its own rows (no collectives,
    bit-exact vs. the unsharded program)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import triage as _tr

    def body(conf, th0, mask, drain, gains):
        # gains = [gamma1, gamma1_up (== gamma1 when unset), gamma2,
        #          interval_s]; all rows share them (TriageStage builds
        # every state from one prototype).
        g1, g1u, g2, interval = gains[0], gains[1], gains[2], gains[3]
        gain = jnp.where(drain >= interval, g1, g1u)

        def step(th, m):
            # Eqs. 8-9 on every row, applied only where the row had
            # items this tick (mask) — rows hold otherwise, exactly like
            # the per-tick driver's refresh(ready-keys-only).
            alpha = jnp.clip(th[:, 0] - gain * (drain - interval),
                             0.5, 1.0)
            new = jnp.stack([alpha, g2 * (1.0 - alpha)], axis=-1)
            th = jnp.where(m[:, None], new, th)
            return th, th

        with jax.named_scope("superstep"):
            _, ths = jax.lax.scan(step, th0, mask)      # (S, R, 2)
        S, R, N = conf.shape
        # no scope here: a scope around a Pallas call names its HLO
        # custom call, and this one's device events are ``body.1``
        routes, slots, _ = _tr.triage_fleet_pallas(
            conf.reshape(S * R, N), ths.reshape(S * R, 2),
            capacity=capacity)
        return routes.reshape(S, R, N), slots.reshape(S, R, N), ths

    if n_shards > 1:
        from repro.distributed.sharding import fleet_specs
        from repro.launch.mesh import make_fleet_mesh

        sp = fleet_specs()
        body = jax.shard_map(
            body, mesh=make_fleet_mesh(n_shards),
            in_specs=(sp["conf"], sp["thresholds"], sp["mask"],
                      sp["drain"], sp["gains"]),
            out_specs=(sp["routes"], sp["slots"], sp["ths_out"]),
            # the pallas launch has no varying-axes rule; rows are
            # independent so shard-local execution IS the semantics
            check_vma=False)
    return jax.jit(body)


class SuperstepDriver:
    """Plans and executes scan-supersteps for one pipeline run.

    The pipeline calls ``tick_out`` from ``_on_tick`` for every tick
    with ready work.  On a plan miss the driver greedily accumulates the
    current tick plus future arrival ticks — stopping at the scenario's
    K, at the next event boundary, or at the element cap — executes the
    fused program ONCE, and caches each covered tick's outputs; the
    following ticks of the run then pop their slice with no device work.
    """

    def __init__(self, pipe):
        self.pipe = pipe
        sc = pipe.sc
        self.sc = sc
        self.enabled = (sc.superstep is not None
                        and sc.scheme in ("surveiledge",
                                          "surveiledge_fixed"))
        self.k = max(1, int(sc.superstep or 1))
        self.supersteps = 0
        self.n_shards = 1
        if self.enabled and sc.shard_fleet:
            import jax
            self.n_shards = max(1, jax.device_count())
        self._plans: Dict[int, Tuple[TickOuts, TickThs]] = {}

    # --- per-tick entry point -------------------------------------------------
    def tick_out(self, tick: int, ready: Dict[Key, List[Item]],
                 ctrl: Ctrl) -> Tuple[TickOuts, TickThs]:
        """This tick's (routes, slots, conf_used) per key + the per-key
        post-update thresholds.  ``ready`` is the tick's PRE-shed ready
        map (threshold updates and db snapshots cover keys the shed then
        drops, matching the per-tick driver's ordering)."""
        plan = self._plans.pop(tick, None)
        if plan is None:
            with self.pipe.spans.span("triage"):
                self._build(tick, ready, ctrl)
            plan = self._plans.pop(tick)
        return plan

    # --- planning + one fused launch ------------------------------------------
    def _build(self, k0: int, ready0: Dict[Key, List[Item]],
               ctrl: Ctrl) -> None:
        pipe, sc = self.pipe, self.sc
        spans = pipe.spans
        adaptive = sc.scheme == "surveiledge"
        shed = ctrl.overloaded if adaptive else frozenset()

        # Greedy segmentation: the current tick always belongs to its
        # own superstep; future arrival ticks join while (a) the run
        # stays under K triaged ticks, (b) the tick processes STRICTLY
        # before the next boundary event (conservative: a boundary at
        # the exact tick boundary cuts the run — cutting early is always
        # bit-exact, absorbing an event never is), and (c) the padded
        # slab stays under the element cap.  Ticks whose pure
        # classification comes back empty are skipped, not counted: the
        # pipeline never asks for a plan on an empty tick.
        with spans.span("triage.plan"):
            next_boundary = pipe.events.next_boundary()
            ticks = [k0]
            readies = [ready0]
            keys = set(ready0)
            max_n = max(len(v) for v in ready0.values())
            order = pipe._tick_order
            i = bisect.bisect_right(order, k0)
            while len(ticks) < self.k and i < len(order):
                k = order[i]
                if (k + 1) * sc.interval_s >= next_boundary - 1e-9:
                    break
                i += 1
                ready = pipe._ready_of(pipe._tick_batches[k])
                if not ready:
                    continue
                cand_keys = keys | set(ready)
                cand_n = max(max_n, max(len(v) for v in ready.values()))
                if (bucket(len(ticks) + 1, 1) * bucket(len(cand_keys))
                        * bucket(cand_n)) > MAX_SUPERSTEP_ELEMS:
                    break
                ticks.append(k)
                readies.append(ready)
                keys, max_n = cand_keys, cand_n

        # pack the slab over the run's active keys only
        with spans.span("triage.pack"):
            keys_sorted = sorted(keys)
            ki = {key: r for r, key in enumerate(keys_sorted)}
            S, R = len(ticks), len(keys_sorted)
            Sb, Rb, Nb = bucket(S, 1), bucket(R), bucket(max_n)
            conf = np.full((Sb, Rb, Nb), -1.0, np.float32)
            mask = np.zeros((Sb, Rb), bool)
            th0 = np.tile(np.asarray([1.0, 0.0], np.float32), (Rb, 1))
            drain = np.zeros(Rb, np.float32)
            stage = pipe.triage_stage
            for r, key in enumerate(keys_sorted):
                st = stage.states[key]
                th0[r] = (st.alpha, st.beta)
                if adaptive:
                    drain[r] = max(ctrl.edge_drain[key[1]], ctrl.esc_drain)
            for s, ready in enumerate(readies):
                for key, items in ready.items():
                    r = ki[key]
                    if adaptive:
                        mask[s, r] = True
                    if key[1] in shed:
                        continue        # row stays pad: outputs never read
                    row = conf[s, r]
                    row[:len(items)] = [it.conf for it in items]
                    calibrate_row(row, len(items), stage.calibrations[key])
            proto = next(iter(stage.states.values()))
            g1u = proto.gamma1 if proto.gamma1_up is None \
                else proto.gamma1_up
            gains = np.asarray([proto.gamma1, g1u, proto.gamma2,
                                sc.interval_s], np.float32)

        with spans.span("triage.launch"):
            n_shards = self.n_shards if Rb % self.n_shards == 0 else 1
            fn = _superstep_fn(sc.escalation_capacity, n_shards)
            routes, slots, ths = (np.asarray(a) for a in
                                  fn(conf, th0, mask, drain, gains))
        stage.launches += 1
        self.supersteps += 1

        with spans.span("triage.fold"):
            # fold back into per-tick plans
            for s, (k, ready) in enumerate(zip(ticks, readies)):
                outs: TickOuts = {}
                ths_k: TickThs = {}
                for key, items in ready.items():
                    r = ki[key]
                    if adaptive:
                        ths_k[key] = (float(ths[s, r, 0]),
                                      float(ths[s, r, 1]))
                    if key[1] not in shed:
                        n = len(items)
                        outs[key] = (routes[s, r, :n], slots[s, r, :n],
                                     conf[s, r, :n])
                self._plans[k] = (outs, ths_k)

            # write the end-of-run thresholds back so the next superstep
            # (or the end-of-run report) starts where this one ended.
            # ONLY the adaptive scheme: the fixed scheme never refreshes,
            # and writing f32-cast copies would perturb its frozen f64
            # (alpha, beta).
            if adaptive:
                for r, key in enumerate(keys_sorted):
                    stage.states[key] = dataclasses.replace(
                        stage.states[key],
                        alpha=float(ths[S - 1, r, 0]),
                        beta=float(ths[S - 1, r, 1]))
