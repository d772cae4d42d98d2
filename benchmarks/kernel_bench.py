"""Kernel microbenchmarks: Pallas kernels vs their pure-jnp oracles.

The backend decides what the Pallas rows are: compiled kernels on a TPU,
the Pallas interpreter on a CPU (``repro.kernels.runtime``).  Row names
carry that substrate, so an interpreted or CPU timing never appears under
a ``*_compiled`` name; the oracle rows are XLA on whatever backend ran.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.runtime import resolve_interpret
from repro.launch.roofline import pixel_cascade_roofline
from repro.system import PixelFrontend, pixel_city, synthetic_confidence_stream
from repro.system.superstep import _superstep_fn

#: committed snapshot of the superstep scaling table (regenerated by every
#: `python -m benchmarks.kernel_bench` run; commit the refresh when the
#: driver changes)
BENCH_SUPERSTEPS = os.path.join(os.path.dirname(__file__),
                                "BENCH_supersteps.json")

#: committed fused-vs-staged pixel-cascade trajectory (see benchmarks/README)
BENCH_PIXEL_CASCADE = os.path.join(os.path.dirname(__file__),
                                   "BENCH_pixel_cascade.json")

#: (fleet width, H, W) grid of the pixel-cascade benchmark
PIXEL_SHAPES = ((4, 96, 128), (16, 96, 128), (4, 192, 256), (16, 192, 256))


def _pallas_mode() -> str:
    """``compiled`` on a TPU backend, ``interpret`` on the CPU one."""
    return "interpret" if resolve_interpret(None) else "compiled"


def _time(fn, *args, n=10, **kw):
    fn(*args, **kw)
    r = fn(*args, **kw)
    jax.tree.map(lambda a: a.block_until_ready()
                 if hasattr(a, "block_until_ready") else a, r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args, **kw)
    jax.tree.map(lambda a: a.block_until_ready()
                 if hasattr(a, "block_until_ready") else a, r)
    return (time.perf_counter() - t0) / n * 1e6     # us


def pixel_cascade_bench(verbose: bool = True, out_path: str = None):
    """Fused-vs-staged pixel frontend across frame sizes and fleet widths.

    Three row families per (B, H, W), with ``<mode>`` the backend's Pallas
    mode (``compiled`` on TPU, ``interpret`` on CPU):

      * ``staged_<mode>`` — the original three Pallas launches
        (framediff, dilate, erode) + jnp mask reduction.
      * ``fused_<mode>`` — ONE fused band-pipelined launch.
      * ``fused_xla_ref`` — the XLA-jitted reference twin of the same
        fused math (substrate ``xla_ref``, no Pallas launch).

    Each shape also records the analytic per-kernel roofline fractions
    (``launch/roofline.pixel_cascade_roofline``): same FLOPs by
    construction, the staged chain paying 4 extra full-frame HBM passes.
    The payload names the device it ran on.  Writes the committed
    ``BENCH_pixel_cascade.json`` snapshot.
    """
    mode = _pallas_mode()
    pallas_sub = f"pallas_{mode}"
    shapes = {}
    for (B, H, W) in PIXEL_SHAPES:
        f = [jax.random.randint(jax.random.PRNGKey(40 + i), (B, H, W, 3),
                                0, 256, dtype=jnp.int32) for i in range(3)]
        px = B * H * W
        n = 3 if px <= 4 * 96 * 128 else 2

        us_staged = _time(ops.pixel_cascade, *f, threshold=40, fused=False,
                          n=n)
        us_fused = _time(ops.pixel_cascade, *f, threshold=40, n=n)
        us_ref = _time(ops.pixel_cascade, *f, threshold=40,
                       use_pallas=False, n=n)

        def _row(us, substrate, launches):
            return {"us_per_call": round(us, 1),
                    "Mpx_s": round(px / us, 3),
                    "substrate": substrate, "pallas_launches": launches}

        key = f"B{B}_{H}x{W}"
        shapes[key] = {
            "rows": {
                f"staged_{mode}": _row(us_staged, pallas_sub, 3),
                f"fused_{mode}": _row(us_fused, pallas_sub, 1),
                "fused_xla_ref": _row(us_ref, "xla_ref", 0),
            },
            "roofline": {
                "fused": pixel_cascade_roofline(B, H, W, fused=True).to_row(),
                "staged": pixel_cascade_roofline(B, H, W,
                                                 fused=False).to_row(),
            },
            "derived": {
                "fused_vs_staged_speedup": round(us_staged / us_fused, 2),
                "xla_ref_vs_staged_speedup": round(us_staged / us_ref, 2),
                "launch_reduction": 3,
            },
        }
        if verbose:
            d = shapes[key]["derived"]
            print(f"pixel cascade {key} [{pallas_sub}]: staged "
                  f"{us_staged:.0f} us (3 launches) vs fused "
                  f"{us_fused:.0f} us (1 launch) -> "
                  f"{d['fused_vs_staged_speedup']}x; xla_ref "
                  f"{us_ref:.0f} us -> {d['xla_ref_vs_staged_speedup']}x")
    dev = jax.devices()[0]
    payload = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                          "count": jax.device_count()},
               "shapes": shapes}
    with open(out_path or BENCH_PIXEL_CASCADE, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    return payload


def run(verbose: bool = True):
    pallas = f"pallas_{_pallas_mode()}"
    B, H, W = 4, 96, 128
    f = [jax.random.randint(jax.random.PRNGKey(i), (B, H, W, 3), 0, 256)
         for i in range(3)]
    mask = ops.framediff(*f, threshold=40, use_pallas=False)
    conf = jax.random.uniform(jax.random.PRNGKey(9), (4096,))
    rows = []
    bytes_fd = 3 * B * H * W * 3 * 4
    rows.append(("framediff_ref", _time(ops.framediff, *f, threshold=40,
                                        use_pallas=False), bytes_fd))
    rows.append((f"framediff_{pallas}", _time(ops.framediff, *f,
                                                  threshold=40), bytes_fd))
    bytes_mo = B * H * W * 4 * 2
    rows.append(("dilate3x3_ref", _time(ops.dilate3x3, mask,
                                        use_pallas=False), bytes_mo))
    rows.append((f"dilate3x3_{pallas}", _time(ops.dilate3x3, mask),
                 bytes_mo))
    rows.append(("erode3x3_ref", _time(ops.erode3x3, mask,
                                       use_pallas=False), bytes_mo))
    bytes_tr = 4096 * 4 * 3
    rows.append(("triage_ref", _time(ops.triage, conf, alpha=0.8, beta=0.1,
                                     capacity=512, use_pallas=False), bytes_tr))
    rows.append((f"triage_{pallas}", _time(ops.triage, conf, alpha=0.8,
                                               beta=0.1, capacity=512), bytes_tr))
    # fleet triage: the whole city_scale tick (64 edges x 512-wide bucket)
    # in ONE launch — vs 64 per-edge launches per tick before
    E, N = 64, 512
    fleet_conf = jax.random.uniform(jax.random.PRNGKey(10), (E, N))
    fleet_th = jnp.stack(
        [jnp.full((E,), 0.8), jnp.full((E,), 0.1)], axis=1)
    bytes_fleet = E * N * 4 * 3 + E * 2 * 4
    rows.append(("triage_fleet_ref",
                 _time(ops.triage_fleet, fleet_conf, fleet_th, capacity=64,
                       use_pallas=False), bytes_fleet))
    rows.append((f"triage_fleet_{pallas}",
                 _time(ops.triage_fleet, fleet_conf, fleet_th, capacity=64),
                 bytes_fleet))
    # multi-query fleet: 3 live CQs x 64 edges x 512-wide buckets, the
    # whole (Q, E, N) tick in ONE Q*E-row-folded launch — vs Q per-query
    # fleet launches (the loop a naive multi-query port would run)
    Qn = 3
    mq_conf = jax.random.uniform(jax.random.PRNGKey(13), (Qn, E, N))
    mq_th = jnp.tile(fleet_th[None], (Qn, 1, 1))
    bytes_mq = Qn * E * N * 4 * 3 + Qn * E * 2 * 4
    rows.append(("triage_fleet_qen_ref",
                 _time(ops.triage_fleet, mq_conf, mq_th, capacity=64,
                       use_pallas=False), bytes_mq))
    rows.append((f"triage_fleet_qen_{pallas}",
                 _time(ops.triage_fleet, mq_conf, mq_th, capacity=64),
                 bytes_mq))
    # fleet recalibration: one fused (E, N) Platt-fit launch per update
    # event — the feedback loop's whole fleet in ONE call (vs E per-edge
    # fits).  The NumPy ref is a per-row float64 Newton loop, so here the
    # fused jnp/Pallas path is also the *algorithmically* interesting one.
    Ec, Nc = 64, 256
    cal_s = jax.random.uniform(jax.random.PRNGKey(11), (Ec, Nc))
    cal_y = (jax.random.uniform(jax.random.PRNGKey(12), (Ec, Nc))
             < cal_s).astype(jnp.float32)
    bytes_cal = Ec * Nc * 4 * 2 + Ec * 2 * 4
    rows.append(("calibrate_fleet_ref",
                 _time(ops.calibrate_fleet, cal_s, cal_y,
                       use_pallas=False, n=3), bytes_cal))
    rows.append((f"calibrate_fleet_{pallas}",
                 _time(ops.calibrate_fleet, cal_s, cal_y, n=3), bytes_cal))
    # cross-camera track association: ONE fused (M, K) QK-similarity +
    # greedy-assignment launch matches a tick's crops (all edges) against
    # the fleet-wide track table
    rng_np = np.random.default_rng(17)
    Ma, Ka, Da, Ea = 64, 128, 32, 8
    a_emb = rng_np.normal(size=(Ma, Da)).astype(np.float32)
    a_emb /= np.linalg.norm(a_emb, axis=1, keepdims=True)
    a_trk = rng_np.normal(size=(Ka, Da)).astype(np.float32)
    a_trk /= np.linalg.norm(a_trk, axis=1, keepdims=True)
    a_cq = np.zeros(Ma, np.int32)
    a_tq = np.zeros(Ka, np.int32)
    a_thr = np.full(Ma, 0.9, np.float32)
    bytes_as = (Ma * Da + Ka * Da + 2 * Ma + Ka) * 4
    rows.append(("associate_ref",
                 _time(ops.associate_tracks, a_emb, a_trk, a_cq, a_tq,
                       a_thr, use_pallas=False, n=5), bytes_as))
    rows.append((f"associate_{pallas}",
                 _time(ops.associate_tracks, a_emb, a_trk, a_cq, a_tq,
                       a_thr, n=5), bytes_as))
    # flash attention (small shape; interpret mode on CPU)
    qk = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 128, 64))
    kk = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 128, 64))
    vk = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 128, 64))
    bytes_fl = (qk.size + kk.size + vk.size) * 4
    rows.append(("flash_attn_ref", _time(ops.flash_attention, qk, kk, vk,
                                         use_pallas=False, n=5), bytes_fl))
    rows.append((f"flash_attn_{pallas}",
                 _time(ops.flash_attention, qk, kk, vk, n=5), bytes_fl))
    out = {}
    for name, us, nbytes in rows:
        gbps = nbytes / (us * 1e-6) / 1e9
        out[name] = {"us_per_call": round(us, 1), "GB_s": round(gbps, 3)}
        if verbose:
            print(f"{name:28s} {us:10.1f} us  {gbps:8.3f} GB/s")
    # the fleet kernel's headline is launch amortization: ONE (E, N) launch
    # replaces E per-edge launches every scheduler tick.  Time the actual
    # per-edge loop as the baseline.
    def _per_edge_tick(conf, th, use_pallas=True):
        return [ops.triage_batched(conf[e], alpha=float(th[e, 0]),
                                   beta=float(th[e, 1]), capacity=64,
                                   use_pallas=use_pallas)
                for e in range(conf.shape[0])]

    fleet_conf_np, fleet_th_np = (jax.device_get(fleet_conf),
                                  jax.device_get(fleet_th))
    us_loop = _time(_per_edge_tick, fleet_conf_np, fleet_th_np,
                    n=3, use_pallas=False)
    us_fleet = _time(ops.triage_fleet, fleet_conf, fleet_th, capacity=64,
                     n=3, use_pallas=False)
    # ... and the query axis: ONE fused (Q, E, N) launch vs Q (E, N)
    # fleet launches per tick (multi-query runtime's hot-path claim)
    def _per_query_tick(conf, th, use_pallas=True):
        return [ops.triage_fleet(conf[q], th[q], capacity=64,
                                 use_pallas=use_pallas)
                for q in range(conf.shape[0])]

    us_qloop = _time(_per_query_tick, mq_conf, mq_th, n=3, use_pallas=False)
    us_qfused = _time(ops.triage_fleet, mq_conf, mq_th, capacity=64,
                      n=3, use_pallas=False)
    # ... and the association axis: ONE fused launch over the whole tick's
    # crops vs the per-edge loop a naive cross-camera port would run (each
    # edge matching only ITS crops against the full track table, paying
    # one dispatch per edge)
    a_edge = rng_np.integers(0, Ea, Ma)

    def _per_edge_associate(use_pallas=False):
        outs = []
        for e in range(Ea):
            m = a_edge == e
            if m.any():
                outs.append(ops.associate_tracks(
                    a_emb[m], a_trk, a_cq[m], a_tq, a_thr[m],
                    use_pallas=use_pallas))
        return outs

    us_aloop = _time(_per_edge_associate, n=5)
    us_afused = _time(ops.associate_tracks, a_emb, a_trk, a_cq, a_tq,
                      a_thr, n=5, use_pallas=False)
    derived = {
        "fleet_launches_per_tick": 1,
        "per_edge_launches_per_tick": E,
        "fleet_launch_reduction": E,
        "fleet_tick_speedup_vs_per_edge_loop": round(us_loop / us_fleet, 2),
        "multi_query_launches_per_tick": 1,
        "per_query_launches_per_tick": Qn,
        "multi_query_launch_reduction": Qn,
        "multi_query_tick_speedup_vs_per_query_loop": round(
            us_qloop / us_qfused, 2),
        "associate_launches_per_tick": 1,
        "per_edge_associate_launches_per_tick": Ea,
        "associate_tick_speedup_vs_per_edge_loop": round(
            us_aloop / us_afused, 2),
    }
    if verbose:
        print(f"fleet tick (E={E}, N={N}): 1 launch {us_fleet:.1f} us vs "
              f"{E}-launch loop {us_loop:.1f} us -> "
              f"{derived['fleet_tick_speedup_vs_per_edge_loop']}x, "
              f"{E}x fewer launches")
        print(f"multi-query tick (Q={Qn}, E={E}, N={N}): 1 fused launch "
              f"{us_qfused:.1f} us vs {Qn}-launch per-query loop "
              f"{us_qloop:.1f} us -> "
              f"{derived['multi_query_tick_speedup_vs_per_query_loop']}x, "
              f"{Qn}x fewer launches")
        print(f"associate tick (M={Ma}, K={Ka}, D={Da}): 1 fused launch "
              f"{us_afused:.1f} us vs {Ea}-edge loop {us_aloop:.1f} us -> "
              f"{derived['associate_tick_speedup_vs_per_edge_loop']}x, "
              f"{Ea}x fewer launches")
    # scan supersteps: S scheduler ticks of Eqs. 8-9 threshold adaptation +
    # fleet triage in ONE jitted lax.scan launch (system/superstep.py) vs
    # the per-tick python loop (host threshold update + one fleet launch
    # per tick) — the metropolis host-loop claim, across fleet widths.
    # Lanes stay narrow (the per-tick per-edge batch is small at 10 Hz);
    # the axis that grows a metropolis is E.
    S_TICKS, N_LANES, CAP = 32, 64, 8
    interval = 0.1
    sup_rows = {}
    for E_sup in (64, 256, 1024):
        key_sup = jax.random.PRNGKey(20 + E_sup)
        conf_sup = jax.device_get(jax.random.uniform(
            key_sup, (S_TICKS, E_sup, N_LANES))).astype(np.float32)
        th0_sup = np.tile(np.asarray([0.8, 0.05], np.float32), (E_sup, 1))
        mask_sup = np.ones((S_TICKS, E_sup), bool)
        drain_sup = np.full(E_sup, 0.3, np.float32)
        gains_sup = np.asarray([0.2, 0.005, 0.25, interval], np.float32)

        def _python_loop_ticks(conf_sup=conf_sup, th0_sup=th0_sup,
                               drain_sup=drain_sup, E_sup=E_sup):
            th = th0_sup.copy()
            outs = []
            for s in range(S_TICKS):
                alpha = np.clip(th[:, 0] - 0.2 * (drain_sup - interval),
                                0.5, 1.0)
                th = np.stack([alpha, 0.25 * (1.0 - alpha)], axis=1)
                outs.append(ops.triage_fleet(conf_sup[s], th, capacity=CAP))
            return outs

        fn_sup = _superstep_fn(CAP, 1)
        us_loop_sup = _time(_python_loop_ticks, n=3)
        us_scan = _time(fn_sup, conf_sup, th0_sup, mask_sup, drain_sup,
                        gains_sup, n=3)
        sup_rows[f"E={E_sup}"] = {
            "python_loop_ticks_per_s": round(S_TICKS / (us_loop_sup * 1e-6),
                                             1),
            "scan_ticks_per_s": round(S_TICKS / (us_scan * 1e-6), 1),
            "speedup": round(us_loop_sup / us_scan, 2),
            "launch_reduction": S_TICKS,
        }
        if verbose:
            r = sup_rows[f"E={E_sup}"]
            print(f"superstep (S={S_TICKS}, E={E_sup}, N={N_LANES}): scan "
                  f"{r['scan_ticks_per_s']} ticks/s vs python loop "
                  f"{r['python_loop_ticks_per_s']} ticks/s -> "
                  f"{r['speedup']}x, {S_TICKS}x fewer launches")
    derived["superstep"] = sup_rows
    # fused pixel cascade: the frontend's whole framediff->dilate->erode
    # chain in ONE launch, across frame sizes and fleet widths (committed
    # snapshot: BENCH_pixel_cascade.json)
    pix_payload = pixel_cascade_bench(verbose=verbose)
    derived["pixel_cascade"] = {
        k: v["derived"] for k, v in pix_payload["shapes"].items()}
    with open(BENCH_SUPERSTEPS, "w") as fh:
        json.dump({"superstep_ticks": S_TICKS, "lanes": N_LANES,
                   "capacity": CAP, "rows": sup_rows}, fh, indent=2)
        fh.write("\n")
    # frontend throughput, fig5-style scheme comparison: the full pixel path
    # (render -> framediff -> crops -> CQ scores) vs the model-free
    # confidence stream on the same small scenario, in detections/s.  The
    # frontend cache is disabled so every timed call does the real work.
    sc = pixel_city(num_cameras=4, duration_s=3.0)
    pix = PixelFrontend(seed=0, cache=False)
    n_pix = len(pix.stream(sc))            # warm the jit caches
    # every cache-disabled stream() call does the full render/score work, so
    # time exactly ONE post-warmup call instead of _time's warmup pair
    t0 = time.perf_counter()
    pix.stream(sc)
    us_pix = (time.perf_counter() - t0) * 1e6
    n_conf = len(synthetic_confidence_stream(sc))
    us_conf = _time(synthetic_confidence_stream, sc, n=3)
    derived.update({
        "pixel_frontend_items_per_s": round(n_pix / (us_pix * 1e-6), 1),
        "confidence_frontend_items_per_s": round(
            n_conf / (us_conf * 1e-6), 1),
        "pixel_vs_confidence_throughput_ratio": round(
            (n_pix / us_pix) / (n_conf / us_conf), 6),
    })
    if verbose:
        print(f"frontend stream ({sc.num_cameras} cams, {sc.duration_s:.0f}s"
              f"): pixel {n_pix} items in {us_pix/1e6:.2f} s "
              f"({derived['pixel_frontend_items_per_s']}/s) vs confidence "
              f"{n_conf} items "
              f"({derived['confidence_frontend_items_per_s']}/s)")
    return out, derived


if __name__ == "__main__":
    run()
