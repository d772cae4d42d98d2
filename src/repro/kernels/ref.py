"""Pure-jnp/NumPy oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def framediff_ref(f0: jax.Array, f1: jax.Array, f2: jax.Array,
                  threshold: int, maxval: int = 255) -> jax.Array:
    """Paper Eqs. 1-4 on uint8-valued int32 frames (B,H,W,3) -> (B,H,W) mask.

    D1 = |f1-f0|, D2 = |f2-f1|, Da = D1 & D2 (bitwise), grayscale (BT.601
    integer weights), fixed-level threshold -> {0, maxval}.
    """
    d1 = jnp.abs(f1 - f0)
    d2 = jnp.abs(f2 - f1)
    da = jnp.bitwise_and(d1, d2)
    gray = (da[..., 0] * 299 + da[..., 1] * 587 + da[..., 2] * 114) // 1000
    return jnp.where(gray > threshold, maxval, 0).astype(f0.dtype)


def _shift2d(x: jax.Array, dy: int, dx: int, fill) -> jax.Array:
    """Shift (..., H, W) by (dy, dx), filling vacated cells."""
    H, W = x.shape[-2], x.shape[-1]
    y = jnp.roll(x, (dy, dx), axis=(-2, -1))
    if dy > 0:
        y = y.at[..., :dy, :].set(fill)
    elif dy < 0:
        y = y.at[..., dy:, :].set(fill)
    if dx > 0:
        y = y.at[..., :, :dx].set(fill)
    elif dx < 0:
        y = y.at[..., :, dx:].set(fill)
    return y


def dilate3x3_ref(x: jax.Array) -> jax.Array:
    """Paper Eq. 5: 3x3 max filter over (B,H,W) int32 (zero-padded)."""
    out = x
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            out = jnp.maximum(out, _shift2d(x, dy, dx, 0))
    return out


def erode3x3_ref(x: jax.Array, maxval: int = 255) -> jax.Array:
    """Paper Eq. 6: 3x3 min filter over (B,H,W) int32 (maxval-padded)."""
    out = x
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            out = jnp.minimum(out, _shift2d(x, dy, dx, maxval))
    return out


def pixel_cascade_ref(f0: jax.Array, f1: jax.Array, f2: jax.Array,
                      threshold: int, maxval: int = 255) -> jax.Array:
    """Jnp twin of the fused cascade: Eqs. 1-6 composed, (B,H,W) mask.

    The morphology runs as ``lax.reduce_window`` (bit-exact for integer
    max/min: window init 0 == dilate's fill since the mask is >= 0, init
    ``maxval`` == erode's fill since the mask is <= maxval).  This is
    what ``ops.pixel_cascade(use_pallas=False)`` and
    ``detect(use_pallas=False)`` run, the reference the kernel's callers
    are compared against; ``dilate3x3_ref`` and ``erode3x3_ref`` above
    are the shift-and-mask forms of the same two stencils.
    """
    m = framediff_ref(f0, f1, f2, threshold, maxval)
    win, strides = (1, 3, 3), (1, 1, 1)
    pad = ((0, 0), (1, 1), (1, 1))
    m = jax.lax.reduce_window(m, jnp.asarray(0, m.dtype),
                              jax.lax.max, win, strides, pad)
    return jax.lax.reduce_window(m, jnp.asarray(maxval, m.dtype),
                                 jax.lax.min, win, strides, pad)


def pixel_cascade_np(f0: np.ndarray, f1: np.ndarray, f2: np.ndarray,
                     threshold: int, maxval: int = 255
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Independent NumPy oracle for the fused pixel cascade.

    Deliberately NOT a composition of the jnp twins: explicit np.pad
    halos and nine-slice loops, so the parity test checks the boundary
    semantics, not a shared implementation.  Returns (mask (B, H, W)
    int32, counts (B,) int32 foreground pixels per camera).
    """
    f0, f1, f2 = (np.asarray(f, np.int64) for f in (f0, f1, f2))
    d1 = np.abs(f1 - f0)
    d2 = np.abs(f2 - f1)
    da = np.bitwise_and(d1, d2)
    gray = (da[..., 0] * 299 + da[..., 1] * 587 + da[..., 2] * 114) // 1000
    m = np.where(gray > threshold, maxval, 0)
    B, H, W = m.shape

    def morph(x, red, fill):
        xp = np.pad(x, ((0, 0), (1, 1), (1, 1)), constant_values=fill)
        acc = None
        for dy in range(3):
            for dx in range(3):
                sl = xp[:, dy:dy + H, dx:dx + W]
                acc = sl if acc is None else red(acc, sl)
        return acc

    m = morph(m, np.maximum, 0)
    m = morph(m, np.minimum, maxval)
    mask = m.astype(np.int32)
    return mask, (mask > 0).sum(axis=(1, 2)).astype(np.int32)


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array,
            causal: bool = True) -> jax.Array:
    """Unfused GQA attention oracle.  q (B,H,Sq,hd), k/v (B,KV,Sk,hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qr = q.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qr, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(hd))
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def triage_ref(conf: jax.Array, alpha: float, beta: float,
               capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Cascade triage + stable compaction of escalated indices.

    conf (N,) f32 -> routes (N,) int32 {0 accept,1 reject,2 escalate},
    slots (N,) int32 (slot in the escalation buffer, or -1),
    count () int32.
    """
    routes = jnp.where(conf > alpha, 0,
                       jnp.where(conf < beta, 1, 2)).astype(jnp.int32)
    esc = routes == 2
    pos = jnp.cumsum(esc.astype(jnp.int32)) - 1
    slots = jnp.where(esc & (pos < capacity), pos, -1).astype(jnp.int32)
    return routes, slots, jnp.sum(esc.astype(jnp.int32))


def triage_fleet_ref(conf: jax.Array, thresholds: jax.Array,
                     capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row triage over the whole fleet's tick matrix.

    conf (..., N) f32 with thresholds (..., 2) f32 [alpha, beta] per row ->
    routes (..., N) int32, slots (..., N) int32 (per-row stable compaction,
    each row's escalation buffer capped at ``capacity``), counts (...,)
    int32.  The leading axes are arbitrary: (E, N) for the single-query
    fleet, (Q, E, N) for the multi-query fleet — every (query, edge) pair
    is an independent row with its own thresholds and its own buffer.
    """
    alpha = thresholds[..., 0:1]
    beta = thresholds[..., 1:2]
    routes = jnp.where(conf > alpha, 0,
                       jnp.where(conf < beta, 1, 2)).astype(jnp.int32)
    esc = routes == 2
    pos = jnp.cumsum(esc.astype(jnp.int32), axis=-1) - 1
    slots = jnp.where(esc & (pos < capacity), pos, -1).astype(jnp.int32)
    return routes, slots, jnp.sum(esc.astype(jnp.int32), axis=-1)


def calibrate_fleet_ref(scores: np.ndarray, truths: np.ndarray,
                        iters: int, min_count: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy twin of ``calibrate.calibrate_fleet_pallas``: per-edge Platt fit.

    Deliberately an *independent* implementation (float64, explicit per-row
    Newton loop) so the parity test checks the numerics, not the layout:
    scores (E, N) with pad lanes -1.0, truths (E, N) {0, 1} ->
    (params (E, 2) [a, b], counts (E,) valid labels).  A (Q, E, N) input
    folds its leading axes to Q·E independent rows — same contract as the
    fused kernel's query axis — and returns (Q, E, 2)/(Q, E).  Constants
    (clip epsilon, ridge, clamps) mirror ``kernels/calibrate.py``.
    """
    from repro.kernels.calibrate import A_MAX, A_MIN, B_MAX, EPS, PRIOR
    scores = np.asarray(scores, np.float64)
    truths = np.asarray(truths, np.float64)
    if scores.ndim == 3:
        lead = scores.shape[:2]
        params, counts = calibrate_fleet_ref(
            scores.reshape(-1, scores.shape[-1]),
            truths.reshape(-1, truths.shape[-1]), iters, min_count)
        return params.reshape(*lead, 2), counts.reshape(lead)
    E = scores.shape[0]
    params = np.tile(np.asarray([1.0, 0.0]), (E, 1))
    counts = np.zeros(E, np.int32)
    for e in range(E):
        valid = scores[e] >= 0.0
        counts[e] = int(valid.sum())
        y01 = truths[e, valid]
        n_pos = y01.sum()
        if counts[e] < min_count or n_pos < 1 or n_pos > counts[e] - 1:
            continue
        n_neg = counts[e] - n_pos
        # Platt target smoothing, same constants as the kernel
        y = np.where(y01 > 0.5, (n_pos + 1.0) / (n_pos + 2.0),
                     1.0 / (n_neg + 2.0))
        c = np.clip(scores[e, valid], EPS, 1.0 - EPS)
        x = np.log(c / (1.0 - c))
        a, b = 1.0, 0.0
        for _ in range(iters):
            p = 1.0 / (1.0 + np.exp(-(a * x + b)))
            g0 = float(np.sum((p - y) * x)) + PRIOR * (a - 1.0)
            g1 = float(np.sum(p - y)) + PRIOR * b
            w = p * (1.0 - p)
            h00 = float(np.sum(w * x * x)) + PRIOR
            h01 = float(np.sum(w * x))
            h11 = float(np.sum(w)) + PRIOR
            det = h00 * h11 - h01 * h01
            a = float(np.clip(a - (h11 * g0 - h01 * g1) / det, A_MIN, A_MAX))
            b = float(np.clip(b - (h00 * g1 - h01 * g0) / det, -B_MAX, B_MAX))
        params[e] = (a, b)
    return params.astype(np.float32), counts


def associate_tracks_ref(emb: np.ndarray, trk: np.ndarray,
                         crop_q: np.ndarray, trk_q: np.ndarray,
                         thr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy twin of ``similarity.associate_pallas``: greedy re-ID matching.

    Deliberately an *independent* implementation (explicit per-crop python
    loop and a mutable claimed set instead of the kernel's vectorized
    one-hot ``fori_loop``) so the parity test checks the matching
    semantics, not a shared implementation.  emb (M, D) and trk (K, D)
    L2-normalized float32, crop_q (M,) / trk_q (K,) int32 query ids, thr
    (M,) per-crop acceptance floors -> (assign (M,) int32 track row index
    or -1, sim (M,) float32 best available score, -1e30 when the crop's
    query has no unclaimed track).  Crops match greedily in row order,
    one-to-one, only within their own query id.
    """
    emb = np.asarray(emb, np.float32)
    trk = np.asarray(trk, np.float32)
    crop_q = np.asarray(crop_q, np.int32)
    trk_q = np.asarray(trk_q, np.int32)
    thr = np.asarray(thr, np.float32)
    M = emb.shape[0]
    K = trk.shape[0]
    assign = np.full(M, -1, np.int32)
    sim = np.full(M, np.float32(-1e30), np.float32)
    if K == 0:
        return assign, sim
    s = emb @ trk.T                                     # (M, K) float32
    s = np.where(crop_q[:, None] == trk_q[None, :], s, np.float32(-1e30))
    claimed = np.zeros(K, bool)
    for i in range(M):
        avail = np.where(claimed, np.float32(-1e30), s[i])
        j = int(np.argmax(avail))
        sim[i] = avail[j]
        if avail[j] >= thr[i]:
            assign[i] = j
            claimed[j] = True
    return assign, sim
