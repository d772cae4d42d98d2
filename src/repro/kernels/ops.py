"""Jit'd public wrappers for the Pallas kernels (padding, dtype, dispatch).

The wrappers never pass ``interpret``: each launcher asks
``repro.kernels.runtime.resolve_interpret``, which compiles on a TPU
backend and interprets on the CPU one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import buckets as _bk
from repro.kernels import calibrate as _ca
from repro.kernels import ccl as _ccl
from repro.kernels import flash_attention as _fa
from repro.kernels import pixel_cascade as _pc
from repro.kernels import similarity as _sim
from repro.kernels import triage as _tr
from repro.kernels import ref as _ref


@functools.partial(jax.jit,
                   static_argnames=("threshold", "maxval", "use_pallas"))
def pixel_cascade(f0: jax.Array, f1: jax.Array, f2: jax.Array, *,
                  threshold: int = 40, maxval: int = 255,
                  use_pallas: bool = True):
    """Whole pixel frontend — framediff → dilate → erode → count — in ONE
    Pallas launch per tick.

    Frames are (B, H, W, 3) uint8/int; returns ``(mask (B, H, W) int32,
    counts (B,) int32)`` where ``counts[b]`` is camera b's foreground pixel
    count — the reduction ``detect`` uses to skip connected-component
    labeling for motionless cameras without a second pass over the mask.

    ``use_pallas=False`` runs the jnp reference ``ref.pixel_cascade_ref``
    plus a mask reduction.  Frames are zero-padded to the
    (FRAME_BAND_H, FRAME_LANE_W) tile from ``kernels/buckets.py`` before
    the launch, with the colour channels moved to a leading plane axis;
    the pad is sliced back off and never reaches counts.
    """
    f0, f1, f2 = (x.astype(jnp.int32) for x in (f0, f1, f2))
    if not use_pallas:
        mask = _ref.pixel_cascade_ref(f0, f1, f2, threshold, maxval)
        return mask, jnp.sum(mask > 0, axis=(1, 2)).astype(jnp.int32)
    H, W = f0.shape[1], f0.shape[2]
    with jax.named_scope("pixel_cascade"):
        mask, counts = _pc.pixel_cascade_pallas(
            *(_pc.planar_frames(x) for x in (f0, f1, f2)),
            threshold=threshold, maxval=maxval, true_hw=(H, W))
    return mask[:, :H, :W], counts


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def label_components(mask: jax.Array, use_pallas: bool = True):
    """Connected-component labels of a (B, H, W) mask, 8-connected:
    ``(labels (B, H, W) int32, steps (B,) int32)``, -1 for background and
    the min linear index ``y * W + x`` of its component for foreground;
    ``steps[b]`` counts camera b's fixpoint steps.

    One launch labels every camera, each in VMEM from its mask to its
    own convergence (``kernels/ccl.py``), when the frame's planes fit in
    VMEM (``ccl.fits``); otherwise, and with ``use_pallas=False``, the
    jnp fixpoint ``ccl.label_components`` runs, the oracle the kernel is
    tested bit-exact against.
    """
    H, W = mask.shape[1], mask.shape[2]
    if use_pallas and _ccl.fits(H, W):
        return _ccl.label_components_pallas(mask)
    return _ccl.label_components(mask)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "use_pallas"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = True) -> jax.Array:
    """Fused attention.  q (B,H,Sq,hd), k/v (B,KV,Sk,hd) -> (B,H,Sq,hd).

    Pads Sq/Sk up to block multiples; padded K positions are masked by the
    causal rule (padded keys sit after all queries) or, for non-causal
    inputs, by padding K with -inf-free zeros and masking via length.
    """
    if not use_pallas:
        return _ref.mha_ref(q, k, v, causal)
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pk and (not causal or Sq > Sk):
        # padded keys would be visible to real queries; fall back
        return _ref.mha_ref(q, k, v, causal)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    out = _fa.flash_attention_pallas(qp, kp, vp, causal=causal,
                                     block_q=min(block_q, qp.shape[2]),
                                     block_k=min(block_k, kp.shape[2]))
    return out[:, :, :Sq]


# padding-bucket arithmetic lives in ``kernels/buckets.py`` (jax-free, so
# the scenario layer can validate fleet dims against the same table);
# these aliases keep the wrappers' call sites and the historical names
_bucket = _bk.bucket


def score_crops(score_fn, tokens, *, minimum: int = 8) -> np.ndarray:
    """Bucket-padded per-tick crop scoring: ONE classifier launch per tick.

    ``tokens`` is the (N, T) patch-token matrix of every motion crop the
    whole camera fleet produced this scheduler tick and ``score_fn`` a jit'd
    ``(N, T) tokens -> (N,) confidences`` model apply.  The tokens are
    written into a (bucket, T) buffer, N rounded up to a power of two (min
    8), which is what the one launch uploads — the same padding contract
    as ``triage_fleet`` — and the N real scores are read back: the only
    program is ``score_fn`` at the bucket's shape, so a run's stream of
    varying tick batches compiles once per bucket and never per N.  Pad
    rows carry token 0; their scores never leave this function.  Returns
    a host (N,) array.
    """
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    padded = np.zeros((_bucket(n, minimum), tokens.shape[1]), np.int32)
    padded[:n] = tokens
    return np.asarray(score_fn(padded))[:n]


@functools.partial(jax.jit, static_argnames=("capacity", "use_pallas"))
def _triage_fleet(conf: jax.Array, thresholds: jax.Array, *, capacity: int,
                  use_pallas: bool = True):
    if not use_pallas:
        return _ref.triage_fleet_ref(conf, thresholds, capacity)
    with jax.named_scope("triage_fleet"):
        return _tr.triage_fleet_pallas(conf, thresholds, capacity=capacity)


_bucket_q = _bk.bucket_q


def triage_fleet(conf, thresholds, *, capacity: int,
                 use_pallas: bool = True):
    """Whole-fleet per-tick triage: ONE kernel launch for every edge —
    and, with a query axis, for every live query on every edge.

    2D: ``conf`` is the (E, N) tick matrix — row e holds edge e's
    detections this scheduler tick, right-padded with -1.0 where edges saw
    fewer than N — and ``thresholds`` the (E, 2) per-edge runtime
    [alpha, beta] from each edge's own Eqs. 8-9 state.  Returns (routes
    (E, N), slots (E, N), counts (E,)); compaction and the ``capacity``
    clamp are per edge row.

    3D: ``conf`` (Q, E, N) with ``thresholds`` (Q, E, 2) — one row per
    (live query, edge) pair, each with its OWN Eqs. 8-9 threshold state
    and its own escalation buffer.  The rows are Q·E-folded onto the 2D
    layout (the query axis counted at its power-of-two bucket), so ALL
    live queries across ALL edges still cost ONE launch per scheduler
    tick; outputs come back (Q, E, N)/(Q, E).  Per-row compaction is
    unchanged by the fold — each (query, edge) keeps a private buffer.

    The rows and lanes are written into a buffer padded up to
    power-of-two buckets (min 8), which is what the launch uploads, and
    the real rows and lanes are read back: the only program is the kernel
    at the bucket's shape, so a run's stream of tick matrices compiles
    once per bucket and never per shape.  Pad lanes use conf=-1.0, which
    always routes to 'reject' (beta >= 0) and therefore can never claim an
    escalation slot or count; pad rows get thresholds (1, 0) for the same
    reason.  Returns host arrays.
    """
    conf = np.asarray(conf, np.float32)
    thresholds = np.asarray(thresholds, np.float32)
    lead, n = conf.shape[:-1], conf.shape[-1]
    rows = int(np.prod(lead))
    folded = _bucket_q(lead[0]) * lead[1] if conf.ndim == 3 else rows
    pad_conf = np.full((_bucket(folded), _bucket(n)), -1.0, np.float32)
    pad_conf[:rows, :n] = conf.reshape(rows, n)
    pad_th = np.tile(np.asarray([1.0, 0.0], np.float32),
                     (pad_conf.shape[0], 1))
    pad_th[:rows] = thresholds.reshape(rows, 2)
    routes, slots, counts = jax.device_get(_triage_fleet(
        pad_conf, pad_th, capacity=capacity, use_pallas=use_pallas))
    return (routes[:rows, :n].reshape(conf.shape),
            slots[:rows, :n].reshape(conf.shape),
            counts[:rows].reshape(lead))


@functools.partial(jax.jit, static_argnames=("iters", "min_count"))
def _calibrate_fleet_pallas(scores: jax.Array, truths: jax.Array, *,
                            iters: int, min_count: int):
    with jax.named_scope("calibrate_fleet"):
        return _ca.calibrate_fleet_pallas(scores, truths, iters=iters,
                                          min_count=min_count)


def calibrate_fleet(scores, truths, *, iters: int = 8, min_count: int = 8,
                    use_pallas: bool = True):
    """Fleet-wide Platt recalibration: ONE fused launch per update event.

    ``scores`` is the (E, N) matrix of cloud-labeled edge confidences —
    row e holds edge e's buffered escalation scores, right-padded with
    -1.0 — and ``truths`` the matching (E, N) 0/1 cloud verdicts.  Returns
    (params (E, 2) [a, b] of ``conf' = sigmoid(a*logit(conf)+b)``, counts
    (E,) valid labels per edge).  Rows with fewer than ``min_count``
    labels, or labels all one class, come back as the identity (1, 0).

    3D: ``scores``/``truths`` (Q, E, N) — one row per (live query, edge)
    pair, query-axis bucket-padded then Q·E-row-folded onto the 2D layout
    (pad rows fully masked, fit to the identity), so a multi-query fleet's
    whole recalibration is still ONE launch per update event; ``params``
    comes back (Q, E, 2) and ``counts`` (Q, E).

    Both trailing axes are padded up to power-of-two buckets (min 8)
    before the launch — the same jit-cache contract as ``triage_fleet`` —
    then the pads are sliced back off.  Pad lanes use score=-1.0 and are
    masked out of every reduction; pad edge rows are fully masked and
    therefore fit to the identity.  The ``use_pallas=False`` path
    dispatches to the independent NumPy oracle
    (``ref.calibrate_fleet_ref``) outside jit.
    """
    scores = jnp.asarray(scores, jnp.float32)
    truths = jnp.asarray(truths, jnp.float32)
    if scores.ndim == 3:
        Q, E, n = scores.shape
        qb = _bucket_q(Q)
        if qb != Q:
            scores = jnp.pad(scores, ((0, qb - Q), (0, 0), (0, 0)),
                             constant_values=-1.0)
            truths = jnp.pad(truths, ((0, qb - Q), (0, 0), (0, 0)))
        params, counts = calibrate_fleet(
            scores.reshape(qb * E, n), truths.reshape(qb * E, n),
            iters=iters, min_count=min_count, use_pallas=use_pallas)
        return (jnp.reshape(jnp.asarray(params), (qb, E, 2))[:Q],
                jnp.reshape(jnp.asarray(counts), (qb, E))[:Q])
    E, n = scores.shape
    eb, nb = _bucket(E), _bucket(n)
    if nb != n:
        scores = jnp.pad(scores, ((0, 0), (0, nb - n)), constant_values=-1.0)
        truths = jnp.pad(truths, ((0, 0), (0, nb - n)))
    if eb != E:
        scores = jnp.pad(scores, ((0, eb - E), (0, 0)), constant_values=-1.0)
        truths = jnp.pad(truths, ((0, eb - E), (0, 0)))
    if not use_pallas:
        params, counts = _ref.calibrate_fleet_ref(
            np.asarray(scores), np.asarray(truths), iters, min_count)
    else:
        params, counts = _calibrate_fleet_pallas(
            scores, truths, iters=iters, min_count=min_count)
    return params[:E], counts[:E]


@jax.jit
def _associate_pallas(emb, trk, crop_q, trk_q, thr):
    with jax.named_scope("associate_tracks"):
        return _sim.associate_pallas(emb, trk, crop_q, trk_q, thr)


def associate_tracks(emb, trk, crop_q, trk_q, thr, *,
                     use_pallas: bool = True):
    """Fleet-wide re-ID association: ONE fused launch per scheduler tick.

    ``emb`` is the (M, D) matrix of every detection-crop embedding the
    whole fleet produced this tick (L2-normalize upstream — scores are
    cosines) and ``trk`` the (K, D) live track table across ALL track
    queries; ``crop_q`` (M,) / ``trk_q`` (K,) carry each row's query id
    (crops only ever match tracks of their own query, which is what lets
    every live track query share the single launch) and ``thr`` (M,) the
    per-crop acceptance floor — warm/cold edge state reaches the kernel as
    data, not trace constants, same contract as ``triage_fleet``'s runtime
    thresholds.  Crops claim tracks greedily in row order, one-to-one.

    Returns (assign (M,) int32 — the matched row index into the UNPADDED
    ``trk``, or -1 — and sim (M,) float32, the best still-unclaimed score
    the crop saw, -1e30 when its query had none).

    M, K, and D are padded up to power-of-two buckets (min 8) before the
    launch — the ``triage_fleet`` jit-cache contract — then the pads are
    sliced back off.  Pad crops carry query id -1 and pad tracks -2, so a
    pad row can never match or be claimed (real ids are >= 0); pad crops
    are appended AFTER the real rows, so the greedy claim order of real
    crops is unchanged by padding.  ``use_pallas=False`` dispatches to the
    independent NumPy oracle (``ref.associate_tracks_ref``) outside jit.
    """
    emb = jnp.asarray(emb, jnp.float32)
    trk = jnp.asarray(trk, jnp.float32)
    crop_q = jnp.asarray(crop_q, jnp.int32)
    trk_q = jnp.asarray(trk_q, jnp.int32)
    thr = jnp.asarray(thr, jnp.float32)
    M, D = emb.shape
    K = trk.shape[0]
    mb, kb, db = _bucket(M), _bucket(K), _bucket(D)
    if db != D:
        emb = jnp.pad(emb, ((0, 0), (0, db - D)))
        trk = jnp.pad(trk, ((0, 0), (0, db - D)))
    if mb != M:
        emb = jnp.pad(emb, ((0, mb - M), (0, 0)))
        crop_q = jnp.pad(crop_q, (0, mb - M), constant_values=-1)
        thr = jnp.pad(thr, (0, mb - M), constant_values=2.0)
    if kb != K:
        trk = jnp.pad(trk, ((0, kb - K), (0, 0)))
        trk_q = jnp.pad(trk_q, (0, kb - K), constant_values=-2)
    if not use_pallas:
        assign, sim = _ref.associate_tracks_ref(
            np.asarray(emb), np.asarray(trk), np.asarray(crop_q),
            np.asarray(trk_q), np.asarray(thr))
        return jnp.asarray(assign)[:M], jnp.asarray(sim)[:M]
    assign, sim = _associate_pallas(emb, trk, crop_q, trk_q, thr)
    return assign[:M], sim[:M]
