"""Pallas TPU kernel: fused re-ID similarity + greedy track association.

Cross-camera track queries match every detection crop's embedding against
the fleet-wide live track table once per scheduler tick.  This kernel
fuses the whole match into ONE launch — the same per-tick budget
discipline as ``triage.triage_fleet_pallas``:

  1. batched QK-style scores: ``s = emb @ trk.T`` over L2-normalized
     embeddings (cosine similarity), computed exactly like the
     ``flash_attention`` kernel's query-key score step, with the same
     ``NEG_INF`` masking discipline — here the mask is query identity
     (a crop may only match tracks of its OWN query), which is also what
     lets every live track query share one launch per tick;
  2. greedy one-to-one assignment folded into the same launch: crops
     claim tracks in arrival order (a ``fori_loop`` carrying the claimed
     set), each taking the best *unclaimed* track of its query, and
     matching only if that best score clears the crop's own threshold
     row (per-crop thresholds are how warm/cold edge state reaches the
     kernel as data, not trace constants).

Unlike attention's long sequences, a fleet's live track table is tiny
(hundreds of rows, not tens of thousands), so the whole problem is one
VMEM-resident block — whole-block ``BlockSpec``s like the fleet-triage
kernel rather than a ``flash_attention``-style K-block grid; the inputs
for the ``vehicle_pursuit`` operating point are a few KB.

Inputs are bucket-padded by the ``ops.associate_tracks`` wrapper
(``buckets.py`` discipline): pad crops carry query id -1, pad tracks
query id -2 — the ids can never be equal, so pad rows are masked
everywhere and can neither match nor be claimed.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

#: flash-attention's additive-mask value, reused as the "impossible match"
#: score (a masked pair can never clear a threshold in (0, 2])
NEG_INF = -1e30


def _associate_kernel(emb_ref, trk_ref, cq_ref, tq_ref, thr_ref,
                      assign_ref, sim_ref):
    """One fused score + greedy-assign pass.

    emb (M, D) crop embeddings, trk (K, D) track embeddings (both
    L2-normalized by the wrapper), cq (M, 1) / tq (1, K) int32 query ids,
    thr (M, 1) per-crop acceptance floors -> assign (M, 1) int32 (track
    row index or -1) and sim (M, 1) f32 (the best *available* score each
    crop saw, ``NEG_INF`` when nothing of its query was unclaimed).

    Every operand and carry is 2-D — column vectors for per-crop data,
    row vectors for per-track data, the claimed set as an int32 row — so
    Mosaic never has to reshape a 1-D vector.  The greedy loop is fully
    vectorized (one-hot row selects, first-index argmax by a masked min),
    so the same body lowers compiled and interpreted.
    """
    emb = emb_ref[...]                         # (M, D)
    trk = trk_ref[...]                         # (K, D)
    thr = thr_ref[...]                         # (M, 1)
    M = emb.shape[0]
    K = trk.shape[0]
    # full f32 precision: scores are compared against acceptance floors,
    # so a bf16 pass on the MXU could flip a match the oracle keeps
    s = jax.lax.dot_general(emb, trk, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (M, K)
    s = jnp.where(cq_ref[...] == tq_ref[...], s, NEG_INF)
    rows = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def body(i, carry):
        claimed, assign, sim = carry
        onei = rows == i                                          # (M, 1)
        row = jnp.sum(jnp.where(onei, s, 0.0), axis=0,
                      keepdims=True)                              # s[i]
        thr_i = jnp.sum(jnp.where(onei, thr, 0.0))
        avail = jnp.where(claimed > 0, NEG_INF, row)
        best = jnp.max(avail)
        j = jnp.min(jnp.where(avail == best, cols, K))   # first argmax
        ok = best >= thr_i
        claimed = jnp.where((cols == j) & ok, 1, claimed)
        assign = jnp.where(onei, jnp.where(ok, j, -1), assign)
        sim = jnp.where(onei, best, sim)
        return claimed, assign, sim

    _, assign, sim = jax.lax.fori_loop(
        0, M, body,
        (jnp.zeros((1, K), jnp.int32),
         jnp.full((M, 1), -1, jnp.int32),
         jnp.full((M, 1), NEG_INF, jnp.float32)))
    assign_ref[...] = assign
    sim_ref[...] = sim


def associate_pallas(emb: jax.Array, trk: jax.Array, crop_q: jax.Array,
                     trk_q: jax.Array, thr: jax.Array, *,
                     interpret: Optional[bool] = None):
    """emb (M, D) f32, trk (K, D) f32, crop_q (M,) i32, trk_q (K,) i32,
    thr (M,) f32 -> (assign (M,) i32, sim (M,) f32)."""
    interpret = resolve_interpret(interpret)
    M, D = emb.shape
    K = trk.shape[0]
    whole = lambda r, c: pl.BlockSpec((r, c), lambda: (0, 0))  # noqa: E731
    assign, sim = pl.pallas_call(
        _associate_kernel,
        in_specs=[whole(M, D), whole(K, D), whole(M, 1), whole(1, K),
                  whole(M, 1)],
        out_specs=(whole(M, 1), whole(M, 1)),
        out_shape=(jax.ShapeDtypeStruct((M, 1), jnp.int32),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)),
        interpret=interpret,
    )(emb, trk, crop_q.reshape(M, 1), trk_q.reshape(1, K), thr.reshape(M, 1))
    return assign[:, 0], sim[:, 0]
